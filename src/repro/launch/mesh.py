"""Production mesh factory.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state — crucial because the dry-run
inflates the host platform to 512 placeholder devices and everything else
(tests, benches, the CPU FL sim) must see the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the simulator and the
    dry-runs place arrays with NamedShardings and let GSPMD propagate
    them, which the ``Explicit`` default of newer JAX rejects (a gather on
    a pod-sharded operand then needs an explicit ``out_sharding``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips — the ``pod`` axis is
    the federation axis (DESIGN.md §3)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for CPU tests of the same code paths."""
    return _mesh((1, 1), ("data", "model"))


def make_fl_mesh(pods: int = 1):
    """Federation-only mesh: a single ``pod`` axis carrying the stacked
    client dimension. pods=1 runs on one real device (the CPU sim's
    mesh-aware mode); pods>1 needs that many (possibly fake) devices."""
    return _mesh((pods,), ("pod",))


def make_fl_smoke_mesh():
    """(pod=2, data=2, model=1) — the smallest mesh that still exercises
    cross-pod collectives in the sharded FL dry-run on CPU CI (4 fake
    devices via --xla_force_host_platform_device_count)."""
    return _mesh((2, 2, 1), ("pod", "data", "model"))
