"""Streaming Pearson-correlation Pallas kernel.

Problem: K client parameter vectors of length M (M up to tens of billions
at pod scale) -> K x K correlation matrix. A naive implementation
standardizes a copy of X (one extra full read+write of HBM) and then runs a
GEMM. This kernel fuses both: each grid step loads one (K, m_blk) tile into
VMEM once and accumulates

    gram  += X_blk @ X_blk^T        (MXU, K padded to sublane multiple)
    sums  += row-sum(X_blk)          (VPU)

so the whole computation is a single pass over HBM at arithmetic intensity
~K flops/byte. Correlation finalization (tiny, K x K) happens in ops.py.

Inputs may be bf16 (the at-scale one-pass mode): the cast to f32 happens in
VMEM, so HBM traffic is halved while both accumulators stay f32.

Grid: (M / m_blk,) — sequential on TPU, so the accumulators in the output
VMEM blocks persist across steps; they are zeroed at step 0 via pl.when.
The whole (Kp, Kp) gram stays resident at every K, so X streams once.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

M_BLK = 2048  # lane-multiple block of the feature axis; (16, 2048) f32 = 128 KiB
# Scoped VMEM for one grid step. The default (16 MiB on v5e) is refused at
# K=1024: the double-buffered (1024, 1024) f32 gram and (1024, 2048) input
# tile need about 30 MiB. v5e has 128 MiB of VMEM; 64 MiB holds K=1024 in
# f32 and bf16 at the full M_BLK (tests/test_tpu_compile.py).
VMEM_LIMIT = 64 << 20


def sublane(dtype) -> int:
    """Minimum second-to-last tile dim for ``dtype`` (f32 8, bf16 16)."""
    return 16 if dtype == jnp.bfloat16 else 8


def _kernel(x_ref, gram_ref, sums_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        gram_ref[...] = jnp.zeros_like(gram_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    x = x_ref[...].astype(jnp.float32)            # (Kp, m_blk)
    # MXU: (Kp, m_blk) @ (m_blk, Kp)
    gram_ref[...] += jax.lax.dot_general(
        x, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    sums_ref[...] += jnp.sum(x, axis=1, keepdims=True)


def pearson_accumulate(X: jnp.ndarray, interpret: Optional[bool] = None,
                       m_blk: int = M_BLK):
    """X: (Kp, Mp) with Kp a sublane multiple for X.dtype and Mp a multiple
    of ``m_blk`` (ops.py pads). Returns (gram (Kp,Kp), sums (Kp,1)) in f32.

    Zero columns of padding contribute nothing to either accumulator, so the
    caller can pad each streamed chunk independently and still divide by the
    true column count at finalization. ``interpret`` forces Pallas interpret
    mode (a test hook); by default it follows the platform.
    """
    Kp, Mp = X.shape
    assert Kp % sublane(X.dtype) == 0 and Mp % m_blk == 0, (Kp, Mp, m_blk)
    n_blk = Mp // m_blk
    return pl.pallas_call(
        _kernel,
        grid=(n_blk,),
        in_specs=[pl.BlockSpec((Kp, m_blk), lambda i: (0, i))],
        out_specs=[
            pl.BlockSpec((Kp, Kp), lambda i: (0, 0)),
            pl.BlockSpec((Kp, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Kp, Kp), jnp.float32),
            jax.ShapeDtypeStruct((Kp, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
        name="pearson_gram",
    )(X)
