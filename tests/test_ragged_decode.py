"""Ragged decode at the layer level: ``models/layers.attention_decode``
with a per-row ``length`` vector, the contiguous cache that the sequential
``launch.serve.generate`` oracle decodes through, must match running each
row as its own batch-1 call (full and sliding-window caches).

The engine-level parity of the paged serving engine against that oracle
is in tests/test_paged_kv.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.serving.fl_model import serve_config


def _ragged_attention_case(window: int, seed: int):
    """One batch-5 call against five batch-1 calls in f32. The written
    ``length`` must match exactly. ``y`` and the written ``k`` are compared
    to 1e-5 absolute and relative: the two programs are different XLA
    computations, whose reductions may run in another order. On the CPU
    the largest difference read is 7.7e-7 in ``y`` (values up to 1.6)
    and 1.7e-6 in ``k``, a few f32 ulps, so 1e-5 is about six times the
    largest reading. A row that writes one slot off, or that drops its
    mask, moves ``k`` or ``y`` by O(1)."""
    cfg = serve_config("qwen3-1.7b")
    if window:
        cfg = dataclasses.replace(cfg, window_size=window)
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    p = L.attention_init(key, cfg, jnp.float32)
    B, C = 5, 12
    cache = L.attention_init_cache(cfg, B, C, jnp.float32)
    # arbitrary per-row depths, including 0 (a dead lane) and C (full ring)
    lengths = np.asarray([0, 1, C // 2, C - 1, C], np.int32)[:B]
    cache["k"] = jnp.asarray(
        rng.normal(size=cache["k"].shape).astype(np.float32))
    cache["v"] = jnp.asarray(
        rng.normal(size=cache["v"].shape).astype(np.float32))
    cache["length"] = jnp.asarray(lengths)
    x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)).astype(np.float32))
    pos = jnp.asarray(lengths)  # pos == length on every production path

    y, new = L.attention_decode(p, cfg, x, cache, pos)
    for b in range(B):
        row = {k: v[b:b + 1] for k, v in cache.items()}
        yb, nb = L.attention_decode(p, cfg, x[b:b + 1], row, pos[b:b + 1])
        np.testing.assert_allclose(np.asarray(y[b]), np.asarray(yb[0]),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(new["length"][b]), np.asarray(nb["length"][0]))
        np.testing.assert_allclose(np.asarray(new["k"][b]),
                                   np.asarray(nb["k"][0]),
                                   atol=1e-5, rtol=1e-5)


def test_ragged_attention_decode_rowwise_full():
    _ragged_attention_case(window=0, seed=0)


def test_ragged_attention_decode_rowwise_windowed():
    # window < cache depth: the ring-buffer path
    _ragged_attention_case(window=8, seed=1)
