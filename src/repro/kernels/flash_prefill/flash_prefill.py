"""Flash-attention prefill Pallas kernel (causal / sliding-window GQA).

This is the TPU-native endgame of §Perf H1: the baseline attention's
memory term (1027 s on yi-34b x prefill_32k) is entirely (q_blk, T) f32
score rows written to HBM; here scores live only in VMEM.

GQA packing: all G query heads of one KV head are folded into the q-block
row axis, so the score matmul is one (G*Q_BLK, D) x (D, KV_BLK) MXU op per
tile (G*Q_BLK is a multiple of 8 by construction; D padded to lane
multiples by ops.py).

Grid: (B, Kv, nQ, nKV) — nKV innermost/sequential, so the online-softmax
state (m, l, acc) persists in VMEM scratch across the KV sweep of each
query tile; output is written once at the last KV step. Tiles entirely
outside the causal frontier or the sliding window are statically skipped
via pl.when (compute AND the k/v tile fetches for them are elided by
Mosaic's revisiting rules on TPU; in interpret mode they simply don't
execute).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

Q_BLK = 128
KV_BLK = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, G, causal, window, s_valid, scale):
    qi = pl.program_id(2)
    kv = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(kv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = qi * Q_BLK
    kv_lo = kv * KV_BLK
    # static-ish tile culling (q_lo, kv_lo are grid-index affine)
    beyond_causal = causal and True  # mask handles partial tiles
    run = (kv_lo < s_valid)
    if causal:
        run = jnp.logical_and(run, kv_lo <= q_lo + Q_BLK - 1)
    if window > 0:
        run = jnp.logical_and(run, kv_lo + KV_BLK - 1 > q_lo - window)

    @pl.when(run)
    def _tile():
        q = q_ref[0, 0, 0].astype(jnp.float32)        # (G*Q_BLK, D)
        k = k_ref[0, 0].astype(jnp.float32)           # (KV_BLK, D)
        v = v_ref[0, 0].astype(jnp.float32)           # (KV_BLK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                      # (G*Q_BLK, KV_BLK)

        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        q_pos = q_lo + jnp.mod(rows, Q_BLK)
        k_pos = kv_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = k_pos < s_valid
        if causal:
            ok &= k_pos <= q_pos
        if window > 0:
            ok &= k_pos > q_pos - window
        s = jnp.where(ok, s, -1e30)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(kv == n_kv - 1)
    def _finalize():
        o_ref[0, 0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def flash_prefill(q, k, v, *, causal: bool, window: int, s_valid: int,
                  scale: float, interpret: Optional[bool] = None):
    """q: (B, Kv, nQ, G*Q_BLK, D); k, v: (B, Kv, Sp, D); Sp % KV_BLK == 0.
    Returns o shaped like q."""
    B, Kv, nQ, GQ, D = q.shape
    Sp = k.shape[2]
    assert GQ % 8 == 0 and Sp % KV_BLK == 0, (GQ, Sp)
    grid = (B, Kv, nQ, Sp // KV_BLK)
    kern = functools.partial(
        _kernel, G=GQ // Q_BLK, causal=causal, window=window,
        s_valid=s_valid, scale=scale,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, GQ, D), lambda b, h, qi, kv: (b, h, qi, 0, 0)),
            pl.BlockSpec((1, 1, KV_BLK, D), lambda b, h, qi, kv: (b, h, kv, 0)),
            pl.BlockSpec((1, 1, KV_BLK, D), lambda b, h, qi, kv: (b, h, kv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, GQ, D), lambda b, h, qi, kv: (b, h, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((GQ, 1), jnp.float32),
            pltpu.VMEM((GQ, 1), jnp.float32),
            pltpu.VMEM((GQ, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
        name="flash_prefill",
    )(q, k, v)
