"""Flash-decode GQA attention Pallas kernel (one new token vs. a long KV
cache — the serving hot loop for decode_32k / long_500k, and the ragged
serving arena's per-row attention).

TPU adaptation: decode attention is memory-bound (the whole KV cache
streams through VMEM once per token), so the kernel keeps the query group
resident in VMEM, streams (s_blk, D) cache tiles, and maintains the online
softmax (m, l, acc) in VMEM scratch across the sequential S grid axis —
one HBM pass, no (S,) score materialization. The GQA group axis (G = Hq/Kv,
padded to a sublane multiple) becomes the MXU sublane dim so the q @ k^T
products are (G, D) x (D, s_blk) matmuls rather than VPU dot products.

Grid: (B, Kv, S/s_blk) — the S axis is innermost/sequential (TPU grid
order), which is what makes the scratch accumulator pattern valid.
Length + window masking supports both full and sliding-window caches.

Ragged rows: lengths/starts are scalar-prefetch operands, so they feed the
k/v BlockSpec index maps *before* the DMA is issued. Cache blocks entirely
outside a row's [start, length) live range are (a) re-pointed at the last
in-range block — consecutive grid steps with the same block index skip the
copy, so a dead lane's cache never streams through VMEM — and (b) skipped
for compute via ``pl.when``. A serving arena with one active slot at depth
d therefore pays for ~d cache positions, not B * S.
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

S_BLK = 512  # max S block; short caches use one 128-multiple block instead


def _kernel(s_blk, lengths_ref, starts_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref):
    b = pl.program_id(0)
    s = pl.program_id(2)
    n_s = pl.num_programs(2)
    length = lengths_ref[b]
    start = starts_ref[b]

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # compute only blocks intersecting the live range [start, length);
    # out-of-range blocks also re-fetch the previous block (index-map
    # clamp), so they cost neither FLOPs nor HBM traffic
    @pl.when((s * s_blk < length) & ((s + 1) * s_blk > start))
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, D), pre-scaled
        k = k_ref[0, 0].astype(jnp.float32)            # (s_blk, D)
        v = v_ref[0, 0].astype(jnp.float32)            # (s_blk, D)

        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                              # (G, s_blk)

        idx = s * s_blk + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        valid = (idx < length) & (idx >= start)
        scores = jnp.where(valid, scores, -1e30)

        m_prev = m_ref[...]                            # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        p = jnp.exp(scores - m_new)                    # (G, s_blk)
        alpha = jnp.exp(m_prev - m_new)                # (G, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(s == n_s - 1)
    def _finalize():
        # a fully-masked row (length 0, e.g. a dead serving lane inside the
        # padded batch) finalizes to zeros, never NaN
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


def flash_decode(q, k, v, lengths, starts, interpret: Optional[bool] = None,
                 s_blk: int = S_BLK):
    """q: (B, Kv, Gp, D); k, v: (B, Kv, Sp, D); lengths/starts: (B,) int32.
    Gp multiple of 8, Sp multiple of ``s_blk``, D multiple of 128 after
    ops.py padding. Returns (B, Kv, Gp, D)."""
    B, Kv, Gp, D = q.shape
    Sp = k.shape[2]
    assert Gp % 8 == 0 and Sp % s_blk == 0, (Gp, Sp, s_blk)
    grid = (B, Kv, Sp // s_blk)

    def kv_index(b, h, s, lengths, starts):
        # clamp dead blocks to the last block intersecting [start, length):
        # the sequential S axis then revisits the same block and Pallas
        # elides the copy (the paged-attention trick). All-dead rows pin
        # block 0.
        last = jnp.maximum(pl.cdiv(lengths[b], s_blk) - 1, 0)
        first = starts[b] // s_blk
        return (b, h, jnp.clip(s, first, last), 0)

    return pl.pallas_call(
        functools.partial(_kernel, s_blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, Gp, D), lambda b, h, s, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, s_blk, D), kv_index),
                pl.BlockSpec((1, 1, s_blk, D), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, Gp, D), lambda b, h, s, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Gp, 1), jnp.float32),
                pltpu.VMEM((Gp, 1), jnp.float32),
                pltpu.VMEM((Gp, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Kv, Gp, D), q.dtype),
        interpret=resolve_interpret(interpret),
        name="decode_attn",
    )(lengths, starts, q, k, v)


# ---------------------------------------------------------------------------
# paged (block-table) variant
# ---------------------------------------------------------------------------


def _paged_kernel(bs, lengths_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref):
    b = pl.program_id(0)
    t = pl.program_id(2)
    n_t = pl.num_programs(2)
    length = lengths_ref[b]

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # compute only table entries holding live logical slots [0, length);
    # later entries re-fetch the last live block (index-map clamp), so a
    # short row pays for its own pages, never the whole pool
    @pl.when(t * bs < length)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, D), pre-scaled
        k = k_ref[0, 0].astype(jnp.float32)            # (bsp, D), one page
        v = v_ref[0, 0].astype(jnp.float32)

        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                              # (G, bsp)

        off = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        # offsets >= bs are sublane padding inside the page, never data
        valid = (off < bs) & (t * bs + off < length)
        scores = jnp.where(valid, scores, -1e30)

        m_prev = m_ref[...]                            # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        p = jnp.exp(scores - m_new)                    # (G, bsp)
        alpha = jnp.exp(m_prev - m_new)                # (G, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(t == n_t - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


def flash_decode_paged(q, k, v, block_tables, lengths, block_size: int,
                       interpret: Optional[bool] = None):
    """Block-table flash decode: q (B, Kv, Gp, D); k, v (P, Kv, bsp, D)
    global page pools (bsp = ``block_size`` sublane-padded, last block =
    trash); block_tables (B, T) int32, -1 = unallocated; lengths (B,)
    int32 over *logical* slots (slot l lives at page bt[b, l // bs]).

    The per-row block table is a scalar-prefetch operand, so it feeds the
    kv BlockSpec index map before the page DMA is issued — dead table
    entries are re-pointed at the row's last live page and consecutive
    identical indices elide the copy, exactly like the contiguous
    kernel's dead-block elision, just one indirection deeper. Returns
    (B, Kv, Gp, D)."""
    B, Kv, Gp, D = q.shape
    T = block_tables.shape[1]
    bs = block_size
    assert Gp % 8 == 0, Gp
    grid = (B, Kv, T)

    def kv_index(b, h, t, lengths, bt):
        last = jnp.maximum(pl.cdiv(lengths[b], bs) - 1, 0)
        blk = bt[b, jnp.minimum(t, last)]
        # an unallocated entry (-1, only reachable on all-dead rows whose
        # compute is pl.when-guarded off) pins page 0
        return (jnp.maximum(blk, 0), h, 0, 0)

    return pl.pallas_call(
        functools.partial(_paged_kernel, bs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, Gp, D), lambda b, h, t, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, k.shape[2], D), kv_index),
                pl.BlockSpec((1, 1, k.shape[2], D), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, Gp, D), lambda b, h, t, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Gp, 1), jnp.float32),
                pltpu.VMEM((Gp, 1), jnp.float32),
                pltpu.VMEM((Gp, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Kv, Gp, D), q.dtype),
        interpret=resolve_interpret(interpret),
        name="paged_decode_attn",
    )(lengths, block_tables, q, k, v)
