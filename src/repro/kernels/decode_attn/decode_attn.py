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


def _paged_kernel(bs, ppb, Kv, Gp, by_dma, layer_ref, lengths_ref, table_ref,
                  q_ref, *refs):
    if by_dma:
        (k_pool, v_pool, o_ref, k_buf, v_buf, k_sem, v_sem, state_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
        o_ref, m_ref, l_ref, acc_ref = refs[2 * ppb:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_b = pl.num_programs(0)
    n_j = pl.num_programs(1)
    N = ppb * bs                       # token rows of one block
    length = lengths_ref[b]

    if by_dma:
        def for_pages(row, blk, slot, act):
            """``act`` on the K and the V copy of each live page of a
            block: in the pool's layout a page is contiguous, all heads."""
            n = jnp.minimum(pl.cdiv(lengths_ref[row], bs) - blk * ppb, ppb)

            def body(i, carry):
                # -1 only on dead rows, which copy nothing; clamp anyway
                page = jnp.maximum(table_ref[row, blk * ppb + i], 0)
                rows = pl.ds(pl.multiple_of(i * bs, bs), bs)
                for pool, buf, sem in ((k_pool, k_buf, k_sem),
                                       (v_pool, v_buf, v_sem)):
                    act(pltpu.make_async_copy(
                        pool.at[layer_ref[0], page], buf.at[slot, rows],
                        sem.at[slot]))
                return carry

            jax.lax.fori_loop(0, n, body, 0)

        @pl.when((b == 0) & (j == 0))
        def _first():
            # slots past a block's live pages keep whatever an earlier
            # block left; start them finite so masked positions weigh 0
            k_buf[...] = jnp.zeros_like(k_buf)
            v_buf[...] = jnp.zeros_like(v_buf)
            state_ref[0] = 0           # buffer slot of the next live block
            state_ref[1] = 0           # whether its copies are in flight

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # only blocks holding live logical slots [0, length) are fetched or
    # computed; a block wholly past the row's length costs one empty step
    @pl.when(j * N < length)
    def _block():
        if by_dma:
            slot = state_ref[0]

            @pl.when(state_ref[1] == 0)
            def _fetch_own():
                for_pages(b, j, slot, lambda c: c.start())

            # start the next live block's pages before computing this one:
            # the row's next block, else the next row's first
            more = (j + 1) * N < length
            nb = jnp.where(more, b, b + 1)
            nj = jnp.where(more, j + 1, 0)
            nb_c = jnp.minimum(nb, n_b - 1)
            ahead = (nb < n_b) & (nj < n_j) & (nj * N < lengths_ref[nb_c])

            @pl.when(ahead)
            def _prefetch():
                for_pages(nb_c, nj, 1 - slot, lambda c: c.start())

            state_ref[0] = 1 - slot
            state_ref[1] = ahead.astype(jnp.int32)
            for_pages(b, j, slot, lambda c: c.wait())
            k, v = k_buf[slot], v_buf[slot]                  # (N, Kv, D)
        else:
            k, v = (jnp.concatenate([r[0, 0] for r in refs_], axis=0)
                    for refs_ in (k_refs, v_refs))

        # all heads at once: one (Kv*Gp, D) x (D, N*Kv) product, masked
        # block-diagonally so each query group scores only its own head
        q = q_ref[0].astype(jnp.float32)                     # (Kv*Gp, D)
        k = k.astype(jnp.float32).reshape(N * Kv, -1)
        v = v.astype(jnp.float32).reshape(N * Kv, -1)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                    # (Kv*Gp, N*Kv)

        col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        valid = ((col % Kv == row // Gp)
                 & (j * N + col // Kv < length))
        scores = jnp.where(valid, scores, -1e30)

        m_prev = m_ref[...]                                  # (Kv*Gp, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(j == n_j - 1)
    def _finalize():
        # a fully-masked row (length 0, a dead lane of the padded batch)
        # finalizes to zeros, never NaN
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def page_is_tiled(kv_heads: int, head_dim: int, dtype) -> bool:
    """Whether a pool page's two minor dims (heads, head dim) are whole
    tiles of the TPU's HBM layout, so one async copy can slice a page out
    of the pool: head dim a multiple of 128 lanes, and heads a whole number
    of packed sublane rows that XLA tiles without padding."""
    packing = 4 // jnp.dtype(dtype).itemsize
    rows, rem = divmod(kv_heads, packing)
    return (head_dim % 128 == 0 and rem == 0
            and (rows in (1, 2, 4, 8) or rows % 8 == 0))


def page_fetches(block_tables, lengths, block_size: int,
                 pages_per_block: int):
    """(B, T) block table -> (B, nb * ppb) page of each (row, block, slot)
    grid operand, nb = ceil(T / ppb). A live entry is its table entry; a
    dead one (past the row's length, or the table's end) repeats what that
    slot fetched last in grid order, so the pipeline skips its copy.
    Entries are clamped to the pool: a dead row of a padded batch has -1
    entries and may still carry a stale length."""
    B, T = block_tables.shape
    ppb = pages_per_block
    nb = -(-T // ppb)
    bt = jnp.pad(jnp.maximum(block_tables, 0), ((0, 0), (0, nb * ppb - T)))
    live = (jnp.arange(nb * ppb)[None, :]
            < -(-lengths[:, None] // block_size))
    bt, live = bt.reshape(B * nb, ppb), live.reshape(B * nb, ppb)
    step = jnp.arange(B * nb, dtype=jnp.int32)[:, None]
    last = jax.lax.cummax(jnp.where(live, step, -1), axis=0)
    pages = jnp.take_along_axis(bt, jnp.maximum(last, 0), axis=0)
    return jnp.where(last >= 0, pages, 0).reshape(B, nb * ppb)


def flash_decode_paged(q, k_pool, v_pool, layer, block_tables, lengths,
                       pages_per_block: int,
                       interpret: Optional[bool] = None):
    """Block-table flash decode over one layer of the stacked page pools,
    as the arena holds them: q (B, Kv*Gp, D), each head's query group
    pre-scaled and padded to Gp rows (a multiple of 8); k_pool, v_pool
    (L, P, bs, Kv, D), every layer's pool (last page = trash); layer
    (1,) int32, the pool the kernel reads; block_tables (B, T) int32, -1 =
    unallocated; lengths (B,) int32 over *logical* slots (slot l lives at
    page bt[b, l // bs]). Returns (B, Kv*Gp, D) float32.

    One grid step is one row and one block of ``pages_per_block`` table
    entries, all heads. The kernel reads the pools where they lie, the
    layer a scalar-prefetched index, and a page past a row's length is
    neither fetched nor computed. Where a page is whole tiles
    (``page_is_tiled``), the kernel copies each live page itself into a
    double-buffered VMEM block, one live block ahead, from the
    scalar-prefetched table. Otherwise a copy cannot slice a page out of
    the pool, and each pool is an operand once per page slot of a block, a
    one-page block whose index map reads the layer and ``page_fetches``:
    the grid pipeline fetches a block while the previous one computes, and
    pads the page in VMEM. Where both work the kernel's own copies win: at
    Qwen3's widths on a v5e the pipeline took 34 us a call against 20, and
    it lets XLA stage a pool it finds in HBM into VMEM whole."""
    B, R, D = q.shape
    bs, Kv = k_pool.shape[2], k_pool.shape[3]
    Gp = R // Kv
    T = block_tables.shape[1]
    ppb = pages_per_block
    assert Gp % 8 == 0 and Gp * Kv == R and 1 <= ppb <= T, (R, Kv, ppb, T)
    by_dma = page_is_tiled(Kv, D, k_pool.dtype)
    q_spec = pl.BlockSpec((1, R, D), lambda b, j, *_: (b, 0, 0))
    stats = [pltpu.VMEM((R, 1), jnp.float32), pltpu.VMEM((R, 1), jnp.float32),
             pltpu.VMEM((R, D), jnp.float32)]
    if by_dma:
        buf = (2, ppb * bs, Kv, D)
        table = block_tables
        pool_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        pools = (k_pool, v_pool)
        scratch = [pltpu.VMEM(buf, k_pool.dtype),
                   pltpu.VMEM(buf, v_pool.dtype),
                   pltpu.SemaphoreType.DMA((2,)),
                   pltpu.SemaphoreType.DMA((2,)),
                   pltpu.SMEM((2,), jnp.int32)] + stats
    else:
        def page_spec(i):
            return pl.BlockSpec(
                (1, 1, bs, Kv, D),
                lambda b, j, layer, _lengths, pages: (
                    layer[0], pages[b, j * ppb + i], 0, 0, 0))

        table = page_fetches(block_tables, lengths, bs, ppb)
        pool_specs = [page_spec(i) for i in range(ppb)] * 2
        pools = (k_pool,) * ppb + (v_pool,) * ppb
        scratch = stats

    return pl.pallas_call(
        functools.partial(_paged_kernel, bs, ppb, Kv, Gp, by_dma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, pl.cdiv(T, ppb)),
            in_specs=[q_spec] + pool_specs,
            out_specs=q_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, R, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="paged_decode_attn",
    )(layer, lengths, table, q, *pools)
