"""Jit'd wrapper: backend selection, GQA layout, padding, window->start
conversion.

Backend selection mirrors ``FLConfig.pearson_backend`` (DESIGN.md §2):

  "auto"      — compiled Pallas kernel on TPU/GPU, the pure-jnp reference
                on CPU (compiling the Mosaic kernel there would fail, and
                interpret mode is orders of magnitude off)
  "pallas"    — force the compiled Pallas kernel
  "interpret" — force the Pallas kernel in interpret mode (the CPU
                correctness path used by tests/test_kernels.py)
  "reference" — force the pure-jnp oracle (ref.py)

The deprecated ``interpret: bool`` kwarg stays accepted verbatim
(True == "interpret", False == "pallas"); passing it alongside a
conflicting explicit ``backend`` raises — never a silently ignored
override (the merge_at / use_kernel_pearson alias pattern).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attn.decode_attn import (
    S_BLK,
    flash_decode,
    flash_decode_paged,
)
from repro.kernels.decode_attn.ref import (
    decode_attention_ref,
    paged_decode_attention_ref,
)

_BACKENDS = ("auto", "pallas", "interpret", "reference")


def resolve_decode_backend(backend: str = "auto",
                           interpret: Optional[bool] = None) -> str:
    """-> one of "pallas" | "interpret" | "reference" for this process."""
    if backend not in _BACKENDS:
        raise ValueError(
            f"decode_attention backend must be one of {_BACKENDS}, "
            f"got {backend!r}"
        )
    if interpret is not None:
        want = "interpret" if interpret else "pallas"
        if backend not in ("auto", want):
            raise ValueError(
                f"conflicting decode_attention backend: backend="
                f"{backend!r} vs deprecated interpret={interpret} "
                f"(= {want!r}); set backend only"
            )
        return want
    if backend == "auto":
        return ("pallas" if jax.default_backend() in ("tpu", "gpu")
                else "reference")
    return backend


def _serving_s_blk(S: int) -> int:
    """S block for the kernel grid: 512 for long caches, one lane-aligned
    block for short serving arenas (padding a 64-position slot cache to
    512 would make the kernel 8x pure masking)."""
    if S >= S_BLK:
        return S_BLK
    return int(np.ceil(S / 128) * 128)


@functools.partial(jax.jit, static_argnames=("window", "s_blk", "interpret"))
def _pallas_decode(q, k, v, lengths, window: int, s_blk: int,
                   interpret: bool):
    B, Hq, D = q.shape
    S, Kv = k.shape[1], k.shape[2]
    G = Hq // Kv
    Gp = int(np.ceil(max(G, 8) / 8) * 8)
    Sp = int(np.ceil(S / s_blk) * s_blk)
    Dp = int(np.ceil(D / 128) * 128)

    # pre-scale by the TRUE head dim (padding would otherwise skew the scale)
    qg = (q * (1.0 / np.sqrt(D))).astype(q.dtype).reshape(B, Kv, G, D)
    qp = jnp.zeros((B, Kv, Gp, Dp), q.dtype).at[:, :, :G, :D].set(qg)
    kt = jnp.moveaxis(k, 1, 2)  # (B, Kv, S, D)
    vt = jnp.moveaxis(v, 1, 2)
    kp = jnp.zeros((B, Kv, Sp, Dp), k.dtype).at[:, :, :S, :D].set(kt)
    vp = jnp.zeros((B, Kv, Sp, Dp), v.dtype).at[:, :, :S, :D].set(vt)

    lengths = lengths.astype(jnp.int32)
    if window > 0:
        starts = jnp.maximum(lengths - window, 0)
    else:
        starts = jnp.zeros_like(lengths)

    out = flash_decode(qp, kp, vp, lengths, starts, interpret=interpret,
                       s_blk=s_blk)
    return out[:, :, :G, :D].reshape(B, Hq, D)


def decode_attention(q, k, v, lengths, window: int = 0,
                     backend: str = "auto",
                     interpret: Optional[bool] = None):
    """q: (B, Hq, D); k, v: (B, S, Kv, D); lengths: (B,) int32.
    window > 0 = sliding-window (attend to the last ``window`` positions).
    Returns (B, Hq, D). Backend selection per module docstring."""
    resolved = resolve_decode_backend(backend, interpret)
    if resolved == "reference":
        return decode_attention_ref(q, k, v, lengths, window=window)
    return _pallas_decode(q, k, v, lengths, window,
                          _serving_s_blk(k.shape[1]),
                          resolved == "interpret")


# bytes of K one block of the paged kernel fetches: big enough that a grid
# step's fixed cost is small against its copies, small enough that K and V,
# double-buffered, take a few MiB of VMEM
PAGED_BLOCK_BYTES = 256 * 1024


def pages_per_block(block_size: int, kv_heads: int, head_dim: int,
                    itemsize: int, table_len: int) -> int:
    """Whole pages per grid step of the paged decode kernel, from the pool's
    shapes: K blocks of about ``PAGED_BLOCK_BYTES`` (8 pages of 16 tokens
    at 8 heads of 128 in bf16), capped at the table width."""
    page = block_size * kv_heads * head_dim * itemsize
    return max(1, min(PAGED_BLOCK_BYTES // page, table_len))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_paged_decode(q, k_pool, v_pool, layer, block_tables, lengths,
                         interpret: bool):
    B, Hq, D = q.shape
    bs, Kv = k_pool.shape[2], k_pool.shape[3]
    G = Hq // Kv
    Gp = int(np.ceil(max(G, 8) / 8) * 8)

    # pre-scale by the head dim, and pad each head's query group to Gp
    # rows; the pools go to the kernel as they are
    qg = (q * (1.0 / np.sqrt(D))).astype(q.dtype).reshape(B, Kv, G, D)
    qp = jnp.zeros((B, Kv, Gp, D), q.dtype).at[:, :, :G].set(qg)
    ppb = pages_per_block(bs, Kv, D, k_pool.dtype.itemsize,
                          block_tables.shape[1])
    out = flash_decode_paged(qp.reshape(B, Kv * Gp, D), k_pool, v_pool,
                             jnp.reshape(layer, (1,)).astype(jnp.int32),
                             block_tables.astype(jnp.int32),
                             lengths.astype(jnp.int32), ppb,
                             interpret=interpret)
    return out.reshape(B, Kv, Gp, D)[:, :, :G].reshape(B, Hq, D).astype(
        q.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                           window: int = 0, backend: str = "auto",
                           interpret: Optional[bool] = None, layer=None):
    """Paged flash decode: q (B, Hq, D); k_pool, v_pool (P, bs, Kv, D)
    global page pools (last block = trash), or every layer's pools stacked
    (L, P, bs, Kv, D) with ``layer`` (an int32 scalar) naming the one to
    read, in place; block_tables (B, T) int32 (-1 = unallocated); lengths
    (B,) int32 over logical slots. Backend selection per module docstring.
    The ring-cache callers always pass ``window=0`` (every resident slot is
    inside the window by cache construction — see
    ``layers.attention_decode``); the kernel therefore only implements
    length masking, while the reference path keeps the ``window`` kwarg
    for direct oracle use."""
    resolved = resolve_decode_backend(backend, interpret)
    if k_pool.ndim == 4:  # one layer's pools: the stack of one
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    if resolved == "reference":
        return paged_decode_attention_ref(q, k_pool[layer], v_pool[layer],
                                          block_tables, lengths,
                                          window=window)
    if window > 0:
        raise NotImplementedError(
            "paged flash decode handles windows via ring lengths, not a "
            "start offset; pass window=0 with window-clamped lengths"
        )
    return _pallas_paged_decode(q, k_pool, v_pool, layer, block_tables,
                                lengths, resolved == "interpret")
