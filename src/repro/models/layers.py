"""Core layers: init helpers, RMSNorm, RoPE, GQA attention (full/blocked/
decode, sliding-window), SwiGLU MLP.

All layers are functional: ``init_*`` returns a param pytree, ``*_fwd``
applies it. Attention's full-sequence path scans over query blocks so the
materialized score tensor is O(q_blk * T) — required for the 32k prefill
shapes to have a sane memory footprint; the scan body is remat-safe.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import flags as FLAGS

Q_BLOCK = 512  # query-block size for the blocked attention scan

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    s = scale if scale is not None else 1.0 / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * s).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D) with positions (..., S) or (S,)."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)  # (half,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def attention_init(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.q_dim, dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.kv_dim, dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.kv_dim, dtype),
        "wo": dense_init(ks[3], cfg.q_dim, cfg.d_model, dtype,
                         scale=1.0 / np.sqrt(cfg.q_dim) / np.sqrt(2 * cfg.num_layers)),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(cfg.head_dim, dtype)
        p["k_norm"] = rmsnorm_init(cfg.head_dim, dtype)
    return p


def _qkv(params, cfg, x, positions):
    B, S, _ = x.shape
    q = (x @ params["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = (x @ params["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = (x @ params["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    if cfg.rope_theta > 0 and not cfg.is_encoder:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _scores_mask(q_pos, k_pos, causal: bool, window: int):
    """(Sq, Sk) additive mask in f32."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def _attend(q_blk, k, v, mask_blk, cfg):
    """q_blk (B, sq, Hq, D); k/v (B, T, Kv, D); mask (sq, T) or per-row
    (B, sq, T) additive."""
    B, sq, Hq, D = q_blk.shape
    Kv = cfg.num_kv_heads
    G = Hq // Kv
    qg = q_blk.reshape(B, sq, Kv, G, D)
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) / np.sqrt(D)
    if mask_blk.ndim == 2:
        mask_blk = mask_blk[None]
    scores = scores + mask_blk[:, None, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out.reshape(B, sq, Hq, D)


def _resolve_prefill_backend(cfg) -> str:
    """cfg.prefill_backend -> "jnp" | "pallas" | "interpret".

    Mirrors ``_resolve_decode_backend``: "auto" picks the compiled
    flash-prefill kernel on TPU/GPU and the jnp blocked/online path on
    CPU. Unknown values raise — never a silent fallback."""
    b = getattr(cfg, "prefill_backend", "auto")
    if b not in ("auto", "pallas", "interpret", "jnp"):
        raise ValueError(
            "cfg.prefill_backend must be one of "
            f"('auto', 'pallas', 'interpret', 'jnp'), got {b!r}"
        )
    if b == "auto":
        return "pallas" if jax.default_backend() in ("tpu", "gpu") else "jnp"
    return b


def attention_fwd(params, cfg, x, positions, causal: bool = True,
                  return_cache: bool = False):
    """Full-sequence attention (train / prefill). Scans over query blocks so
    peak score memory is (B, heads, Q_BLOCK, T).

    The cache-returning pass (serving admission prefill) can route through
    ``kernels/flash_prefill`` via ``cfg.prefill_backend``; the training
    forward always stays on the differentiable jnp implementations."""
    B, S, _ = x.shape
    q, k, v = _qkv(params, cfg, x, positions)
    window = cfg.window_size
    is_causal = causal and not cfg.is_encoder

    prefill_backend = _resolve_prefill_backend(cfg) if return_cache else "jnp"
    if prefill_backend != "jnp":
        from repro.kernels.flash_prefill.ops import flash_prefill_attention

        out = flash_prefill_attention(
            q, k, v, causal=is_causal, window=window,
            interpret=(prefill_backend == "interpret"),
        )
        y = out.reshape(B, S, cfg.q_dim) @ params["wo"]
        return y, {"k": k, "v": v}

    q_blk = min(Q_BLOCK, S)
    if S % q_blk != 0:  # fall back to one block for odd smoke shapes
        q_blk = S
    n_blk = S // q_blk

    if getattr(cfg, "attn_impl", "blocked") == "online":
        out = _attention_online(q, k, v, positions, is_causal, window, cfg,
                                q_blk, n_blk)
    else:
        def body(carry, qb):
            qi, q_pos = qb
            mask = _scores_mask(q_pos, positions, is_causal, window)
            return carry, _attend(qi, k, v, mask, cfg)

        qs = q.reshape(B, n_blk, q_blk, cfg.num_heads, cfg.head_dim).transpose(
            1, 0, 2, 3, 4
        )
        pos_blocks = positions.reshape(n_blk, q_blk)
        _, outs = jax.lax.scan(body, (), (qs, pos_blocks),
                               unroll=FLAGS.scan_unroll())
        out = outs.transpose(1, 0, 2, 3, 4).reshape(B, S, cfg.q_dim)
    y = out @ params["wo"]
    if return_cache:
        return y, {"k": k, "v": v}
    return y


def _attention_online(q, k, v, positions, is_causal, window, cfg, q_blk, n_blk):
    """Flash-style attention (§Perf H1): python loop over q blocks; per
    block, an inner kv-block scan carries the online-softmax state
    (m, l, acc) so no (q_blk, T) score row is ever materialized, kv blocks
    outside the causal triangle / sliding window are statically skipped,
    and the probability tile is cast to the value dtype (bf16) for the PV
    matmul. Numerics match the baseline to ~1e-6 (f32 stats)."""
    B, S, Hq, D = q.shape
    Kv = cfg.num_kv_heads
    G = Hq // Kv
    kv_blk = q_blk
    outs = []
    for qi in range(n_blk):
        q_lo, q_hi = qi * q_blk, (qi + 1) * q_blk
        qg = q[:, q_lo:q_hi].reshape(B, q_blk, Kv, G, D)
        q_pos = positions[q_lo:q_hi]
        # static kv range for this q block: causal upper, window lower
        hi = q_hi if is_causal else S
        lo = 0
        if window > 0:
            lo = max(0, (q_lo - window + 1) // kv_blk * kv_blk)
        hi = ((hi + kv_blk - 1) // kv_blk) * kv_blk
        n_kv = (hi - lo) // kv_blk
        ks = k[:, lo:hi].reshape(B, n_kv, kv_blk, Kv, D).transpose(1, 0, 2, 3, 4)
        vs = v[:, lo:hi].reshape(B, n_kv, kv_blk, Kv, D).transpose(1, 0, 2, 3, 4)
        kpos = positions[lo:hi].reshape(n_kv, kv_blk)

        def body(carry, kv, qg=qg, q_pos=q_pos):
            m, l, acc = carry
            kb, vb, kp = kv
            s = jnp.einsum(
                "bskgd,btkd->bkgst", qg.astype(jnp.float32),
                kb.astype(jnp.float32),
            ) / np.sqrt(D)
            s = s + _scores_mask(q_pos, kp, is_causal, window)[None, None, None]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pv = jnp.einsum("bkgst,btkd->bskgd", p.astype(vb.dtype), vb)
            acc = acc * alpha.transpose(0, 3, 1, 2, 4).astype(acc.dtype) + pv
            return (m_new, l, acc), ()

        m0 = jnp.full((B, Kv, G, q_blk, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Kv, G, q_blk, 1), jnp.float32)
        a0 = jnp.zeros((B, q_blk, Kv, G, D), v.dtype)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (ks, vs, kpos))
        li = l.transpose(0, 3, 1, 2, 4)  # (B, q_blk, Kv, G, 1)
        outs.append((acc.astype(jnp.float32) / jnp.maximum(li, 1e-30)).astype(v.dtype))
    return jnp.concatenate(outs, axis=1).reshape(B, S, Hq * D)


def _resolve_decode_backend(cfg) -> str:
    """cfg.decode_attn_backend -> "jnp" | "pallas" | "interpret".

    "auto" picks the compiled Pallas flash-decode kernel on TPU/GPU and the
    masked-jnp ``_attend`` path on CPU (the latter is bit-identical to the
    full-sequence numerics, which is what the serving parity oracle needs).
    Unknown values raise — never a silent fallback."""
    b = getattr(cfg, "decode_attn_backend", "auto")
    if b not in ("auto", "pallas", "interpret", "jnp"):
        raise ValueError(
            "cfg.decode_attn_backend must be one of "
            f"('auto', 'pallas', 'interpret', 'jnp'), got {b!r}"
        )
    if b == "auto":
        return "pallas" if jax.default_backend() in ("tpu", "gpu") else "jnp"
    return b


def _ring_decode_mask(length, slot, C, pos, window, width=None):
    """Per-row additive decode mask over a ring cache of logical size C.

    ``width`` is the physical number of cached slots in the attended view
    (defaults to C; the paged view is T*block_size >= C when the page size
    does not divide C). Slots >= C are never written and stay masked, so
    widening the view only appends exactly-masked columns."""
    W = C if width is None else width
    idx = jnp.arange(W)[None, :]  # (1, W)
    total = (length + 1)[:, None]  # (B, 1): tokens now present per row
    slot_b = slot[:, None]
    # slot s holds absolute position: if total <= C: s; else the ring map
    abs_pos = jnp.where(
        total <= C, idx,
        jnp.where(idx <= slot_b, total - 1 - (slot_b - idx),
                  total - 1 - (slot_b + C - idx))
    )
    valid = idx < jnp.minimum(total, C)
    if window > 0:
        valid &= abs_pos > (pos[:, None] - window)
    return jnp.where(valid, 0.0, -1e30).astype(jnp.float32)[:, None, :]


def attention_decode(params, cfg, x, cache, pos):
    """One-token ragged decode.

    Contiguous layout — ``cache``: {k,v: (B, C, Kv, D), length: int32[B]}
    with C = window (sliding) or max_len. Row b's new token writes at
    ``length[b] % C`` (ring buffer when windowed) and attends over that
    row's valid slots only — rows at different depths share one batched
    call. ``pos`` (B,) is the absolute position of each row's new token
    (== length[b] on every production path).

    Paged layout — ``cache``: {k,v: (P+1, bs, Kv, D) global page pools
    (last block = trash), block_tables: int32[B, T] (-1 = unallocated),
    length: int32[B]}; dispatched by the ``block_tables`` key. Logical
    slot l of row b lives at pool page ``block_tables[b, l // bs]``,
    offset ``l % bs`` — same ring semantics, one indirection deeper. The
    pools are every layer's of the run stacked, and the ``layer`` key names
    the one this layer reads and writes (``blocks.blocks_decode``)."""
    if "block_tables" in cache:
        return _attention_decode_paged(params, cfg, x, cache, pos)
    B = x.shape[0]
    q, k, v = _qkv(params, cfg, x, pos[:, None] if pos.ndim == 1 else pos)
    C = cache["k"].shape[1]
    length = cache["length"]  # int32 (B,): tokens already in each row's cache
    slot = jnp.mod(length, C)  # (B,)
    rows = jnp.arange(B)
    ck = cache["k"].at[rows, slot].set(k[:, 0])
    cv = cache["v"].at[rows, slot].set(v[:, 0])
    new_cache = {"k": ck, "v": cv, "length": length + 1}

    backend = _resolve_decode_backend(cfg)
    if backend != "jnp":
        # Pallas flash-decode path. Valid slots are exactly
        # idx < min(length+1, C): with a sliding window, C <= window by
        # cache construction, so every resident slot is inside the window
        # and the [0, eff_len) contiguous model matches the ring cache
        # (attention is permutation-invariant over cached slots — RoPE is
        # already applied at write time).
        from repro.kernels.decode_attn.ops import decode_attention

        eff_len = jnp.minimum(length + 1, C).astype(jnp.int32)
        out = decode_attention(q[:, 0], ck, cv, eff_len, window=0,
                               backend=backend)
        y = out.reshape(B, 1, cfg.q_dim) @ params["wo"]
        return y, new_cache

    # masked-jnp path: per-row additive mask over the ring cache
    mask = _ring_decode_mask(length, slot, C, pos, cfg.window_size)
    out = _attend(q, ck, cv, mask, cfg)  # (B, 1, Hq, D)
    y = out.reshape(B, 1, cfg.q_dim) @ params["wo"]
    return y, new_cache


def _attention_decode_paged(params, cfg, x, cache, pos):
    """Paged one-token decode: write the new token's k/v through the block
    table into the global page pools, then attend over the row's logical
    slots. A row whose write lands on an unallocated table entry (-1 —
    only inactive lanes; live rows hold their full page reservation from
    admission) is redirected to the trash page, so it can never corrupt
    another row's pages.

    The pools are every layer's of the run, stacked (L, P+1, bs, Kv, D),
    as the layer loop carries them: the token is written at
    ``[layer, page, offset]`` by one scatter a pool, in place, and the
    kernel reads that layer's pool where it lies."""
    B = x.shape[0]
    q, k, v = _qkv(params, cfg, x, pos[:, None] if pos.ndim == 1 else pos)
    k_pool, v_pool = cache["k"], cache["v"]  # (L, P+1, bs, Kv, D)
    layer = cache["layer"]
    bt = cache["block_tables"]               # (B, T) int32
    bs = k_pool.shape[2]
    W = bt.shape[1] * bs                     # physical slots in the view
    # logical ring size: same rule as the contiguous cache. W may exceed
    # min(window, max_row_len) by page-size rounding; slots >= C are never
    # written and stay masked.
    C = min(cfg.window_size, W) if cfg.window_size > 0 else W
    length = cache["length"]
    slot = jnp.mod(length, C)                # (B,) logical write slot
    rows = jnp.arange(B)
    trash = k_pool.shape[1] - 1
    wblk = bt[rows, slot // bs]
    wblk = jnp.where(wblk >= 0, wblk, trash)
    ck = k_pool.at[layer, wblk, slot % bs].set(k[:, 0])
    cv = v_pool.at[layer, wblk, slot % bs].set(v[:, 0])
    new_cache = dict(cache, k=ck, v=cv, length=length + 1)

    backend = _resolve_decode_backend(cfg)
    if backend != "jnp":
        # window handled via ring lengths: every resident slot is inside
        # the window by cache construction (see the contiguous path), so
        # the kernel only needs length masking.
        from repro.kernels.decode_attn.ops import paged_decode_attention

        eff_len = jnp.minimum(length + 1, C).astype(jnp.int32)
        out = paged_decode_attention(q[:, 0], ck, cv, bt, eff_len, window=0,
                                     backend=backend, layer=layer)
        y = out.reshape(B, 1, cfg.q_dim) @ params["wo"]
        return y, new_cache

    # masked-jnp path: gather the table-ordered view, then the identical
    # ring mask as the contiguous cache (extra page-rounding columns are
    # exactly masked)
    from repro.kernels.decode_attn.ref import gather_paged_kv

    gk, gv = gather_paged_kv(ck[layer], cv[layer], bt)  # (B, W, Kv, D)
    mask = _ring_decode_mask(length, slot, C, pos, cfg.window_size, width=W)
    out = _attend(q, gk, gv, mask, cfg)      # (B, 1, Hq, D)
    y = out.reshape(B, 1, cfg.q_dim) @ params["wo"]
    return y, new_cache


def attention_init_cache(cfg, batch: int, max_len: int, dtype):
    C = min(max_len, cfg.window_size) if cfg.window_size > 0 else max_len
    return {
        "k": jnp.zeros((batch, C, cfg.num_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, C, cfg.num_kv_heads, cfg.head_dim), dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def attention_init_cache_paged(cfg, batch: int, max_row_len: int, dtype,
                               block_size: int, num_blocks: int):
    """Paged arena: ``num_blocks`` allocatable pages plus one trailing
    trash page (id ``num_blocks``) that absorbs writes routed through
    unallocated (-1) table entries. Per-row table capacity covers the
    logical ring C = min(max_row_len, window)."""
    C = min(max_row_len, cfg.window_size) if cfg.window_size > 0 else max_row_len
    T = -(-C // block_size)
    return {
        "k": jnp.zeros((num_blocks + 1, block_size, cfg.num_kv_heads,
                        cfg.head_dim), dtype),
        "v": jnp.zeros((num_blocks + 1, block_size, cfg.num_kv_heads,
                        cfg.head_dim), dtype),
        "block_tables": jnp.full((batch, T), -1, jnp.int32),
        "length": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def mlp_init(key, cfg, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, cfg.d_model, cfg.d_ff, dtype),
        "w_up": dense_init(k2, cfg.d_model, cfg.d_ff, dtype),
        "w_down": dense_init(k3, cfg.d_ff, cfg.d_model, dtype,
                             scale=1.0 / np.sqrt(cfg.d_ff) / np.sqrt(2 * cfg.num_layers)),
    }


def mlp_fwd(params, x):
    g = jax.nn.silu((x @ params["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    u = x @ params["w_up"]
    return (g * u) @ params["w_down"]
