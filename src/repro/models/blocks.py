"""Uniform layer wrapper + heterogeneous layer-stack execution.

Every layer is (pre-norm -> temporal mixer -> residual) and, when the
config has an FFN (d_ff > 0), (pre-norm -> MLP/MoE -> residual). The mixer
type varies per layer for the hybrid (rglru/attn) and ssm (mlstm/slstm)
families.

Layers are executed as *runs*: maximal contiguous spans with the same mixer
type, parameters stacked on a leading axis, driven by ``lax.scan`` so the
HLO contains each distinct layer body once (compile-time and HLO-parse
sanity at 60 layers). Each scan body is wrapped in ``jax.checkpoint`` on
the gradient path (per-layer remat).
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.models import flags as FLAGS
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import xlstm as XL


# mixer registry: init(key,cfg,dtype), fwd(params,cfg,x,pos,return_state),
#                 decode(params,cfg,x,state,pos), init_state(cfg,B,max_len,dtype)
def _attn_init_state(cfg, batch, max_len, dtype):
    return L.attention_init_cache(cfg, batch, max_len, dtype)


MIXERS = {
    "attn": (
        L.attention_init,
        lambda p, c, x, pos, rs: (
            L.attention_fwd(p, c, x, pos, return_cache=rs)
            if rs
            else L.attention_fwd(p, c, x, pos)
        ),
        L.attention_decode,
        _attn_init_state,
    ),
    "rglru": (
        RG.rglru_init,
        lambda p, c, x, pos, rs: RG.rglru_fwd(p, c, x, pos, return_state=rs),
        RG.rglru_decode,
        lambda c, b, ml, dt: RG.rglru_init_state(c, b, dt),
    ),
    "mlstm": (
        XL.mlstm_init,
        lambda p, c, x, pos, rs: XL.mlstm_fwd(p, c, x, pos, return_state=rs),
        XL.mlstm_decode,
        lambda c, b, ml, dt: XL.mlstm_init_state(c, b),
    ),
    "slstm": (
        XL.slstm_init,
        lambda p, c, x, pos, rs: XL.slstm_fwd(p, c, x, pos, return_state=rs),
        XL.slstm_decode,
        lambda c, b, ml, dt: XL.slstm_init_state(c, b),
    ),
}


def layer_types(cfg) -> Tuple[str, ...]:
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        return ("attn",) * cfg.num_layers
    if cfg.family == "hybrid":
        pat = cfg.block_pattern or ("rglru", "rglru", "attn")
        return tuple(pat[i % len(pat)] for i in range(cfg.num_layers))
    if cfg.family == "ssm":
        return tuple(
            "slstm" if i in cfg.slstm_at else "mlstm" for i in range(cfg.num_layers)
        )
    raise ValueError(f"unknown family {cfg.family}")


def runs(cfg) -> List[Tuple[str, int]]:
    """Contiguous (mixer_type, count) runs."""
    out: List[Tuple[str, int]] = []
    for t in layer_types(cfg):
        if out and out[-1][0] == t:
            out[-1] = (t, out[-1][1] + 1)
        else:
            out.append((t, 1))
    return out


def _ffn_kind(cfg) -> str:
    if cfg.d_ff == 0:
        return "none"
    return "moe" if cfg.num_experts > 0 else "mlp"


# ---------------------------------------------------------------------------
# single-layer init / fwd / decode
# ---------------------------------------------------------------------------


def layer_init(key, cfg, mixer_type: str, dtype):
    k_mix, k_ffn = jax.random.split(key)
    p = {
        "norm1": L.rmsnorm_init(cfg.d_model, dtype),
        "mixer": MIXERS[mixer_type][0](k_mix, cfg, dtype),
    }
    kind = _ffn_kind(cfg)
    if kind == "mlp":
        p["norm2"] = L.rmsnorm_init(cfg.d_model, dtype)
        p["ffn"] = L.mlp_init(k_ffn, cfg, dtype)
    elif kind == "moe":
        p["norm2"] = L.rmsnorm_init(cfg.d_model, dtype)
        p["ffn"] = MOE.moe_init(k_ffn, cfg, dtype)
    return p


def layer_fwd(params, cfg, mixer_type: str, x, positions, return_state: bool):
    fwd = MIXERS[mixer_type][1]
    res = fwd(params["mixer"], cfg, L.rmsnorm(params["norm1"], x), positions,
              return_state)
    state = None
    if return_state:
        y, state = res
    else:
        y = res
    x = x + y
    aux = jnp.zeros((), jnp.float32)
    kind = _ffn_kind(cfg)
    if kind == "mlp":
        x = x + L.mlp_fwd(params["ffn"], L.rmsnorm(params["norm2"], x))
    elif kind == "moe":
        moe = MOE.moe_fwd_ep if getattr(cfg, "moe_impl", "gspmd") == "ep" else MOE.moe_fwd
        y, aux = moe(params["ffn"], cfg, L.rmsnorm(params["norm2"], x))
        x = x + y
    return x, state, aux


def layer_decode(params, cfg, mixer_type: str, x, state, pos):
    dec = MIXERS[mixer_type][2]
    with jax.named_scope("mixer"):
        y, new_state = dec(params["mixer"], cfg, L.rmsnorm(params["norm1"], x),
                           state, pos)
    x = x + y
    kind = _ffn_kind(cfg)
    with jax.named_scope("ffn"):
        if kind == "mlp":
            x = x + L.mlp_fwd(params["ffn"], L.rmsnorm(params["norm2"], x))
        elif kind == "moe":
            moe = MOE.moe_fwd_ep if getattr(cfg, "moe_impl", "gspmd") == "ep" else MOE.moe_fwd
            y, _ = moe(params["ffn"], cfg, L.rmsnorm(params["norm2"], x))
            x = x + y
    return x, new_state


# ---------------------------------------------------------------------------
# stacked-run execution
# ---------------------------------------------------------------------------


def init_blocks(key, cfg, dtype):
    """Returns a tuple of stacked param pytrees, one per run."""
    out = []
    for run_idx, (mtype, count) in enumerate(runs(cfg)):
        keys = jax.random.split(jax.random.fold_in(key, run_idx), count)
        out.append(jax.vmap(lambda k: layer_init(k, cfg, mtype, dtype))(keys))
    return tuple(out)


def blocks_fwd(block_params, cfg, x, positions, return_state: bool = False,
               remat: bool = True):
    """Full-sequence pass through all runs. Returns (x, states, aux_sum)."""
    states = []
    aux_total = jnp.zeros((), jnp.float32)

    for (mtype, _count), stacked in zip(runs(cfg), block_params):
        def body(carry, lp, _mtype=mtype):
            xc, aux = carry
            fn = lambda p, xx: layer_fwd(p, cfg, _mtype, xx, positions, return_state)
            if remat and not return_state:
                fn = jax.checkpoint(fn)
            xc, state, a = fn(lp, xc)
            return (xc, aux + a), state

        (x, aux_total), run_states = jax.lax.scan(body, (x, aux_total), stacked,
                                                  unroll=FLAGS.scan_unroll())
        states.append(run_states)
    return x, tuple(states), aux_total


def blocks_decode(block_params, cfg, x, states, pos):
    """One-token pass; states is a tuple of stacked per-run states."""
    new_states = []
    for (mtype, count), stacked, run_state in zip(runs(cfg), block_params, states):
        if "block_tables" in run_state:
            x, new_run_state = _decode_carrying_pools(
                stacked, cfg, mtype, count, x, run_state, pos)
            new_states.append(new_run_state)
            continue

        def body(xc, lp_state, _mtype=mtype):
            lp, st = lp_state
            xc, new_st = layer_decode(lp, cfg, _mtype, xc, st, pos)
            return xc, new_st

        x, new_run_state = jax.lax.scan(body, x, (stacked, run_state),
                                        unroll=FLAGS.scan_unroll())
        new_states.append(new_run_state)
    return x, tuple(new_states)


def _decode_carrying_pools(stacked, cfg, mtype, count, x, run_state, pos):
    """A paged attention run: the stacked page pools ride in the loop's
    carry, and each layer writes its token into its own pool and reads
    it there, by its index. Scanned as ``xs``/``ys`` instead, every step
    would slice each layer's pool out and stack new ones: the whole pools
    copied about three times a token. Only the parameters and the rest
    of the state (block tables, lengths) are per-layer slices."""
    rest = {n: a for n, a in run_state.items() if n not in ("k", "v")}

    def body(carry, xs):
        xc, k, v = carry
        lp, st, layer = xs
        xc, new = layer_decode(lp, cfg, mtype, xc,
                               dict(st, k=k, v=v, layer=layer), pos)
        return (xc, new["k"], new["v"]), {n: new[n] for n in st}

    (x, k, v), rest = jax.lax.scan(
        body, (x, run_state["k"], run_state["v"]),
        (stacked, rest, jnp.arange(count, dtype=jnp.int32)),
        unroll=FLAGS.scan_unroll())
    return x, dict(rest, k=k, v=v)


def init_decode_states(cfg, batch: int, max_len: int, dtype):
    """Zero decode state stacked per run."""
    out = []
    for (mtype, count) in runs(cfg):
        init_state = MIXERS[mtype][3]
        single = init_state(cfg, batch, max_len, dtype)
        out.append(
            jax.tree_util.tree_map(
                lambda s: jnp.broadcast_to(s, (count,) + s.shape), single
            )
        )
    return tuple(out)


def init_decode_states_paged(cfg, batch: int, max_row_len: int, dtype,
                             block_size: int, num_blocks: int):
    """Paged decode state: attention runs get per-layer page pools plus a
    shared-shape block table (one logical block id addresses the same page
    slot in every layer's pool, so a single host-side table drives the
    whole stack); recurrent runs are identical to the contiguous layout."""
    out = []
    for (mtype, count) in runs(cfg):
        if mtype == "attn":
            single = L.attention_init_cache_paged(
                cfg, batch, max_row_len, dtype, block_size, num_blocks
            )
        else:
            single = MIXERS[mtype][3](cfg, batch, max_row_len, dtype)
        out.append(
            jax.tree_util.tree_map(
                lambda s: jnp.broadcast_to(s, (count,) + s.shape), single
            )
        )
    return tuple(out)
