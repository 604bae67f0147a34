"""Plain reference of Qwen3-1.7B (a dense decoder with grouped-query
attention, RMSNorm on queries and keys, rotary positions, SwiGLU and a tied
output head), written from the published configuration alone. It imports
nothing of the program under test.

``init_weights`` makes the benchmark's random weights on the device, in the
served dtype, in one call. ``logits`` runs the full forward pass over one
sequence in float32 at full matmul precision, layer by layer so that it
fits beside nothing else, and returns the logits at the positions asked
for. ``quant="fp8"`` is the control: every weight rounded per output
channel to float8 (e4m3) before the same float32 computation.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PAD = 512  # sequences are padded at the end to a multiple of this (causal)


def shapes(m: dict) -> Dict[str, tuple]:
    L, d, dh = m["num_hidden_layers"], m["hidden_size"], m["head_dim"]
    q = m["num_attention_heads"] * dh
    kv = m["num_key_value_heads"] * dh
    ff = m["intermediate_size"]
    return {
        "embed": (m["vocab_size"], d),
        "attn_norm": (L, d), "q_norm": (L, dh), "k_norm": (L, dh),
        "wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv), "wo": (L, q, d),
        "mlp_norm": (L, d),
        "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d),
        "final_norm": (d,),
    }


def init_weights(m: dict, seed: int):
    """Random weights from the seed: matrices N(0, 1/fan_in) (output
    projections further scaled by 1/sqrt(2 * layers)), the embedding
    N(0, 0.02^2), norm scales N(1, 0.1^2). Made in one jitted call, in the
    configuration's dtype."""
    dtype = jnp.dtype(m["torch_dtype"])
    L = m["num_hidden_layers"]
    sh = shapes(m)

    def make(key):
        keys = dict(zip(sorted(sh), jax.random.split(key, len(sh))))
        out = {}
        for name, s in sh.items():
            z = jax.random.normal(keys[name], s, jnp.float32)
            if name.endswith("norm"):
                v = 1.0 + 0.1 * z
            elif name == "embed":
                v = 0.02 * z
            else:
                v = z / np.sqrt(s[-2])
                if name in ("wo", "w_down"):
                    v = v / np.sqrt(2 * L)
            out[name] = v.astype(dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotary positions on (S, heads, D), rotating the two halves."""
    S, _, D = x.shape
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _quant(w, quant, axis: int = -2):
    """Weight as the reference (float32 of the served values) or as the
    control (rounded per output channel, the reduction over ``axis``, to
    float8 e4m3)."""
    w = w.astype(jnp.float32)
    if quant is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.lru_cache(maxsize=None)
def _layer_fn(H: int, Kv: int, D: int, eps: float, theta: float, quant):
    def layer(x, w, l):
        g = {k: _quant(jax.lax.dynamic_index_in_dim(w[k], l, 0, keepdims=False),
                       quant if k.startswith("w") else None)
             for k in w}
        S = x.shape[0]
        h = _rms(x, g["attn_norm"], eps)
        q = (h @ g["wq"]).reshape(S, H, D)
        k = (h @ g["wk"]).reshape(S, Kv, D)
        v = (h @ g["wv"]).reshape(S, Kv, D)
        q = _rope(_rms(q, g["q_norm"], eps), theta)
        k = _rope(_rms(k, g["k_norm"], eps), theta)
        qg = q.reshape(S, Kv, H // Kv, D)
        s = jnp.einsum("skgd,tkd->kgst", qg, k) / np.sqrt(D)
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgst,tkd->skgd", p, v).reshape(S, H * D)
        x = x + o @ g["wo"]
        h = _rms(x, g["mlp_norm"], eps)
        return x + (jax.nn.silu(h @ g["w_gate"]) * (h @ g["w_up"])) @ g["w_down"]

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, quant):
    def head(x, final_norm, embed, rows):
        h = _rms(x[rows], final_norm.astype(jnp.float32), eps)
        return h @ _quant(embed, quant, axis=-1).T

    return jax.jit(head)


def logits(m: dict, w, tokens: np.ndarray, rows: Sequence[int],
           quant=None) -> jnp.ndarray:
    """Float32 logits (len(rows), vocab) of the sequence ``tokens`` at the
    positions ``rows`` (the logits at position i predict token i + 1)."""
    S = len(tokens)
    Sp = -(-S // PAD) * PAD
    ids = np.zeros(Sp, np.int32)
    ids[:S] = tokens
    H, Kv, D = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    per_layer = {k: w[k] for k in ("attn_norm", "q_norm", "k_norm", "wq", "wk",
                                   "wv", "wo", "mlp_norm", "w_gate", "w_up",
                                   "w_down")}
    with jax.default_matmul_precision("highest"):
        embed = _quant(w["embed"], quant, axis=-1)
        x = jnp.take(embed, jnp.asarray(ids), axis=0)
        del embed
        layer = _layer_fn(H, Kv, D, eps, theta, quant)
        for li in range(m["num_hidden_layers"]):
            x = layer(x, per_layer, li)
        return _head_fn(eps, quant)(x, w["final_norm"], w["embed"],
                                    jnp.asarray(np.asarray(rows, np.int32)))
