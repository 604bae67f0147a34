"""RG-LRU recurrent block (RecurrentGemma / Griffin, arXiv:2402.19427).

Temporal mixing: x -> [gelu gate branch] * [causal conv1d -> RG-LRU], -> out
projection. The RG-LRU recurrence

    a_t = exp(-c * softplus(Lambda) * r_t),   r_t = sigmoid(W_r xi_t)
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * xi_t)

is a *linear* (elementwise) recurrence, so training/prefill use
``jax.lax.associative_scan`` (log-depth parallel prefix — the TPU-native
adaptation; a sequential scan would leave the VPU idle). Decode carries
(h, conv ring) state. Recurrence math in f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import dense_init

_CONV_W = 4
_C = 8.0


def rglru_init(key, cfg, dtype):
    d, dr = cfg.d_model, cfg.rglru_width or cfg.d_model
    ks = jax.random.split(key, 6)
    return {
        "w_gate": dense_init(ks[0], d, dr, dtype),
        "w_in": dense_init(ks[1], d, dr, dtype),
        "conv": (jax.random.normal(ks[2], (_CONV_W, dr), jnp.float32) * 0.1).astype(dtype),
        "w_r": dense_init(ks[3], dr, dr, dtype),
        "w_i": dense_init(ks[4], dr, dr, dtype),
        # Lambda init so that a ~ U[0.9, 0.999]^c-ish (stable memory)
        "lam": jnp.asarray(
            np.log(np.expm1(-np.log(np.linspace(0.9, 0.999, dr)) / _C)),
            jnp.float32,
        ),
        "w_out": dense_init(ks[5], dr, d, dtype,
                            scale=1.0 / np.sqrt(dr) / np.sqrt(2 * cfg.num_layers)),
    }


def _causal_conv_full(w, x):
    """Depthwise causal conv, x (B,S,Dr), w (W,Dr)."""
    pads = jnp.pad(x, ((0, 0), (_CONV_W - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(_CONV_W):
        out = out + pads[:, i : i + x.shape[1], :].astype(jnp.float32) * w[i].astype(jnp.float32)
    return out.astype(x.dtype)


def _gates(params, xi):
    r = jax.nn.sigmoid((xi @ params["w_r"]).astype(jnp.float32))
    i = jax.nn.sigmoid((xi @ params["w_i"]).astype(jnp.float32))
    log_a = -_C * jax.nn.softplus(params["lam"]) * r  # (..., Dr) f32
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (
        i * xi.astype(jnp.float32)
    )
    return a, b


def rglru_fwd(params, cfg, x, positions=None, return_state: bool = False):
    """x (B,S,D) -> (B,S,D). Parallel prefix over the linear recurrence."""
    gate = jax.nn.gelu((x @ params["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    xi = _causal_conv_full(params["conv"], x @ params["w_in"])
    a, b = _gates(params, xi)  # (B,S,Dr) f32

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    y = (h.astype(x.dtype) * gate) @ params["w_out"]
    if return_state:
        # the conv window the next token sees; a prompt shorter than it
        # leaves the conv's zero padding in front
        u = jnp.pad(x @ params["w_in"], ((0, 0), (_CONV_W - 1, 0), (0, 0)))
        state = {"h": h[:, -1, :], "conv": u[:, -(_CONV_W - 1):, :]}
        return y, state
    return y


def rglru_decode(params, cfg, x, state, pos=None):
    """One-step decode. x (B,1,D); state {h: (B,Dr) f32, conv: (B,W-1,Dr)}."""
    gate = jax.nn.gelu((x @ params["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    u = x @ params["w_in"]  # (B,1,Dr)
    hist = jnp.concatenate([state["conv"], u], axis=1)  # (B,W,Dr)
    xi = jnp.einsum(
        "bwd,wd->bd", hist.astype(jnp.float32), params["conv"].astype(jnp.float32)
    ).astype(x.dtype)[:, None, :]
    a, b = _gates(params, xi)  # (B,1,Dr)
    h = a[:, 0] * state["h"] + b[:, 0]
    y = (h[:, None, :].astype(x.dtype) * gate) @ params["w_out"]
    return y, {"h": h, "conv": hist[:, 1:, :]}


def rglru_init_state(cfg, batch: int, dtype):
    dr = cfg.rglru_width or cfg.d_model
    return {
        "h": jnp.zeros((batch, dr), jnp.float32),
        "conv": jnp.zeros((batch, _CONV_W - 1, dr), dtype),
    }
