"""Jit'd wrapper: GQA head folding, padding, scale handling."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_prefill.flash_prefill import KV_BLK, Q_BLK, flash_prefill


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def flash_prefill_attention(q, k, v, causal: bool = True, window: int = 0,
                            interpret: Optional[bool] = None):
    """q: (B, S, Hq, D); k, v: (B, S, Kv, D) -> (B, S, Hq, D).
    ``interpret`` forces Pallas interpret mode (a test hook); by default it
    follows the platform (kernels/platform.resolve_interpret)."""
    B, S, Hq, D = q.shape
    Kv = k.shape[2]
    G = Hq // Kv
    Sp = int(np.ceil(S / max(Q_BLK, KV_BLK)) * max(Q_BLK, KV_BLK))
    Dp = int(np.ceil(D / 128) * 128)
    nQ = Sp // Q_BLK

    scale = 1.0 / np.sqrt(D)
    # (B, S, Hq, D) -> (B, Kv, nQ, G*Q_BLK, D): fold G query heads of each
    # kv head into the q-tile row axis
    qg = jnp.moveaxis(q.reshape(B, S, Kv, G, D), 1, 3)      # (B, Kv, G, S, D)
    qp = jnp.zeros((B, Kv, G, Sp, Dp), q.dtype).at[..., :S, :D].set(qg)
    qp = qp.reshape(B, Kv, G, nQ, Q_BLK, Dp).transpose(0, 1, 3, 2, 4, 5)
    qp = qp.reshape(B, Kv, nQ, G * Q_BLK, Dp)

    kt = jnp.moveaxis(k, 1, 2)                              # (B, Kv, S, D)
    vt = jnp.moveaxis(v, 1, 2)
    kp = jnp.zeros((B, Kv, Sp, Dp), k.dtype).at[:, :, :S, :D].set(kt)
    vp = jnp.zeros((B, Kv, Sp, Dp), v.dtype).at[:, :, :S, :D].set(vt)

    o = flash_prefill(qp, kp, vp, causal=causal, window=window, s_valid=S,
                      scale=scale, interpret=interpret)
    o = o.reshape(B, Kv, nQ, G, Q_BLK, Dp).transpose(0, 1, 3, 2, 4, 5)
    o = o.reshape(B, Kv, G, Sp, Dp)[..., :S, :D]
    return jnp.moveaxis(o, 3, 1).reshape(B, S, Hq, D)
