"""Jit'd wrappers for the streaming Pearson kernel: padding, per-chunk
accumulation for the tree-streaming path, and the shared finalization."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.pearson.pearson import M_BLK, pearson_accumulate, sublane


@jax.jit
def finalize_pearson(gram: jnp.ndarray, sums: jnp.ndarray, n_cols,
                     eps: float = 1e-8) -> jnp.ndarray:
    """(gram (K,K), sums (K,), true column count) -> (K,K) correlation.

    Shared by the single-matrix kernel wrapper and the streaming tree path:
    both accumulate the same (gram, sums) statistics, only the chunking
    differs. ``n_cols`` is the number of REAL columns accumulated (zero
    padding cancels in the mean/cov because we divide by the true count).
    """
    K = gram.shape[0]
    M = jnp.asarray(n_cols, jnp.float32)
    mu = sums / M
    ms = jnp.diag(gram) / M                      # E[x^2]
    cov = gram / M - jnp.outer(mu, mu)
    var = ms - mu * mu
    # One-pass variance suffers cancellation when |mu| >> sd: the f32 error
    # floor is ~eps32 * E[x^2]. Rows below that floor are 'constant' and
    # correlate 0 (matches the two-pass oracle's exact cancellation).
    tol = 16.0 * jnp.float32(1.19e-7) * ms + eps
    valid = var > tol
    sd = jnp.sqrt(jnp.where(valid, var, 1.0))
    pair_ok = jnp.outer(valid, valid)
    corr = jnp.where(pair_ok, cov / jnp.outer(sd, sd), 0.0)
    corr = jnp.clip(corr, -1.0, 1.0)
    return corr * (1 - jnp.eye(K)) + jnp.eye(K)


def _pad_chunk(X: jnp.ndarray):
    """Pad one (K, m) chunk to kernel tiling: K to a sublane multiple of its
    dtype, m to a lane/block multiple. Small chunks get a single block of
    the next 128-multiple instead of a full M_BLK — per-leaf padding is at
    most one block, never a full-matrix copy."""
    K, m = X.shape
    sub = sublane(X.dtype)
    Kp = int(np.ceil(max(K, sub) / sub) * sub)
    blk = M_BLK if m >= M_BLK else int(np.ceil(max(m, 128) / 128) * 128)
    Mp = int(np.ceil(m / blk) * blk)
    if (Kp, Mp) == (K, m):
        return X, blk  # already tile-aligned: no zero-fill copy
    Xp = jnp.zeros((Kp, Mp), X.dtype).at[:K, :m].set(X)
    return Xp, blk


@functools.partial(jax.jit, static_argnames=("interpret",))
def pearson_chunk(X: jnp.ndarray, interpret: Optional[bool] = None):
    """One streamed chunk (K, m) -> partial (gram (K,K), sums (K,)) in f32.

    The tree-streaming path (core/pearson.pearson_tree) sums these partials
    across leaves; zero padding contributes nothing to either statistic.
    ``interpret`` forces Pallas interpret mode (a test hook); by default it
    follows the platform (kernels/platform.resolve_interpret).
    """
    K = X.shape[0]
    Xp, blk = _pad_chunk(X)
    gram, sums = pearson_accumulate(Xp, interpret=interpret, m_blk=blk)
    return gram[:K, :K], sums[:K, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def pearson_corr(X: jnp.ndarray, interpret: Optional[bool] = None,
                 eps: float = 1e-8):
    """X: (K, M) any float dtype -> (K, K) f32 Pearson correlation matrix.

    Pads K to a sublane multiple and M to M_BLK (zero pads cancel in the
    mean/cov finalization because we divide by the true M)."""
    K, M = X.shape
    gram, sums = pearson_chunk(X, interpret=interpret)
    return finalize_pearson(gram, sums, M, eps=eps)
