"""Pearson correlation matrix over client parameter vectors (paper §IV.D,
merging-algorithm step 1).

``pearson_matrix`` is the pure-jnp two-pass implementation (the oracle for
everything else). ``pearson_tree`` is the production path: it streams the
stacked client pytree leaf by leaf through a (gram, sums) accumulator —
either the Pallas kernel in repro/kernels/pearson or a jnp dot with f32
accumulation — so the correlation never materializes the (K, M) client
matrix. Column subsampling and constant-leaf exclusion are fused into the
stream (indices are bucketed per leaf; nothing gathers over a concatenated
matrix), and a bf16-input mode halves the HBM read at scale while keeping
f32 accumulators.

``client_param_matrix`` + ``subsample_columns`` remain as the materialized
oracle pipeline for tests and benchmarks.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def pearson_matrix(X: jnp.ndarray, eps: float = 1e-8) -> jnp.ndarray:
    """X: (K, M) -> (K, K) correlation matrix, f32.

    PCC(x_i, x_j) = Cov(x_i, x_j) / (sigma_i * sigma_j). Rows with ~zero
    variance correlate 0 with everything (diag forced to 1).
    """
    Xf = X.astype(jnp.float32)
    mu = jnp.mean(Xf, axis=1, keepdims=True)
    Z = Xf - mu
    cov = Z @ Z.T / X.shape[1]
    sd = jnp.sqrt(jnp.diag(cov))
    denom = jnp.outer(sd, sd)
    corr = jnp.where(denom > eps, cov / jnp.maximum(denom, eps), 0.0)
    corr = jnp.clip(corr, -1.0, 1.0)
    K = X.shape[0]
    return corr * (1 - jnp.eye(K)) + jnp.eye(K)


def pearson_matrix_fast(X: jnp.ndarray,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Kernel-backed path (VMEM-tiled streaming accumulation)."""
    from repro.kernels.pearson.ops import pearson_corr

    return pearson_corr(X, interpret=interpret)


# Leaves that start identical across clients (constant init: norm scales,
# gate biases, decay params). Including them INFLATES the correlation
# between unrelated clients (measured: two independently initialized
# qwen3 clients correlate 0.28 instead of ~0) — beyond-paper refinement,
# see EXPERIMENTS.md §Perf H3-it2.
CONSTANT_INIT_LEAVES = ("scale", "b_fgate", "b_f", "b_i", "lam", "b")


def _leaf_views(stacked_params, exclude_constant: bool) -> List[jnp.ndarray]:
    """Stacked client params -> list of (K, m_leaf) views, deterministic
    tree_flatten order (matches client_param_matrix's column order)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(stacked_params)
    views = []
    for path, leaf in flat:
        name = [str(getattr(p, "key", "")) for p in path]
        name = name[-1] if name else ""
        if exclude_constant and name in CONSTANT_INIT_LEAVES:
            continue
        views.append(leaf.reshape(leaf.shape[0], -1))
    return views


def client_param_matrix(
    stacked_params,
    dtype=jnp.float32,
    exclude_constant: bool = False,
) -> jnp.ndarray:
    """Stacked client params (leading K axis on every leaf) -> (K, M).

    Materializes the full matrix — oracle/benchmark path only; the default
    merge path streams leaves via ``pearson_tree``."""
    return jnp.concatenate(
        [v.astype(dtype) for v in _leaf_views(stacked_params, exclude_constant)],
        axis=1,
    )


def subsample_columns(X: jnp.ndarray, n: int, seed: int = 0) -> jnp.ndarray:
    """Random coordinate subsample of the (K, M) client matrix.

    Beyond-paper optimization (§Perf H3-it3): the Pearson estimate over a
    uniform subsample of n << M coordinates concentrates at rate
    O(1/sqrt(n)); n = 1e5 gives +-0.004 on the CNN sim while cutting the
    at-scale correlation gather by M/n (~17,000x for a 1.7B model)."""
    if n <= 0 or n >= X.shape[1]:
        return X
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.choice(X.shape[1], size=n, replace=False))
    return X[:, idx]


def sample_leaf_columns(
    leaf_sizes: Sequence[int], n: int, seed: int = 0
) -> Optional[List[np.ndarray]]:
    """Draw ``subsample_columns``'s global column sample, bucketed per leaf.

    Returns per-leaf local column indices (or None for 'use everything').
    The sampled SET is identical to subsampling the concatenated matrix
    with the same seed — Pearson is invariant to column order, so the
    streamed estimate matches the materialized oracle."""
    M = int(sum(leaf_sizes))
    if n <= 0 or n >= M:
        return None
    rng = np.random.default_rng(seed)
    idx = np.sort(rng.choice(M, size=n, replace=False))
    offsets = np.concatenate([[0], np.cumsum(leaf_sizes)])
    lo = np.searchsorted(idx, offsets[:-1], side="left")
    hi = np.searchsorted(idx, offsets[1:], side="left")
    return [idx[a:b] - off for a, b, off in zip(lo, hi, offsets[:-1])]


@jax.jit
def _accumulate_chunk(gram, sums, chunk):
    """jnp fallback accumulator: one HBM pass per chunk, f32 accumulation
    regardless of input dtype (mirrors the Pallas kernel's in-VMEM cast)."""
    x = chunk.astype(jnp.float32)
    gram = gram + jax.lax.dot_general(
        x, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    return gram, sums + jnp.sum(x, axis=1)


# fused-scan path: chunk width of the packed column buffer. One scan step
# per PEARSON_SCAN_CHUNK columns keeps the XLA loop body a single
# fixed-shape dot — fewer dispatches than the per-leaf Python loop when
# the tree has many leaves (transformers: 100s).
PEARSON_SCAN_CHUNK = 16384


@functools.partial(jax.jit, static_argnames=("eps",))
def _pearson_scan_packed(views, eps: float = 1e-8):
    """Single jitted ``lax.scan`` (gram, sums) accumulation over packed
    leaf chunks: the (already subsampled / cast) per-leaf views are packed
    column-wise, zero-padded to a chunk multiple (padding cancels — the
    finalization divides by the true column count), and streamed through
    one scan. ONE dispatch for the whole tree instead of one per leaf; the
    trade is one packed (K, M') copy inside the program, so the per-leaf
    loop remains the default for the pod-sharded at-scale path where
    (K, M) must never materialize."""
    from repro.kernels.pearson.ops import finalize_pearson

    views = list(views)
    K = int(views[0].shape[0])
    n_cols = int(sum(v.shape[1] for v in views))
    chunk = min(PEARSON_SCAN_CHUNK, n_cols)
    packed = jnp.concatenate(views, axis=1)
    pad = (-n_cols) % chunk
    if pad:
        packed = jnp.pad(packed, ((0, 0), (0, pad)))
    n_chunks = packed.shape[1] // chunk

    def body(carry, i):
        gram, sums = carry
        # slice the chunk in place (no transposed rechunk copy)
        x = jax.lax.dynamic_slice_in_dim(
            packed, i * chunk, chunk, axis=1
        ).astype(jnp.float32)
        gram = gram + jax.lax.dot_general(
            x, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (gram, sums + jnp.sum(x, axis=1)), None

    (gram, sums), _ = jax.lax.scan(
        body,
        (jnp.zeros((K, K), jnp.float32), jnp.zeros((K,), jnp.float32)),
        jnp.arange(n_chunks),
    )
    return finalize_pearson(gram, sums, n_cols, eps=eps)


def pearson_tree(
    stacked_params,
    exclude_constant: bool = False,
    sample: int = 0,
    seed: int = 0,
    compute_dtype=None,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
    fused: bool = False,
    eps: float = 1e-8,
    mesh=None,
) -> jnp.ndarray:
    """Streaming tree-Pearson: stacked (K, ...) pytree -> (K, K) correlation
    without ever materializing the (K, M) client matrix.

    Each leaf is reshaped (a view), optionally column-subsampled in place,
    optionally cast to ``compute_dtype`` (bf16 halves the HBM read; both
    accumulators stay f32), and folded into a running (gram, sums) pair —
    through the Pallas kernel when ``use_kernel`` (each chunk padded
    independently, at most one block of waste per leaf) or a jnp dot
    otherwise. ``fused=True`` replaces the per-leaf Python loop with ONE
    ``lax.scan`` over packed fixed-width column chunks (fewer dispatches
    at many leaves / large K; accumulation order changes, so results
    differ from the loop at f32 rounding level — benchmarked in
    benchmarks/merge_pipeline.py, not used where bit-parity with the
    per-leaf oracle is asserted). Finalization divides by the true column
    count, shared with the kernel wrapper in kernels/pearson/ops.py.

    A compiled Pallas kernel cannot be partitioned by XLA, so on a
    multi-device ``mesh`` the kernel path runs inside a ``shard_map`` with
    every input replicated: each device gathers the client rows and
    computes the whole (K, K) result.
    """
    from repro.kernels.pearson.ops import finalize_pearson, pearson_chunk

    if fused and use_kernel:
        raise ValueError(
            "pearson_tree: fused=True is the jnp packed-scan path and "
            "cannot be combined with use_kernel=True (the Pallas kernel "
            "does its own per-chunk tiling); pick one"
        )
    views = _leaf_views(stacked_params, exclude_constant)
    if not views:
        raise ValueError("pearson_tree: no leaves to correlate")
    K = int(views[0].shape[0])
    picked = sample_leaf_columns([v.shape[1] for v in views], sample, seed)

    kept = []
    for i, v in enumerate(views):
        if picked is not None:
            if picked[i].size == 0:
                continue
            v = jnp.take(v, jnp.asarray(picked[i]), axis=1)
        if v.shape[1] == 0:
            continue  # zero-width leaf: nothing to accumulate
        if compute_dtype is not None:
            v = v.astype(compute_dtype)
        kept.append(v)
    if not kept:
        raise ValueError("pearson_tree: no columns left to correlate")

    if fused:
        return _pearson_scan_packed(kept, eps=eps)

    n_cols = sum(int(v.shape[1]) for v in kept)

    def accumulate(*views):
        gram = jnp.zeros((K, K), jnp.float32)
        sums = jnp.zeros((K,), jnp.float32)
        for v in views:
            if use_kernel:
                g, s = pearson_chunk(v, interpret=interpret)
                gram, sums = gram + g, sums + s
            else:
                gram, sums = _accumulate_chunk(gram, sums, v)
        return gram, sums

    if use_kernel and mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P

        accumulate = jax.shard_map(accumulate, mesh=mesh, in_specs=P(),
                                   out_specs=P(), check_vma=False)
    gram, sums = accumulate(*kept)
    return finalize_pearson(gram, sums, n_cols, eps=eps)


# ---------------------------------------------------------------------------
# sketched similarity: the K x d client sketch (tentpole layer 1)
# ---------------------------------------------------------------------------
#
# At population scale the similarity input must never be a (K, M) matrix —
# not even leaf by leaf, because the PLANNER downstream would still need
# K x K. The sketch path reduces every client to a d-dimensional summary
# in ONE streaming pass over the stacked tree, and all similarity math
# (per-block Pearson, cross-block representative Pearson) runs on (·, d)
# row subsets of the sketch.
#
# Two sketch modes, one concentration knob (``sketch_dim``):
#
#   subsample — gather ``sketch_dim`` uniformly sampled coordinates
#               (bucketed per leaf via ``sample_leaf_columns``, the same
#               sampled SET as ``corr_sample``). Pearson over the sketch
#               is then the EXACT Pearson of the subsampled coordinates:
#               estimate error concentrates at O(1/sqrt(sketch_dim))
#               (§Perf H3-it3 measured +-0.004 at d=1e5 on the CNN sim).
#   project   — Gaussian random projection: sketch = X_centered @ P with
#               P (M, d) iid N(0, 1); cosine similarity of the projected
#               centered rows estimates Pearson with the JL guarantee,
#               error O(1/sqrt(sketch_dim)) independent of M. Centering
#               is exact and stays streaming: proj(x - mu 1) =
#               proj(x) - mu * proj(1), with mu and proj(1) accumulated
#               alongside the projection. Sampling-free, so adversarial
#               coordinate structure cannot hide in the unsampled set.
#
# ``pearson_sketch_rows`` is the shared finalization: a jit-traceable
# similarity over any row subset of the sketch, used by the blocked
# planner for per-block and cross-block correlations.


def sketch_tree(
    stacked_params,
    sketch_dim: int,
    seed: int = 0,
    mode: str = "subsample",
    exclude_constant: bool = False,
    compute_dtype=None,
) -> jnp.ndarray:
    """Stacked (K, ...) pytree -> (K, d) similarity sketch, streaming per
    leaf (the (K, M) client matrix is never materialized).

    ``mode="subsample"`` gathers ``sketch_dim`` sampled coordinates;
    ``mode="project"`` accumulates a Gaussian random projection of the
    mean-centered rows. Both are deterministic in ``seed``. See
    ``pearson_sketch_rows`` for the matching similarity finalization."""
    if sketch_dim <= 0:
        raise ValueError("sketch_tree: sketch_dim must be > 0")
    views = _leaf_views(stacked_params, exclude_constant)
    if not views:
        raise ValueError("sketch_tree: no leaves to sketch")
    if mode == "subsample":
        picked = sample_leaf_columns(
            [v.shape[1] for v in views], sketch_dim, seed
        )
        cols = []
        for i, v in enumerate(views):
            if picked is not None:
                if picked[i].size == 0:
                    continue
                v = jnp.take(v, jnp.asarray(picked[i]), axis=1)
            if v.shape[1] == 0:
                continue
            if compute_dtype is not None:
                v = v.astype(compute_dtype)
            cols.append(v.astype(jnp.float32))
        return jnp.concatenate(cols, axis=1)
    if mode != "project":
        raise ValueError(
            f"sketch_tree: mode must be 'subsample' or 'project', got {mode!r}"
        )
    K = int(views[0].shape[0])
    d = int(sketch_dim)
    key = jax.random.PRNGKey(seed)
    proj = jnp.zeros((K, d), jnp.float32)      # sum_leaf leaf @ P_leaf
    ones_p = jnp.zeros((d,), jnp.float32)      # proj of the all-ones vector
    sums = jnp.zeros((K,), jnp.float32)        # per-row coordinate sums
    M = 0
    for i, v in enumerate(views):
        m = int(v.shape[1])
        if m == 0:
            continue
        if compute_dtype is not None:
            v = v.astype(compute_dtype)
        P = jax.random.normal(jax.random.fold_in(key, i), (m, d), jnp.float32)
        proj = proj + jnp.matmul(
            v.astype(jnp.float32), P, preferred_element_type=jnp.float32
        )
        ones_p = ones_p + jnp.sum(P, axis=0)
        sums = sums + jnp.sum(v.astype(jnp.float32), axis=1)
        M += m
    mu = sums / jnp.float32(M)
    # proj(x - mu 1) = proj(x) - mu * proj(1): exact mean-centering of the
    # original rows, computed entirely in sketch space
    return proj - mu[:, None] * ones_p[None, :]


def pearson_sketch_rows(rows: jnp.ndarray, mode: str = "subsample",
                        eps: float = 1e-8) -> jnp.ndarray:
    """Similarity over a (k, d) row subset of a ``sketch_tree`` sketch —
    jit-traceable, so the blocked planner can vmap it over blocks.

    subsample sketches carry raw coordinates: full Pearson (center over
    the d sampled columns). project sketches are already mean-centered in
    the ORIGINAL space, so the estimator is the cosine of the projected
    rows — re-centering in sketch space would double-center."""
    if mode == "subsample":
        return pearson_matrix(rows, eps=eps)
    rf = rows.astype(jnp.float32)
    norms = jnp.sqrt(jnp.sum(rf * rf, axis=1))
    denom = jnp.outer(norms, norms)
    sim = jnp.where(denom > eps, (rf @ rf.T) / jnp.maximum(denom, eps), 0.0)
    sim = jnp.clip(sim, -1.0, 1.0)
    k = rows.shape[0]
    return sim * (1 - jnp.eye(k)) + jnp.eye(k)


def pearson_round_program(
    exclude_constant: bool = False,
    sample: int = 0,
    seed: int = 0,
    compute_dtype=None,
    fused: bool = False,
):
    """The round-level correlation program as ONE jit-able function over a
    stacked (K, ...) client pytree — the streaming ``pearson_tree`` path,
    closed over its host-side options so ``jax.jit``/``.lower`` see a
    single tree argument. Under a mesh this is what the pod-sharded
    dry-run analyzes: per-leaf (gram, sums) accumulation, with the K x K
    reduction as the only cross-pod collective — no (K, M) client matrix
    is ever materialized.
    """

    def program(stacked_params):
        return pearson_tree(
            stacked_params,
            exclude_constant=exclude_constant,
            sample=sample,
            seed=seed,
            compute_dtype=compute_dtype,
            fused=fused,
        )

    return program
