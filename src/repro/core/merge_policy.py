"""Pluggable merge policies (registry-backed extension point #1).

A :class:`MergePolicy` answers the two questions the simulator asks on a
merge round, and nothing else:

  similarity(x_locals) -> (K, K) numpy matrix over the round's local models
  plan(sim_matrix, weights, active) -> MergePlan (fixed-shape merge matrix)

The simulator keeps only the shard/weight/control bookkeeping; which
clients merge, and why, is the policy's business. Policies are registered
by name and selected via ``FLConfig.merge_policy``:

  pearson       — the paper's algorithm: streaming device tree-Pearson
                  (or the host numpy oracle, per FLConfig.pipeline) +
                  greedy threshold grouping. Numerics are unchanged from
                  the pre-registry FederatedSimulator._correlate path.
  cosine        — cosine similarity of the raw parameter vectors (no mean
                  centering), same greedy grouping.
  random-pairs  — seeded random pairing of active clients; the ablation
                  control for "does *which* clients merge matter?".
  none          — never merges (identity plan); lets merge scheduling stay
                  on without any population change.

Register your own with ``@MERGE_POLICIES.register("name")`` — the class is
constructed with the run's FLConfig and the simulator's mesh (None when
unmeshed).
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.core.merging import (
    MergePlan,
    blocked_merge_plan,
    build_merge_plan,
    plan_from_groups,
)
from repro.core.pearson import (
    client_param_matrix,
    pearson_matrix,
    pearson_sketch_rows,
    pearson_tree,
    sketch_tree,
    subsample_columns,
)
from repro.utils.registry import Registry

MERGE_POLICIES: Registry["MergePolicy"] = Registry("merge policy")


class MergePolicy:
    """Base policy: similarity is abstract, planning is the paper's greedy
    threshold grouping over whatever similarity the subclass computes."""

    def __init__(self, fl, mesh=None):
        self.fl = fl
        # kernel-backed similarity programs run inside the simulator's mesh
        # (see core/pearson.pearson_tree)
        self.mesh = mesh

    def similarity(self, x_locals) -> np.ndarray:
        raise NotImplementedError

    def plan(self, sim_matrix: np.ndarray, weights: np.ndarray,
             active: np.ndarray) -> MergePlan:
        return build_merge_plan(
            sim_matrix,
            data_sizes=weights.astype(np.int64),
            threshold=self.fl.threshold,
            max_group_size=self.fl.max_group_size,
            active=active.astype(bool),
            alpha=self.fl.alpha,
        )

    def merge_plan(self, x_locals, weights: np.ndarray,
                   active: np.ndarray) -> MergePlan:
        """The simulator's one merge-round entry point: local models in,
        MergePlan out. The base route materializes the full similarity
        matrix and runs the flat greedy plan; scale-aware policies
        (pearson-blocked) override it to never touch a K x K object."""
        return self.plan(self.similarity(x_locals), weights, active)

    # ---- shared helper ---------------------------------------------------
    def _materialized_matrix(self, x_locals) -> jnp.ndarray:
        """(K, M) client matrix with the config's exclusion/subsampling
        applied — the materialized oracle layout."""
        X = client_param_matrix(
            x_locals, exclude_constant=self.fl.corr_exclude_constant
        )
        return subsample_columns(X, self.fl.corr_sample, seed=self.fl.seed)


@MERGE_POLICIES.register("pearson")
class PearsonPolicy(MergePolicy):
    """The paper's Pearson-threshold policy (§IV.D).

    Device pipeline: streaming tree-Pearson — per-leaf (gram, sums)
    accumulation (optionally through the Pallas kernel) with fused column
    subsampling; only the K x K result crosses to host. Host pipeline: the
    original materialized (K, M) oracle."""

    def similarity(self, x_locals) -> np.ndarray:
        return np.asarray(self.device_similarity(x_locals)) \
            if self.fl.pipeline != "host" else self._host_similarity(x_locals)

    def device_similarity(self, x_locals) -> jnp.ndarray:
        """jnp similarity program — also called from inside the compiled
        round engine's fused merge step (core/engine.py), so it must stay
        jit-traceable. The backend (Pallas kernel vs jnp accumulation) is
        the config's resolved choice (auto: kernel on TPU/GPU)."""
        return pearson_tree(
            x_locals,
            exclude_constant=self.fl.corr_exclude_constant,
            sample=self.fl.corr_sample,
            seed=self.fl.seed,
            use_kernel=self.fl.pearson_kernel,
            interpret=self.fl.pearson_interpret,
            mesh=self.mesh,
        )

    def _host_similarity(self, x_locals) -> np.ndarray:
        X = self._materialized_matrix(x_locals)
        if self.fl.pearson_kernel:
            from repro.core.pearson import pearson_matrix_fast
            return np.asarray(pearson_matrix_fast(
                jnp.asarray(X), interpret=self.fl.pearson_interpret))
        return np.asarray(pearson_matrix(jnp.asarray(X)))


@MERGE_POLICIES.register("pearson-blocked")
class PearsonBlockedPolicy(PearsonPolicy):
    """Scale-generic Pearson merging: blocked hierarchical planning over
    sketched similarity (core/merging.blocked_merge_plan — the paper's
    greedy scan per ``FLConfig.block_size``-sized pod, then once more
    across block representatives).

    ``sketch_dim > 0`` reduces every client to a d-dimensional sketch
    (core/pearson.sketch_tree) in one streaming pass; all similarity
    requests are then (·, d) row subsets — neither the (K, M) client
    matrix nor the K x K correlation ever exists. ``sketch_dim == 0``
    keeps exact streaming tree-Pearson (documented O(K^2) similarity —
    the paper-scale / bit-parity configuration; with ``block_size >= K``
    this policy IS the flat ``pearson`` policy, plan for plan).

    The engine pipeline detects ``blocked`` and plans/mixes per block on
    device with fixed-shape (nb, B, B) matrices (core/engine.py)."""

    blocked = True

    def effective_block_size(self, K: int) -> int:
        b = self.fl.block_size
        return K if b <= 0 else min(int(b), K)

    def device_sketch(self, x_locals) -> jnp.ndarray:
        """(K, d) sketch — jit-traceable, used in-engine."""
        return sketch_tree(
            x_locals,
            self.fl.sketch_dim,
            seed=self.fl.seed,
            mode=self.fl.sketch_mode,
            exclude_constant=self.fl.corr_exclude_constant,
        )

    def merge_plan(self, x_locals, weights: np.ndarray,
                   active: np.ndarray) -> MergePlan:
        K = _stacked_k(x_locals)
        if self.fl.sketch_dim > 0:
            rows = np.asarray(self.device_sketch(x_locals))
            mode = self.fl.sketch_mode

            def corr_fn(idx):
                return np.asarray(
                    pearson_sketch_rows(jnp.asarray(rows[idx]), mode=mode)
                )
        else:
            full = self.similarity(x_locals)

            def corr_fn(idx):
                return full[np.ix_(idx, idx)]

        return blocked_merge_plan(
            corr_fn,
            K,
            data_sizes=weights.astype(np.int64),
            threshold=self.fl.threshold,
            max_group_size=self.fl.max_group_size,
            active=active.astype(bool),
            alpha=self.fl.alpha,
            block_size=self.effective_block_size(K),
        )


@MERGE_POLICIES.register("cosine")
class CosinePolicy(MergePolicy):
    """Cosine similarity of the raw local parameter vectors. Unlike
    Pearson this keeps the mean, so constant-offset clients still look
    alike — the natural contrast policy from the robust-aggregation
    literature (Krum/FoolsGold both reason over cosine geometry)."""

    def similarity(self, x_locals) -> np.ndarray:
        X = np.asarray(self._materialized_matrix(x_locals), np.float64)
        norms = np.linalg.norm(X, axis=1)
        denom = np.outer(norms, norms)
        sim = np.divide(X @ X.T, denom, out=np.zeros_like(denom),
                        where=denom > 1e-12)
        np.fill_diagonal(sim, 1.0)
        return np.clip(sim, -1.0, 1.0).astype(np.float32)


@MERGE_POLICIES.register("random-pairs")
class RandomPairsPolicy(MergePolicy):
    """Seeded random pairing of the active clients — similarity-free
    control. If random merging matches Pearson merging, the similarity
    signal carries no information on that workload."""

    def similarity(self, x_locals) -> np.ndarray:
        return np.eye(_stacked_k(x_locals), dtype=np.float32)

    def plan(self, sim_matrix, weights, active) -> MergePlan:
        K = sim_matrix.shape[0]
        rng = np.random.default_rng(self.fl.seed)
        act = np.flatnonzero(np.asarray(active) > 0)
        perm = rng.permutation(act)
        groups = [sorted(map(int, perm[i : i + 2]))
                  for i in range(0, len(perm) - 1, 2)]
        unmerged = [int(perm[-1])] if len(perm) % 2 else []
        return plan_from_groups(K, groups, unmerged, weights.astype(np.int64),
                                alpha=self.fl.alpha)


@MERGE_POLICIES.register("none")
class NoMergePolicy(MergePolicy):
    """Identity plan: every active client stays independent."""

    def similarity(self, x_locals) -> np.ndarray:
        return np.eye(_stacked_k(x_locals), dtype=np.float32)

    def plan(self, sim_matrix, weights, active) -> MergePlan:
        K = sim_matrix.shape[0]
        unmerged = [int(i) for i in np.flatnonzero(np.asarray(active) > 0)]
        return plan_from_groups(K, [], unmerged, weights.astype(np.int64),
                                alpha=self.fl.alpha)


def _stacked_k(x_locals) -> int:
    """Leading (client) axis length of a stacked pytree."""
    import jax
    return jax.tree_util.tree_leaves(x_locals)[0].shape[0]


def make_merge_policy(fl, mesh=None) -> MergePolicy:
    return MERGE_POLICIES.get(fl.merge_policy)(fl, mesh)
