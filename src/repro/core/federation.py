"""Federated-learning simulator: rounds, fault injection, and the paper's
merge-at-round-t intermediary-node mechanism.

The simulator owns all *host-side* state (numpy client shards, merge
bookkeeping, fault schedules) and calls one jitted round function per
communication round — or, with ``FLConfig.pipeline="engine"``, hands the
whole loop to the compiled round engine (core/engine.RoundEngine), which
runs segments of rounds under one ``lax.scan`` and keeps this class as
the thin host shell (shard bookkeeping, records, checkpoints). WHO merges is delegated to the MergePolicy named by
``FLConfig.merge_policy`` (core/merge_policy.MERGE_POLICIES); the
scenario owns its data attacks and applies them to the shards here at
construction (core/scenarios.SCENARIOS has the registered factories). Merging never changes device-side shapes: retired
clients keep their slot with active=0, and their data is concatenated into
the representative's shard (the intermediary node answers for the group —
paper §IV.D "managing federated learning rounds in place of the original
nodes"). Communication accounting reads the active mask as it stood when
the round trained (pre-merge on merge rounds).

Mesh-aware mode: pass a Mesh with a 'pod' axis and the stacked client
axis — local controls/models, per-round batch stacks, the losses vector,
and the flat shard-row buffers — carries a NamedSharding over 'pod'
(globals replicated), so the same simulator drives the pod-sharded
production layout that launch/fl_dryrun.py analyzes. The device pipeline
also double-buffers the batch gather: round t+1's gather is dispatched
while round t computes (FLConfig.overlap_gather).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field, replace as dc_replace
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import sharding as SH
from repro.core.merge_policy import make_merge_policy
from repro.core.merging import (
    apply_merge,
    apply_merge_device,
    intermediary_models,
    merged_data_sizes,
)
from repro.core.scaffold import (
    AlgoConfig,
    init_controls,
    make_aggregate_fn,
    make_round_fn,
    make_train_fn,
)
from repro.data.attacks import DataAttack
from repro.data.faults import NetworkDelay, PacketLoss
from repro.utils.pytree import tree_bytes


@dataclass(frozen=True)
class FLConfig:
    algo: AlgoConfig = AlgoConfig()
    num_rounds: int = 10
    local_epochs: int = 2
    steps_per_epoch: int = 15
    batch_size: int = 32
    # the paper's merging technique
    # partial participation: fraction of ACTIVE clients sampled per round
    # (1.0 = full participation, the paper's setting)
    participation: float = 1.0
    merge_enabled: bool = True
    # which MergePolicy decides the grouping on merge rounds:
    # "pearson" (the paper) | "cosine" | "random-pairs" | "none" — see
    # core/merge_policy.MERGE_POLICIES
    merge_policy: str = "pearson"
    # the merge schedule: the set of rounds on which the policy runs.
    # None means "derive from the deprecated merge_round/merge_rounds
    # kwargs" (__post_init__ normalizes all three into one sorted tuple).
    merge_at: Optional[Tuple[int, ...]] = None
    threshold: float = 0.7
    max_group_size: int = 3
    alpha: str = "uniform"
    # beyond-paper refinements (§Perf H3): estimate the correlation from a
    # random coordinate subsample (0 = use all params) and/or exclude
    # constant-initialized leaves that inflate cross-client correlation
    corr_sample: int = 0
    corr_exclude_constant: bool = False
    # population scale (merge_policy="pearson-blocked", DESIGN.md §9):
    # plan within fixed-size blocks of consecutive clients, then across
    # block representatives (0 = one block, the flat paper planner) ...
    block_size: int = 0
    # ... over a d-dimensional per-client similarity sketch
    # (core/pearson.sketch_tree; 0 = exact streaming tree-Pearson). The
    # concentration knob: estimate error is O(1/sqrt(sketch_dim)).
    sketch_dim: int = 0
    # "subsample" (exact Pearson over d sampled coordinates) or "project"
    # (Gaussian random projection of the centered rows, cosine estimator)
    sketch_mode: str = "subsample"
    # DEPRECATED aliases for merge_at, kept as accepted kwargs: the single
    # first merge round plus the tuple of re-merge rounds. They are left
    # exactly as passed (None when unset) — merge_at is the one field to
    # read. Aliases that contradict an explicit merge_at raise — never a
    # silently ignored schedule.
    merge_round: Optional[int] = None
    merge_rounds: Optional[Tuple[int, ...]] = None
    # which implementation accumulates the streamed correlation chunks:
    # "auto" (default) picks the Pallas kernel on TPU/GPU and the jnp
    # accumulation on CPU; "pallas"/"jnp" force one backend.
    pearson_backend: str = "auto"
    # DEPRECATED alias for pearson_backend, kept as an accepted kwarg and
    # left exactly as passed (None when unset): True forces the Pallas
    # kernel, False forces jnp. A value that contradicts an explicit
    # pearson_backend raises — never a silently ignored override.
    use_kernel_pearson: Optional[bool] = None
    # "device" (default): zero-copy streaming merge pipeline — per-leaf
    # tree-Pearson, jitted merge-apply with donated buffers, on-device
    # batch sampling; no (K, M) materialization, no mid-round device_get.
    # "host": the original numpy oracle pipeline (materialized client
    # matrix, f64 host merge-apply, numpy batch gather) kept for A/B
    # parity tests and benchmarks.
    # "engine": the compiled round engine (core/engine.RoundEngine) —
    # segments of rounds under one lax.scan, on-device merge planning,
    # fixed-capacity stale-delta ring buffers; device/host remain the
    # per-round oracles it is parity-tested against.
    pipeline: str = "device"
    # engine pipeline: cap on rounds per compiled scan segment (bounds the
    # stacked per-round outputs a segment materializes for eval)
    engine_max_segment: int = 32
    # double-buffered batch gather (device pipeline): round t+1's gather is
    # dispatched while round t's round_fn computes, so the gather is off
    # the round loop's critical path. Off = the synchronous oracle order.
    overlap_gather: bool = True
    seed: int = 0

    def __post_init__(self):
        # normalize the merge schedule into merge_at. The deprecated
        # merge_round/merge_rounds kwargs still work on their own and are
        # kept verbatim (so a __dict__/replace round-trip carries exactly
        # what the caller set); when both forms are passed, the aliases
        # must be contained in merge_at — a contradiction raises rather
        # than silently picking one schedule.
        if self.merge_at is None:
            # historical semantics: merge at merge_round (default 4) plus
            # any extra merge_rounds
            first = 4 if self.merge_round is None else int(self.merge_round)
            at = tuple(sorted(
                {first} | {int(t) for t in (self.merge_rounds or ())}
            ))
        else:
            at = tuple(sorted({int(t) for t in self.merge_at}))
            # only what the caller actually passed constrains merge_at —
            # no default merge_round is injected here
            passed = set() if self.merge_round is None else {int(self.merge_round)}
            passed |= {int(t) for t in (self.merge_rounds or ())}
            if not passed <= set(at):
                raise ValueError(
                    f"conflicting merge schedule: merge_at={at} vs "
                    f"deprecated merge_round/merge_rounds="
                    f"{tuple(sorted(passed))}; set merge_at only (leave "
                    f"the deprecated kwargs unset)"
                )
        object.__setattr__(self, "merge_at", at)
        # normalize the Pearson backend choice; the deprecated
        # use_kernel_pearson alias stays verbatim (same pattern as
        # merge_round/merge_rounds above) and only constrains the choice
        if self.pearson_backend not in ("auto", "pallas", "jnp"):
            raise ValueError(
                f"FLConfig.pearson_backend must be 'auto', 'pallas' or "
                f"'jnp', got {self.pearson_backend!r}"
            )
        if self.use_kernel_pearson is not None and self.pearson_backend != "auto":
            want = "pallas" if self.use_kernel_pearson else "jnp"
            if want != self.pearson_backend:
                raise ValueError(
                    f"conflicting Pearson backend: pearson_backend="
                    f"{self.pearson_backend!r} vs deprecated "
                    f"use_kernel_pearson={self.use_kernel_pearson} "
                    f"(= {want!r}); set pearson_backend only"
                )

    @property
    def pearson_kernel(self) -> bool:
        """Resolved backend decision: route the streamed correlation
        chunks through the Pallas kernel? Explicit settings win; "auto"
        picks the kernel on accelerators and jnp accumulation on CPU."""
        if self.pearson_backend != "auto":
            return self.pearson_backend == "pallas"
        if self.use_kernel_pearson is not None:
            return bool(self.use_kernel_pearson)
        return jax.default_backend() in ("tpu", "gpu")

    @property
    def pearson_interpret(self) -> bool:
        """Pallas interpret mode: only off on a real accelerator."""
        from repro.kernels.platform import resolve_interpret
        return resolve_interpret()

    @property
    def local_steps(self) -> int:
        return self.local_epochs * self.steps_per_epoch


@dataclass
class Scenario:
    """Adverse conditions (paper §V), composable: a scenario owns its data
    attacks (applied by the simulator to the client shards at construction,
    via :meth:`apply_data_attacks`), its model attacks (per-round update
    scaling), and its network faults (packet loss / delay schedules).
    Registered factories live in core/scenarios.SCENARIOS."""
    name: str = "normal"
    # data poisoning: specs applied to shards before any training
    data_attacks: Tuple[DataAttack, ...] = ()
    model_poison: Dict[int, float] = field(default_factory=dict)
    packet_loss: Optional[PacketLoss] = None
    # stale updates: a delayed client's delta is excluded from its round's
    # aggregation and applied (weighted) when it "arrives" d rounds later
    network_delay: Optional[NetworkDelay] = None
    # adaptive adversary (core/adversary.Adversary): hooked into the round
    # loop after local training and before similarity/aggregation — it
    # observes round state per its threat-model tier and rewrites the
    # attacker clients' uploads (and/or mutates shards pre-round, e.g.
    # concept drift). None = static attacks only (the historical behavior).
    adversary: Optional[object] = None

    def apply_data_attacks(self, shards, seed: int):
        """Return shards with every data attack applied. The first attack
        sees base seed ``seed`` (per-client streams ``seed + cid`` — the
        historical launcher streams, bit-for-bit); each further attack
        gets a large-stride offset so composed attacks draw independent
        row masks instead of corrupting identical rows. Clients not named
        by any attack pass through untouched, sharing storage with the
        input."""
        if not self.data_attacks:
            return list(shards)
        out = []
        for cid, (x, y) in enumerate(shards):
            for i, atk in enumerate(self.data_attacks):
                x, y = atk.apply(cid, x, y, seed + 1_000_003 * i)
            out.append((x, y))
        return out


@dataclass
class RoundRecord:
    """Per-round accounting. Communication fields describe the round as it
    RAN: on merge rounds the clients that trained and uploaded are the
    pre-merge active set, so ``active_nodes``/``updates_sent``/``mean_loss``
    are snapshotted before ``_merge`` shrinks the mask; the post-merge
    population is ``active_nodes_end`` (== ``active_nodes`` otherwise).

    ``wall_s`` is host time (``time.perf_counter``). In the compiled
    engine a training round's ``wall_s`` is its share of the scan
    segment, from the program call to the losses on the host, and leaves
    out the round's evaluation; a merge round's covers the whole round,
    its evaluation included. The per-round pipelines time each round
    whole, evaluation included."""
    round: int
    accuracy: float
    mean_loss: float
    active_nodes: int        # clients active during the round (pre-merge)
    updates_sent: int        # pre-merge active clients whose update arrived
    bytes_sent: int
    active_nodes_end: int = -1   # active set after any merge this round
    merged_groups: Tuple[Tuple[int, ...], ...] = ()
    wall_s: float = 0.0


class FederatedSimulator:
    def __init__(
        self,
        init_params_fn: Callable[[jax.Array], object],
        loss_fn: Callable[[object, dict], jnp.ndarray],
        eval_fn: Callable[[object], float],
        client_shards: Sequence[Tuple[np.ndarray, np.ndarray]],
        fl: FLConfig,
        scenario: Optional[Scenario] = None,
        mesh: Optional[Mesh] = None,
    ):
        if fl.pipeline not in ("device", "host", "engine"):
            raise ValueError(
                f"FLConfig.pipeline must be 'device', 'host' or 'engine', "
                f"got {fl.pipeline!r}"
            )
        if mesh is not None and fl.pipeline not in ("device", "engine"):
            raise ValueError(
                "mesh-aware mode requires pipeline='device' or 'engine'"
            )
        self.fl = fl
        self.mesh = mesh
        self.scenario = scenario or Scenario()
        self.eval_fn = eval_fn
        self.loss_fn = loss_fn  # the engine builds its own round programs
        # the scenario owns its data attacks: poisoned shards are built
        # here, before any weights/buffers are derived from them
        self.shards: List[Tuple[np.ndarray, np.ndarray]] = [
            (np.asarray(x), np.asarray(y))
            for x, y in self.scenario.apply_data_attacks(client_shards, fl.seed)
        ]
        self.K = len(self.shards)
        self.policy = make_merge_policy(fl, mesh)
        self.rng = np.random.default_rng(fl.seed)

        key = jax.random.PRNGKey(fl.seed)
        params = init_params_fn(key)
        # (params, c_global, c_locals) are donated: each round's state update
        # reuses the previous round's HBM buffers instead of allocating and
        # copying — the round loop holds no stale references (see run()).
        self.load_state(params, *init_controls(params, self.K))
        if mesh is not None:
            # Mesh-aware mode: the stacked client axis carries a
            # NamedSharding over the federation ('pod') axis, globals are
            # replicated across pods. One layout contract for controls,
            # local models, losses, batch stacks, and the flat shard
            # buffers — round_fn and the gather pin their outputs to it so
            # the round loop never reshards between stages.
            rep = NamedSharding(mesh, P())
            stacked = NamedSharding(mesh, P(SH.client_axis(mesh, self.K)))
            self.round_fn = jax.jit(
                make_round_fn(loss_fn, fl.algo),
                donate_argnums=(0, 1, 2),
                out_shardings=(rep, rep, stacked, stacked, stacked),
            )
            self._gather = jax.jit(
                _gather_batches,
                static_argnames=("steps", "batch"),
                out_shardings={"x": stacked, "y": stacked},
            )
        else:
            self.round_fn = jax.jit(
                make_round_fn(loss_fn, fl.algo), donate_argnums=(0, 1, 2)
            )
            self._gather = _gather_batches_jit

        self.active = np.ones(self.K, np.float32)
        self.weights = np.asarray([len(y) for _, y in self.shards], np.float32)
        self.merge_plan = None
        self.history: List[RoundRecord] = []
        # post-merge checkpoint hook (serving bridge, DESIGN.md §10):
        # ``on_merge(t, plan, models, global_params)`` fires on every merge
        # round that actually formed groups, with ``models`` the
        # {representative: merged local-model pytree} serving artifacts
        # (core/merging.intermediary_models) and ``global_params`` the
        # round's post-aggregation global model. Set it BEFORE run() — the
        # engine pipeline bakes "does the fused merge step return the
        # stacked local models?" into its compiled programs.
        self.on_merge: Optional[Callable] = None

        # adaptive adversary (DESIGN.md §8): crafting adversaries take the
        # SPLIT round path — jitted train half, eager craft (so host-
        # stateful adversaries work), jitted aggregate half. The fused
        # round_fn above stays the adversary-free path, bit-for-bit.
        self.adversary = self.scenario.adversary
        self.engine_adversary_fallback: Optional[str] = None
        if self.adversary is not None and self.adversary.crafts:
            self._train_fn = jax.jit(make_train_fn(loss_fn, fl.algo))
            self._agg_fn = jax.jit(make_aggregate_fn(fl.algo, adversarial=True))
            self._adv_state = self.adversary.init_state(self.params, self.K)
            self._adv_mask = jnp.asarray(self.adversary.mask(self.K))

        if self.scenario.packet_loss is not None:
            self._loss_sched = self.scenario.packet_loss.schedule(
                self.K, fl.num_rounds
            )
        else:
            self._loss_sched = np.zeros((fl.num_rounds, self.K), bool)
        if self.scenario.network_delay is not None:
            self._delay_sched = self.scenario.network_delay.schedule(
                self.K, fl.num_rounds
            )
        else:
            self._delay_sched = np.zeros((fl.num_rounds, self.K), np.int64)
        # (arrival_round, cid, dx pytree, send-time weight)
        self._stale: List[tuple] = []

        self._param_bytes = tree_bytes(self.params)
        self._batch_key = jax.random.PRNGKey(fl.seed)
        self._prefetched: Optional[Tuple[int, dict]] = None
        if fl.pipeline in ("device", "engine"):
            self._upload_shards()

    # ------------------------------------------------------------------
    def load_state(self, params, c_global, c_locals):
        """Set the global model and the controls, placed in this
        simulator's layout: under a mesh the globals are replicated and the
        stacked client axis is split over 'pod'."""
        if self.mesh is not None:
            rep = NamedSharding(self.mesh, P())
            params = jax.device_put(params, rep)
            c_global = jax.device_put(c_global, rep)
            c_locals = jax.device_put(
                c_locals, SH.client_stack_shardings(self.mesh, c_locals)
            )
        self.params, self.c_global, self.c_locals = params, c_global, c_locals

    def _upload_shards(self):
        """Device-resident copy of the client shards in a flat concatenated
        layout (rows of all clients back to back + per-client offset and
        length), rebuilt only when shards change (init + merge). No
        padding: total device memory is exactly the sum of shard rows —
        retired clients hold zero-length slots, so every training row
        exists exactly once. Per-round batch sampling gathers from these
        on device — no host->device transfer per round. In mesh-aware mode
        the row dimension is sharded over the 'pod' axis (merging moves
        rows between clients but preserves the total, so the sharding
        survives merge rounds). The program span ``fed.upload_shards``
        counts the bytes put on the device (``nbytes``)."""
        with TraceAnnotation("fed.upload_shards") as span:
            xs = np.concatenate([x for x, _ in self.shards])
            ys = np.concatenate([y for _, y in self.shards])
            lens = np.asarray([len(y) for _, y in self.shards], np.int32)
            offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
            span.set_metadata(
                nbytes=xs.nbytes + ys.nbytes + lens.nbytes + offs.nbytes
            )
            if self.mesh is not None:
                rep = NamedSharding(self.mesh, P())
                self._shard_x = jax.device_put(
                    xs, SH.row_sharding(self.mesh, len(xs))
                )
                self._shard_y = jax.device_put(
                    ys, SH.row_sharding(self.mesh, len(ys))
                )
                self._shard_len = jax.device_put(lens, rep)
                self._shard_off = jax.device_put(offs, rep)
            else:
                self._shard_x = jnp.asarray(xs)
                self._shard_y = jnp.asarray(ys)
                self._shard_len = jnp.asarray(lens)
                self._shard_off = jnp.asarray(offs)

    def _sample_batches(self, t: int):
        """(K, steps, B, ...) batches drawn from each client's shard.

        Device pipeline: a jitted jax.random gather over the flat
        device-resident shards (uniform per client via its offset/length) —
        the sampled batches never exist on host. Host pipeline: the
        original per-round numpy gather + transfer (oracle)."""
        S, Bsz = self.fl.local_steps, self.fl.batch_size
        if self.fl.pipeline == "device":
            key = jax.random.fold_in(self._batch_key, t)
            return self._gather(
                key, self._shard_x, self._shard_y,
                self._shard_off, self._shard_len, S, Bsz,
            )
        xs, ys = [], []
        for x, y in self.shards:
            if len(y) == 0:
                # retired (merged-away) client: zero-filled dummy batches —
                # round_fn masks its delta/loss/weight via active=0
                xs.append(np.zeros((S, Bsz) + x.shape[1:], x.dtype))
                ys.append(np.zeros((S, Bsz) + y.shape[1:], y.dtype))
                continue
            idx = self.rng.integers(0, len(y), size=(S, Bsz))
            xs.append(x[idx])
            ys.append(y[idx])
        return {"x": jnp.asarray(np.stack(xs)), "y": jnp.asarray(np.stack(ys))}

    def participation_table(self) -> np.ndarray:
        """(T, K) pre-drawn participation uniforms — the simulator's own
        seeded stream, drawn lazily (configs may be replaced after
        construction in tests) and ONCE: the per-round device loop and the
        compiled engine select identical participants from identical draws
        by construction. A dedicated child stream keeps the draw order
        independent of pipeline-specific ``self.rng`` consumption."""
        if getattr(self, "_part_u", None) is None:
            rng = np.random.default_rng(
                np.random.SeedSequence([self.fl.seed, 0x9A57])
            )
            self._part_u = rng.random((self.fl.num_rounds, self.K))
        return self._part_u

    def _round_masks(self, t: int):
        S = self.fl.local_steps
        steps_mask = np.ones((self.K, S), np.float32)
        round_mask = np.ones(self.K, np.float32)
        pl = self.scenario.packet_loss
        if pl is not None:
            hit = self._loss_sched[t]
            if pl.drop_update:
                round_mask[hit] = 0.0
            else:
                # "not completing the training process in the epochs after
                # the first epoch" — truncate to the first local epoch
                steps_mask[hit, self.fl.steps_per_epoch :] = 0.0
        # delayed clients are excluded now; their delta arrives later
        round_mask[self._delay_sched[t] > 0] = 0.0
        # partial participation: sample a subset of active clients via the
        # pre-drawn uniform table (shared with the engine pipeline, which
        # consumes the SAME draws — see participation_mask)
        if self.fl.participation < 1.0:
            round_mask *= participation_mask(
                self.participation_table()[t], self.active,
                self.fl.participation,
            )
        poison = np.ones(self.K, np.float32)
        for cid, factor in self.scenario.model_poison.items():
            poison[cid] = factor
        return steps_mask, round_mask, poison

    def _enqueue_stale(self, t: int, x_before, x_locals):
        """Record delayed clients' deltas for later arrival, together with
        the client's CURRENT data weight: if the client is merged away
        before the delta arrives, ``merged_data_sizes`` zeroes
        ``self.weights[cid]`` (its share moves to the representative), but
        the in-flight delta still answers for the pre-merge share (paper
        §IV.D — the intermediary takes over only from the merge onward)."""
        delays = self._delay_sched[t]
        for cid in np.flatnonzero(delays > 0):
            if self.active[cid] == 0:
                continue
            dx = jax.tree_util.tree_map(
                lambda loc, g, c=cid: np.asarray(loc[c], np.float64)
                - np.asarray(g, np.float64),
                x_locals, x_before,
            )
            self._stale.append(
                (t + int(delays[cid]), cid, dx, float(self.weights[cid]))
            )

    def _apply_stale_updates(self, t: int):
        """Server applies stale deltas that arrive at round t, weighted by
        the sender's data share at SEND time (scaled by the global lr).
        Merging preserves the total weight, so the denominator is stable."""
        arrived = [s for s in self._stale if s[0] <= t]
        if not arrived:
            return
        self._stale = [s for s in self._stale if s[0] > t]
        total = float(self.weights.sum())
        for _, cid, dx, w_send in arrived:
            w = self.fl.algo.lr_global * w_send / total
            self.params = jax.tree_util.tree_map(
                lambda p, d: (np.asarray(p, np.float64) + w * d).astype(
                    np.asarray(p).dtype
                ),
                self.params, dx,
            )
        self.params = jax.tree_util.tree_map(jnp.asarray, self.params)
        if self.mesh is not None:
            self.params = jax.device_put(
                self.params, NamedSharding(self.mesh, P())
            )

    # ------------------------------------------------------------------
    def _merge(self, t: int, x_locals) -> Tuple[Tuple[int, ...], ...]:
        """Run the configured MergePolicy on the round's local models and
        apply its plan: mix control state, move merged members' data rows
        to the representative, update weights and the active mask. The
        policy decides WHO merges; everything here is bookkeeping."""
        plan = self.policy.merge_plan(x_locals, self.weights, self.active)
        self.merge_plan = plan
        if not plan.groups:
            # identity plan (e.g. policy "none", or nothing above
            # threshold): no state changes, no buffer rebuild
            self.active = plan.active.astype(np.float32)
            return ()
        # serving bridge: snapshot the intermediary models BEFORE the
        # bookkeeping advances weights (alpha='data' mixes with the
        # pre-merge shares the plan was computed against)
        if self.on_merge is not None:
            models = intermediary_models(
                plan, x_locals, self.fl.alpha, self.weights
            )
        # merge control variates (paper line 46: c_merged)
        if self.fl.pipeline == "device":
            # jitted W @ leaf contraction; c_locals donated (mixed in place)
            self.c_locals = apply_merge_device(plan, self.c_locals)
        else:
            self.c_locals = jax.tree_util.tree_map(
                jnp.asarray, apply_merge(plan, jax.device_get(self.c_locals))
            )
        self._merge_bookkeeping(plan)
        if self.on_merge is not None:
            self.on_merge(t, plan, models, self.params)
        return plan.groups

    def _merge_bookkeeping(self, plan):
        """Host-side consequences of a merge plan, shared with the engine
        pipeline (which mixes controls on device but keeps shard / weight
        bookkeeping here): the intermediary node inherits the union of
        member data; retired members keep their slot (fixed shapes
        everywhere) but give up their rows — otherwise the flat device
        buffers hold every merged row twice and the gather keeps sampling
        retired clients. Returns the rows moved into representatives."""
        moved = 0
        for group in plan.groups:
            rep = group[0]
            moved += sum(len(self.shards[j][1]) for j in group[1:])
            xs = np.concatenate([self.shards[j][0] for j in group])
            ys = np.concatenate([self.shards[j][1] for j in group])
            self.shards[rep] = (xs, ys)
            for j in group[1:]:
                xj, yj = self.shards[j]
                self.shards[j] = (xj[:0], yj[:0])
        self.weights = merged_data_sizes(plan, self.weights).astype(np.float32)
        self.active = plan.active.astype(np.float32)
        if self.fl.pipeline in ("device", "engine"):
            self._upload_shards()  # representative shards grew
        return moved

    # ------------------------------------------------------------------
    def _round_record(self, t: int, accuracy, losses, active_round,
                      round_mask, merged=(), wall_s: float = 0.0
                      ) -> RoundRecord:
        """THE definition of per-round accounting, shared by the per-round
        loop and the engine's per-segment materialization. ``active_round``
        is the mask the round TRAINED with (pre-merge on merge rounds —
        the PR 2 semantics); ``self.active`` has already been advanced past
        any merge, so it supplies ``active_nodes_end``."""
        sent = int((active_round * round_mask).sum())
        mean_loss = float(
            np.sum(np.asarray(losses) * active_round)
            / max(active_round.sum(), 1)
        )
        return RoundRecord(
            round=t,
            accuracy=float(accuracy),
            mean_loss=mean_loss,
            active_nodes=int(active_round.sum()),
            updates_sent=sent,
            bytes_sent=sent * self._param_bytes,
            active_nodes_end=int(self.active.sum()),
            merged_groups=merged,
            wall_s=wall_s,
        )

    def _adversarial_round(self, t: int, batches, steps_mask, round_mask,
                           poison):
        """The split round (DESIGN.md §8): jitted local training, then the
        adversary observes the round state its tier permits and crafts the
        attackers' uploads, then the jitted aggregate half substitutes
        them (delta AND reported local model) and aggregates. Called
        eagerly so host-stateful adversaries work in every per-round
        pipeline; the compiled engine inlines the same three stages into
        its scan for jittable adversaries."""
        from repro.core.adversary import make_context

        adv = self.adversary
        trained = self._train_fn(
            self.params, self.c_global, self.c_locals, batches,
            jnp.asarray(steps_mask),
        )
        dx, _dc, _c_new, x_locals_t, _losses = trained
        part = jnp.asarray(
            (self.active * round_mask).astype(np.float32)
        )
        corr = None
        if adv.needs_similarity:
            # the similarity matrix as the ACTIVE policy computes it over
            # the honestly-trained locals — the whitebox observation
            corr = jnp.asarray(self.policy.similarity(x_locals_t))
        ctx = make_context(
            jnp.asarray(t, jnp.int32), self.params, dx, x_locals_t,
            jnp.asarray(self.active), part, jnp.asarray(self.weights),
            self.fl.threshold, self.fl.algo.lr_global, corr,
        )
        adv_dx, self._adv_state = adv.craft(ctx, self._adv_state)
        return self._agg_fn(
            self.params, self.c_global, self.c_locals, trained,
            jnp.asarray(self.weights), jnp.asarray(self.active),
            jnp.asarray(round_mask), jnp.asarray(poison),
            adv_dx, self._adv_mask,
        )

    def run(self, verbose: bool = False, start: int = 0,
            stop: Optional[int] = None) -> List[RoundRecord]:
        """Run rounds [start, stop) (default: all of them) from the current
        model, controls, weights and active set, and return the history.
        With ``load_state`` a later call resumes from any state."""
        if self.fl.pipeline == "engine":
            adv = self.adversary
            incompatible = adv is not None and (
                not adv.jittable
                or (adv.needs_similarity and not callable(
                    getattr(self.policy, "device_similarity", None)))
            )
            if incompatible:
                # DESIGN.md §8: host-stateful adversaries (and whitebox
                # adversaries under a policy with no device similarity
                # program) cannot run inside the compiled scan — the
                # documented per-round host fallback drops this run to the
                # per-round device pipeline. Recorded on the simulator so
                # harnesses/tests can assert which engine actually ran.
                self.engine_adversary_fallback = (
                    f"adversary '{adv.name}' (jittable={adv.jittable}, "
                    f"needs_similarity={adv.needs_similarity}) cannot run "
                    f"in-scan; using the per-round device pipeline"
                )
                self.fl = dc_replace(self.fl, pipeline="device")
            else:
                from repro.core.engine import RoundEngine

                # cache the compiled segment/merge programs on the
                # simulator so repeated run() calls (and benchmark warm
                # timings) skip the cold re-jit — mirrors the device
                # pipeline jitting round_fn once in __init__
                engine = RoundEngine(
                    self, programs=getattr(self, "_engine_programs", None)
                )
                self._engine_programs = engine.programs
                return engine.run(verbose=verbose, start=start, stop=stop)
        fl = self.fl
        stop = fl.num_rounds if stop is None else stop
        self._prefetched = None
        for t in range(start, stop):
            t0 = time.perf_counter()
            if self.adversary is not None:
                drifted = self.adversary.pre_round(t, self.shards, fl.seed)
                if drifted is not None:
                    # environment shift (e.g. label_drift): shards changed
                    # under us — refresh the device buffers and drop any
                    # batch prefetched against the stale rows
                    self.shards = [
                        (np.asarray(x), np.asarray(y)) for x, y in drifted
                    ]
                    if fl.pipeline == "device":
                        self._upload_shards()
                    self._prefetched = None
            if self._prefetched is not None and self._prefetched[0] == t:
                batches = self._prefetched[1]
            else:
                batches = self._sample_batches(t)
            self._prefetched = None
            steps_mask, round_mask, poison = self._round_masks(t)
            # round_fn donates params/controls; keep a pre-round copy only
            # on rounds where a delayed client will actually need it
            delayed_now = self.scenario.network_delay is not None and bool(
                (self._delay_sched[t] > 0).any()
            )
            x_before = None
            if delayed_now:
                x_before = jax.tree_util.tree_map(
                    lambda a: jnp.array(a, copy=True), self.params
                )
            if self.adversary is not None and self.adversary.crafts:
                (
                    self.params,
                    self.c_global,
                    self.c_locals,
                    x_locals,
                    losses,
                ) = self._adversarial_round(
                    t, batches, steps_mask, round_mask, poison
                )
            else:
                (
                    self.params,
                    self.c_global,
                    self.c_locals,
                    x_locals,
                    losses,
                ) = self.round_fn(
                    self.params,
                    self.c_global,
                    self.c_locals,
                    batches,
                    jnp.asarray(steps_mask),
                    jnp.asarray(self.weights),
                    jnp.asarray(self.active),
                    jnp.asarray(round_mask),
                    jnp.asarray(poison),
                )
            will_merge = fl.merge_enabled and t in fl.merge_at
            overlap = fl.pipeline == "device" and fl.overlap_gather
            if overlap and not will_merge and t + 1 < stop:
                # double buffer: round t+1's gather is enqueued now, while
                # round t's round_fn is still computing (async dispatch) —
                # the gather leaves the round loop's critical path
                self._prefetched = (t + 1, self._sample_batches(t + 1))
            if delayed_now:
                self._enqueue_stale(t, x_before, x_locals)
            # snapshot BEFORE _merge mutates self.active: this round's
            # training and uploads ran against the pre-merge active set,
            # so its communication/loss accounting must too
            active_round = self.active.copy()
            merged: Tuple[Tuple[int, ...], ...] = ()
            if will_merge:
                merged = self._merge(t, x_locals)
                if overlap and t + 1 < stop:
                    # shard buffers were rebuilt; gather from the merged
                    # layout (no overlap win on merge rounds)
                    self._prefetched = (t + 1, self._sample_batches(t + 1))
            self._apply_stale_updates(t)

            acc = self.eval_fn(self.params)
            rec = self._round_record(
                t, acc, losses, active_round, round_mask, merged,
                time.perf_counter() - t0,
            )
            self.history.append(rec)
            if verbose:
                print(
                    f"round {t:2d} acc={acc:.4f} loss={rec.mean_loss:.4f} "
                    f"active={rec.active_nodes} sent={rec.updates_sent}"
                    + (f" merged={merged}" if merged else "")
                )
        return self.history


def participation_mask(u_row: np.ndarray, active: np.ndarray,
                       participation: float) -> np.ndarray:
    """(K,) f32 participant mask from one pre-drawn uniform row: the
    ``k = max(1, round(p * n_active))`` active clients with the SMALLEST
    uniforms participate (a threshold rule over pre-drawn randomness, so
    the compiled engine and the per-round loop — which see the evolving
    active mask at different times — select identical subsets from the
    same table). Ties have probability zero under continuous draws."""
    act = np.asarray(active) > 0
    n_act = int(act.sum())
    if n_act == 0:
        return np.ones_like(u_row, np.float32)
    k = max(1, int(round(participation * n_act)))
    u = np.where(act, u_row, np.inf)
    thr = np.partition(u, k - 1)[k - 1]
    return (u <= thr).astype(np.float32)


def _gather_batches(key, xs, ys, offsets, lengths, steps: int, batch: int):
    """(K, steps, batch, ...) uniform batch gather over flat shards.

    ``xs``/``ys`` hold all clients' rows back to back; client k owns rows
    [offsets[k], offsets[k] + lengths[k]). Indices are drawn with integer
    ``jax.random.randint`` (exact for any shard size — no f32 rounding of
    row ids). Retired (merged-away) clients own a zero-length slot: their
    draw is clamped to one in-bounds dummy row whose content never
    matters (round_fn masks their delta, loss, and weight via active=0) —
    no retired data is sampled and no shapes change. Runs jitted on
    device — the per-round batch tensors are produced and consumed
    without touching host memory."""
    K = lengths.shape[0]
    row = jax.random.randint(
        key, (K, steps, batch), minval=0,
        maxval=jnp.maximum(lengths, 1)[:, None, None],
    )
    idx = jnp.minimum(offsets[:, None, None] + row, xs.shape[0] - 1)
    return {"x": jnp.take(xs, idx, axis=0), "y": jnp.take(ys, idx, axis=0)}


_gather_batches_jit = jax.jit(
    _gather_batches, static_argnames=("steps", "batch")
)
