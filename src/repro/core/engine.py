"""Compiled round engine: scan-over-rounds federation
(``FLConfig.pipeline="engine"``).

The per-round pipelines (``device``/``host``) re-enter Python every round:
one jitted dispatch per round, host-drawn fault masks, host merge
planning, a host stale-delta queue, and an eval that blocks the loop. At
paper scale (small CNN, K=10-100) that host choreography dominates
wall-clock. The engine compiles the loop itself:

  * **Segments under one ``lax.scan``** — every run of rounds between
    merge boundaries (capped by ``FLConfig.engine_max_segment``) is one
    jitted, buffer-donating call whose step fuses batch gather -> train
    round -> stale-delta ring buffer -> stale arrivals. Per-round scenario
    randomness is pre-drawn into stacked (T, K) tables
    (:func:`repro.core.scenarios.round_tables`) consumed as scan inputs.
  * **Fused merge step** — a merge round runs train + streaming
    tree-Pearson + on-device greedy merge planning
    (:func:`repro.core.merging.device_merge_plan`) + the W-mix merge apply
    in a single jitted call; only the (K, K) assignment matrix crosses to
    host, where the thin shell moves shard rows and rebuilds the flat
    device buffers (``FederatedSimulator._merge_bookkeeping``). Policies
    without a device similarity program (cosine/random-pairs/none) fall
    back to host planning at the boundary — the scan segments still apply.
  * **Eval off the round loop** — the scan stacks per-round params and
    losses; ``RoundRecord``s (including the per-round eval) materialize
    once per segment from the stacked outputs, after the segment's
    compute has been dispatched.

The stale-delta queue is a fixed-capacity device ring buffer
(capacity K * (max_delay + 1): at most K enqueues per round and a slot
lives at most ``max_delay`` rounds, so a live slot can never be
overwritten). Arrivals are accumulated in f32 on device, where the
per-round oracle applies them sequentially in f64 on host — the one
documented tolerance vs the ``device`` pipeline (network-delay scenarios
agree to ~1e-6; everything else is bit-for-bit, see
tests/test_engine.py). A second, measure-zero edge: the device planner
compares correlations against the f32-cast threshold while the host
planner compares against the f64 value, so a correlation EXACTLY equal
to ``float32(threshold)`` (a ~3e-9-wide window) could group on device
but not on host; real similarity values never land there (the planner
property test nudges generated values off the knife edge).

Mesh-aware mode: the carried state keeps the pod-sharded layout contract
(stacked client axis over 'pod', globals replicated) via explicit
``out_shardings`` on the compiled segment/merge programs.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding as SH
from repro.core.federation import (
    RoundRecord,
    _gather_batches,
    participation_mask,
)
from repro.core.merge_policy import MergePolicy
from repro.core.merging import (
    apply_merge_device,
    compose_cross_groups,
    device_merge_plan,
    groups_from_assignment,
    intermediary_models,
    mix_stacked_tree,
    plan_from_groups,
)
from repro.core.pearson import pearson_sketch_rows
from repro.core.adversary import make_context
from repro.core.scaffold import make_aggregate_fn, make_round_fn, make_train_fn
from repro.core.scenarios import round_tables

# empty ring-buffer slot sentinel: an arrival round that never comes
_NEVER = np.int32(np.iinfo(np.int32).max)


class RoundEngine:
    """Drives a :class:`FederatedSimulator` whose ``pipeline="engine"``.

    The simulator stays the host shell (shards, schedules, telemetry,
    history); the engine owns the compiled programs and the device-side
    round state. ``programs`` can be shared between engines of identical
    configuration (same model/loss, FLConfig, scenario shape) so a second
    run hits the jit cache — benchmarks use this for warm timings.
    """

    def __init__(self, sim, programs: Optional[Dict] = None):
        fl = sim.fl
        if fl.pipeline != "engine":
            raise ValueError("RoundEngine requires FLConfig.pipeline='engine'")
        if fl.engine_max_segment < 1:
            raise ValueError("engine_max_segment must be >= 1")
        self.sim = sim
        self.fl = fl
        # built from the simulator's OWN pre-drawn schedules, so both
        # pipelines consume identical fault draws by construction
        self.tables = round_tables(
            sim.scenario, sim.K, fl.num_rounds, fl.steps_per_epoch,
            fl.local_steps,
            loss_sched=sim._loss_sched, delay_sched=sim._delay_sched,
            part_u=(sim.participation_table()
                    if fl.participation < 1.0 else None),
        )
        maxd = int(self.tables.delay.max()) if self.tables.delay.size else 0
        self._has_delay = maxd > 0
        self.cap = sim.K * (maxd + 1) if self._has_delay else 0
        self._merge_set = (
            {t for t in fl.merge_at if 0 <= t < fl.num_rounds}
            if fl.merge_enabled else set()
        )
        # on-device planning needs a jit-traceable similarity AND the base
        # class's greedy plan (a policy overriding plan() — random-pairs,
        # none — keeps its host semantics via the fallback)
        pol = sim.policy
        self._device_plan = (
            type(pol).plan is MergePolicy.plan
            and callable(getattr(pol, "device_similarity", None))
        )
        # blocked hierarchical planning (pearson-blocked, DESIGN.md §9):
        # per-block on-device plans + a representative cross pass, so no
        # K x K object exists at any layer. A single exact block IS the
        # flat fused merge program — route it there, which also makes the
        # paper-scale (block_size >= K, sketch_dim = 0) configuration
        # reproduce the flat policy's history bit for bit.
        self._blocked = bool(getattr(pol, "blocked", False))
        if self._blocked:
            self._B = pol.effective_block_size(sim.K)
            self._nb = -(-sim.K // self._B)
            if self._nb == 1 and fl.sketch_dim == 0:
                self._blocked = False
        # the post-merge hook (serving checkpoints) needs the round-t local
        # models, which the blocked program never materializes as a flat
        # (K, ...) stack — and the fused programs must bake in whether the
        # extra output exists, so a cached program set from a hookless run
        # cannot be reused (and vice versa)
        self._want_locals = getattr(sim, "on_merge", None) is not None
        if self._want_locals and self._blocked:
            raise ValueError(
                "on_merge hook is not supported with blocked engine "
                "planning (local models are never materialized flat); "
                "use the flat engine or the device pipeline"
            )
        if programs is not None and (
            programs.get("want_locals", False) != self._want_locals
        ):
            programs = None
        self.programs = programs if programs is not None else self._build_programs()

    # ------------------------------------------------------------------
    def _build_programs(self) -> Dict:
        sim, fl = self.sim, self.fl
        S, B = fl.local_steps, fl.batch_size
        cap, has_delay = self.cap, self._has_delay
        lr_g = fl.algo.lr_global
        thr, G, alpha = fl.threshold, fl.max_group_size, fl.alpha
        round_body = make_round_fn(sim.loss_fn, fl.algo)
        pol = sim.policy
        mesh = sim.mesh
        want_locals = self._want_locals

        # jittable crafting adversary (DESIGN.md §8): the round splits into
        # train -> craft -> aggregate INSIDE the scan, with the adversary's
        # fixed-shape state threaded through the carry. Non-jittable (and
        # whitebox-without-device-similarity) adversaries never reach the
        # engine — FederatedSimulator.run() drops them to the per-round
        # pipeline first (engine_adversary_fallback).
        adv = sim.adversary
        if adv is not None and adv.crafts:
            assert adv.jittable and (
                not adv.needs_similarity
                or callable(getattr(pol, "device_similarity", None))
            ), "non-jittable adversary reached the engine (fallback missed)"
            train_body = make_train_fn(sim.loss_fn, fl.algo)
            agg_body = make_aggregate_fn(fl.algo, adversarial=True)
            adv_mask = jnp.asarray(adv.mask(sim.K))
        else:
            adv = None

        batch_sh = None
        if mesh is not None:
            rep = NamedSharding(mesh, P())
            batch_sh = NamedSharding(mesh, P(SH.client_axis(mesh, sim.K)))

        def core(state, const, xrow):
            """One fused round: gather -> train [-> craft] -> stale enqueue
            -> stale arrivals. Exactly the per-round device pipeline's order
            (merge, which commutes with the params-only arrival update,
            happens at the jitted merge step's tail instead)."""
            (params, c_g, c_l, weights, active, buf, buf_w, buf_arr, wptr,
             adv_st) = state
            sx, sy, soff, slen, bkey, poison = const
            t = xrow["t"]
            key = jax.random.fold_in(bkey, t)
            batches = _gather_batches(key, sx, sy, soff, slen, S, B)
            if batch_sh is not None:
                batches = jax.lax.with_sharding_constraint(
                    batches, {"x": batch_sh, "y": batch_sh}
                )
            x_old = params
            if adv is None:
                params, c_g, c_l, x_locals, losses = round_body(
                    params, c_g, c_l, batches, xrow["steps_mask"], weights,
                    active, xrow["round_mask"], poison,
                )
            else:
                # the split round, same ops as the fused body: the adversary
                # observes the honestly-trained deltas (and, whitebox, the
                # policy's own similarity program over them), crafts, and
                # the aggregate half substitutes the attackers' uploads
                trained = train_body(
                    params, c_g, c_l, batches, xrow["steps_mask"]
                )
                corr = (
                    pol.device_similarity(trained[3])
                    if adv.needs_similarity else None
                )
                ctx = make_context(
                    t, params, trained[0], trained[3], active,
                    active * xrow["round_mask"], weights, thr, lr_g, corr,
                )
                adv_dx, adv_st = adv.craft(ctx, adv_st)
                params, c_g, c_l, x_locals, losses = agg_body(
                    params, c_g, c_l, trained, weights, active,
                    xrow["round_mask"], poison, adv_dx, adv_mask,
                )
            if has_delay:
                # enqueue delayed senders' deltas with their send-time
                # weight (fixed-capacity ring; rank-compacted slots, the
                # cap-index means "not enqueued" and is dropped)
                e = (xrow["delay"] > 0) & (active > 0)
                ei = e.astype(jnp.int32)
                slot = jnp.where(e, (wptr + jnp.cumsum(ei) - 1) % cap, cap)
                dx = jax.tree_util.tree_map(
                    lambda xl, xo: xl.astype(jnp.float32)
                    - xo.astype(jnp.float32)[None],
                    x_locals, x_old,
                )
                buf = jax.tree_util.tree_map(
                    lambda b, d: b.at[slot].set(d, mode="drop"), buf, dx
                )
                buf_w = buf_w.at[slot].set(weights, mode="drop")
                buf_arr = buf_arr.at[slot].set(t + xrow["delay"], mode="drop")
                wptr = (wptr + jnp.sum(ei)) % cap
                # apply deltas arriving this round (send-time weight over
                # the total, which merging preserves)
                arrived = buf_arr <= t

                def _apply(p_tree):
                    coef = jnp.where(arrived, buf_w, 0.0) * (
                        lr_g / jnp.sum(weights)
                    )
                    return jax.tree_util.tree_map(
                        lambda p, b: (
                            p.astype(jnp.float32)
                            + jnp.tensordot(coef, b, axes=1)
                        ).astype(p.dtype),
                        p_tree, buf,
                    )

                params = jax.lax.cond(
                    jnp.any(arrived), _apply, lambda p: p, params
                )
                buf_arr = jnp.where(arrived, _NEVER, buf_arr)
            state = (params, c_g, c_l, weights, active, buf, buf_w, buf_arr,
                     wptr, adv_st)
            return state, x_locals, losses

        def segment(state, const, xs):
            def step(st, xrow):
                st, _x_locals, losses = core(st, const, xrow)
                return st, (st[0], losses)

            return jax.lax.scan(step, state, xs)

        def merge_device(state, const, xrow):
            """Fused merge round: train + streaming tree-Pearson +
            on-device plan + W-mix of the control state. Weights/active
            update on device; only (A, active_new) cross to host for the
            shard bookkeeping. Its phases carry named scopes (train,
            similarity, plan, mix), which the device trace keeps."""
            with jax.named_scope("train"):
                state, x_locals, losses = core(state, const, xrow)
            params, c_g, c_l, weights, active, *rest = state
            with jax.named_scope("similarity"):
                corr = pol.device_similarity(x_locals)
            with jax.named_scope("plan"):
                W, A, act_new = device_merge_plan(
                    corr, active, weights,
                    threshold=thr, max_group_size=G, alpha=alpha,
                )
            # mirror the host path's "skip the apply on empty plans":
            # identity-mix (bit-exact no-op) when nothing grouped
            with jax.named_scope("mix"):
                has_groups = jnp.any(jnp.sum(A, axis=1) > 1.5)
                K = A.shape[0]
                W_eff = jnp.where(has_groups, W, jnp.eye(K, dtype=W.dtype))
                c_l = mix_stacked_tree(W_eff, c_l)
                weights = jnp.where(has_groups, A @ weights, weights)
            state = (params, c_g, c_l, weights, act_new, *rest)
            if want_locals:
                # serving checkpoint hook: ship the round-t local models to
                # host so intermediary models can be formed from the plan
                return state, losses, A, act_new, x_locals
            return state, losses, A, act_new

        def merge_host(state, const, xrow):
            """Merge-round train step for host-planned policies: returns
            the local models so the policy's similarity/plan run on host
            exactly as in the per-round device pipeline."""
            state, x_locals, losses = core(state, const, xrow)
            return state, losses, x_locals

        merge_blocked = None
        if getattr(self, "_blocked", False):
            Bb, nb = self._B, self._nb
            K = sim.K
            Kp = nb * Bb
            pad = Kp - K
            d_sk = fl.sketch_dim
            sk_mode = fl.sketch_mode

            def _pad_rows(a):
                # padded clients are permanently inactive: zero sketch rows
                # (zero variance -> correlation 0 via the eps guard) and
                # active=0, so the per-block planner never touches them
                if pad == 0:
                    return a
                return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))

            def merge_blocked(state, const, xrow):
                """Fused blocked merge round (tentpole layer 3): train +
                sketch + vmapped per-block on-device planning + blockwise
                W-mix + representative cross pass, all fixed-shape
                (nb, B, B) — the dense K x K merge matrix of the flat
                program never exists. Only the per-block assignments and
                the (nb, nb) cross assignment go to host (O(K * B))."""
                state, x_locals, losses = core(state, const, xrow)
                params, c_g, c_l, weights, active, *rest = state
                act_b = _pad_rows(active).reshape(nb, Bb)
                w_b = _pad_rows(weights).reshape(nb, Bb)
                if d_sk > 0:
                    rows_b = _pad_rows(pol.device_sketch(x_locals)) \
                        .reshape(nb, Bb, -1)
                    corr_b = jax.vmap(
                        lambda r: pearson_sketch_rows(r, mode=sk_mode)
                    )(rows_b)
                else:
                    # exact similarity (documented O(K^2)) — the small-K /
                    # bit-parity configuration
                    corr_p = jnp.pad(
                        pol.device_similarity(x_locals),
                        ((0, pad), (0, pad)),
                    )
                    corr_b = jnp.stack([
                        corr_p[i * Bb:(i + 1) * Bb, i * Bb:(i + 1) * Bb]
                        for i in range(nb)
                    ])
                W1, A1, act1 = jax.vmap(
                    lambda c, a, w: device_merge_plan(
                        c, a, w, threshold=thr, max_group_size=G, alpha=alpha
                    )
                )(corr_b, act_b, w_b)
                # same "skip the apply on empty plans" guard as the flat
                # program: identity-mix (bit-exact no-op) if nothing grouped
                has1 = jnp.any(jnp.sum(A1, axis=2) > 1.5)
                W1e = jnp.where(has1, W1, jnp.eye(Bb, dtype=W1.dtype)[None])

                def _mix1(leaf):
                    lf = _pad_rows(leaf).reshape((nb, Bb) + leaf.shape[1:])
                    mixed = jnp.einsum(
                        "nij,nj...->ni...", W1e, lf.astype(jnp.float32)
                    )
                    return mixed.reshape((Kp,) + leaf.shape[1:])[:K] \
                        .astype(leaf.dtype)

                c_l = jax.tree_util.tree_map(_mix1, c_l)
                w1 = jnp.where(
                    has1, jnp.einsum("nij,nj->ni", A1, w_b), w_b
                ).reshape(Kp)
                # ---- cross pass over one designated rep per block: the
                # lowest-index post-pass-1 active node
                rep_loc = jnp.argmax(act1 > 0, axis=1)
                has_rep = jnp.any(act1 > 0, axis=1)
                rep_glob = rep_loc + Bb * jnp.arange(nb)
                if d_sk > 0:
                    corr_r = pearson_sketch_rows(
                        jnp.take(rows_b.reshape(Kp, -1), rep_glob, axis=0),
                        mode=sk_mode,
                    )
                else:
                    corr_r = corr_p[rep_glob[:, None], rep_glob[None, :]]
                w_r = jnp.take(w1, rep_glob)
                W2, A2, act2 = device_merge_plan(
                    corr_r, has_rep.astype(jnp.float32), w_r,
                    threshold=thr, max_group_size=G, alpha=alpha,
                )
                has2 = jnp.any(jnp.sum(A2, axis=1) > 1.5)
                W2e = jnp.where(has2, W2, jnp.eye(nb, dtype=W2.dtype))

                def _mix2(leaf):
                    lf = _pad_rows(leaf).astype(jnp.float32)
                    rep_vals = jnp.take(lf, rep_glob, axis=0)
                    mixed = jnp.tensordot(W2e, rep_vals, axes=1)
                    sel = has_rep.reshape((nb,) + (1,) * (lf.ndim - 1))
                    # repless blocks scatter their own value back (no-op)
                    out = lf.at[rep_glob].set(jnp.where(sel, mixed, rep_vals))
                    return out[:K].astype(leaf.dtype)

                c_l = jax.tree_util.tree_map(_mix2, c_l)
                w2_r = jnp.where(has2, A2 @ w_r, w_r)
                weights = w1.at[rep_glob].set(
                    jnp.where(has_rep, w2_r, w_r)
                )[:K]
                act1f = act1.reshape(Kp)
                act_new = act1f.at[rep_glob].set(
                    jnp.where(has_rep, act2, jnp.take(act1f, rep_glob))
                )[:K]
                state = (params, c_g, c_l, weights, act_new, *rest)
                return state, losses, A1, act1, A2, act2, rep_glob, has_rep

        if mesh is not None:
            rep_tree = jax.tree_util.tree_map(lambda _: rep, sim.params)
            stacked_tree = SH.client_stack_shardings(mesh, sim.c_locals)
            buf_tree = jax.tree_util.tree_map(lambda _: rep, sim.params)
            adv_sh = jax.tree_util.tree_map(
                lambda _: rep, getattr(sim, "_adv_state", ())
            )
            state_sh = (rep_tree, rep_tree, stacked_tree, rep, rep,
                        buf_tree, rep, rep, rep, adv_sh)
            seg = jax.jit(segment, donate_argnums=(0,),
                          out_shardings=(state_sh, (rep_tree, rep)))
            dev_out = (state_sh, rep, rep, rep)
            if want_locals:
                dev_out = dev_out + (stacked_tree,)
            m_dev = jax.jit(merge_device, donate_argnums=(0,),
                            out_shardings=dev_out)
            m_host = jax.jit(merge_host, donate_argnums=(0,),
                             out_shardings=(state_sh, rep, stacked_tree))
            m_blk = merge_blocked and jax.jit(
                merge_blocked, donate_argnums=(0,),
                out_shardings=(state_sh,) + (rep,) * 7,
            )
        else:
            seg = jax.jit(segment, donate_argnums=(0,))
            m_dev = jax.jit(merge_device, donate_argnums=(0,))
            m_host = jax.jit(merge_host, donate_argnums=(0,))
            m_blk = merge_blocked and jax.jit(
                merge_blocked, donate_argnums=(0,)
            )
        return {"segment": seg, "merge_device": m_dev,
                "merge_host": m_host, "merge_blocked": m_blk,
                "want_locals": want_locals}

    # ------------------------------------------------------------------
    def _init_state(self):
        sim, cap = self.sim, self.cap
        buf = jax.tree_util.tree_map(
            lambda p: jnp.zeros((cap,) + p.shape, jnp.float32), sim.params
        )
        buf_w = jnp.zeros((cap,), jnp.float32)
        buf_arr = jnp.full((cap,), _NEVER, jnp.int32)
        state = (
            sim.params, sim.c_global, sim.c_locals,
            jnp.asarray(sim.weights), jnp.asarray(sim.active),
            buf, buf_w, buf_arr, jnp.asarray(0, jnp.int32),
            getattr(sim, "_adv_state", ()),  # crafting adversary's carry
        )
        if sim.mesh is not None:
            rep = NamedSharding(sim.mesh, P())
            state = (
                state[0], state[1], state[2],
                jax.device_put(state[3], rep), jax.device_put(state[4], rep),
                jax.device_put(state[5], rep), jax.device_put(state[6], rep),
                jax.device_put(state[7], rep), jax.device_put(state[8], rep),
                jax.device_put(state[9], rep),
            )
        return state

    def _const(self):
        sim = self.sim
        return (
            sim._shard_x, sim._shard_y, sim._shard_off, sim._shard_len,
            sim._batch_key, jnp.asarray(self.tables.poison),
        )

    def _effective_masks(self, t0: int, t1: int, active) -> np.ndarray:
        """(t1-t0, K) round masks with partial participation folded in.
        The active set is constant between merge boundaries, so every
        round's participant subset (the k smallest pre-drawn uniforms
        among active clients) is computable on host at segment dispatch —
        the one shared selection rule (``participation_mask``) keeps the
        engine and the per-round loop on identical subsets."""
        rows = np.asarray(self.tables.round_mask[t0:t1])
        if self.tables.part_u is None:
            return rows
        rows = rows.copy()
        for i, t in enumerate(range(t0, t1)):
            rows[i] *= participation_mask(
                self.tables.part_u[t], active, self.fl.participation
            )
        return rows

    def _xs(self, t0: int, t1: int, round_mask: np.ndarray):
        tb = self.tables
        return {
            "t": jnp.arange(t0, t1, dtype=jnp.int32),
            "steps_mask": jnp.asarray(tb.steps_mask[t0:t1]),
            "round_mask": jnp.asarray(round_mask),
            "delay": jnp.asarray(tb.delay[t0:t1]),
        }

    def _xrow(self, t: int, round_mask: np.ndarray):
        return {k: v[0] for k, v in self._xs(t, t + 1, round_mask).items()}

    # ------------------------------------------------------------------
    def _record(self, t: int, accuracy: float, losses_np, active_pre,
                round_mask, merged_groups=(), wall_s: float = 0.0):
        """Round accounting through the simulator's single shared helper
        (same formulas as the per-round loop by construction)."""
        return self.sim._round_record(
            t, accuracy, losses_np, active_pre, round_mask,
            merged_groups, wall_s,
        )

    def _run_segment(self, state, t0: int, t1: int, verbose: bool):
        sim = self.sim
        active_pre = sim.active.copy()
        eff_mask = self._effective_masks(t0, t1, active_pre)
        wall0 = time.perf_counter()
        with TraceAnnotation("fed.segment", rounds=t1 - t0):
            state, (p_stack, l_stack) = self.programs["segment"](
                state, self._const(), self._xs(t0, t1, eff_mask)
            )
            losses_np = np.asarray(l_stack)
        wall = (time.perf_counter() - wall0) / (t1 - t0)
        for i, t in enumerate(range(t0, t1)):
            with TraceAnnotation("fed.eval", round=t):
                params_t = jax.tree_util.tree_map(lambda l: l[i], p_stack)
                acc = float(sim.eval_fn(params_t))
            rec = self._record(
                t, acc, losses_np[i], active_pre, eff_mask[i], (), wall
            )
            sim.history.append(rec)
            if verbose:
                print(
                    f"round {t:2d} acc={acc:.4f} loss={rec.mean_loss:.4f} "
                    f"active={rec.active_nodes} sent={rec.updates_sent}"
                )
        return state

    def _decode_blocked(self, A1, act1, A2, act2, rep_glob):
        """Decode the blocked program's per-block + cross assignments into
        a host MergePlan for the shard bookkeeping. O(K * B) host work,
        and ``with_w=False`` — the mixes already happened on device, so no
        dense K x K matrix is ever built."""
        sim, fl = self.sim, self.fl
        B, K = self._B, sim.K
        A1, act1 = np.asarray(A1), np.asarray(act1)
        pass1_groups, pass1_unmerged = [], []
        for b in range(self._nb):
            g, u = groups_from_assignment(A1[b], act1[b])
            # padded clients are never active, so only real ids appear
            pass1_groups.extend([j + b * B for j in grp] for grp in g)
            pass1_unmerged.extend(j + b * B for j in u)
        g2, _ = groups_from_assignment(np.asarray(A2), np.asarray(act2))
        if g2:
            groups, unmerged = compose_cross_groups(
                pass1_groups, pass1_unmerged, np.asarray(rep_glob), g2
            )
        else:
            groups, unmerged = pass1_groups, pass1_unmerged
        return plan_from_groups(
            K, groups, unmerged, sim.weights.astype(np.int64),
            alpha=fl.alpha, with_w=False,
        )

    def _run_merge_round(self, state, t: int, verbose: bool):
        """One merge round in three program spans: ``fed.merge_program``
        (the fused program, until what the host plans from is on the
        host), ``fed.merge_host`` (the plan, the shard bookkeeping and its
        re-upload) and ``fed.eval``."""
        sim, fl = self.sim, self.fl
        active_pre = sim.active.copy()
        eff_mask = self._effective_masks(t, t + 1, active_pre)
        xrow = self._xrow(t, eff_mask)
        wall0 = time.perf_counter()
        moved = 0
        if self._blocked:
            with TraceAnnotation("fed.merge_program"):
                (state, losses, A1, act1, A2, act2, rep_glob, _has_rep) = \
                    self.programs["merge_blocked"](state, self._const(), xrow)
                A1, act1, A2, act2, rep_glob = jax.device_get(
                    (A1, act1, A2, act2, rep_glob))
            with TraceAnnotation("fed.merge_host") as span:
                plan = self._decode_blocked(A1, act1, A2, act2, rep_glob)
                sim.merge_plan = plan
                if plan.groups:
                    # controls, weights AND active were advanced on device
                    # with fixed-shape per-block matrices; the host shell
                    # only moves shard rows and refreshes the flat row
                    # buffers (O(K))
                    moved = sim._merge_bookkeeping(plan)
                else:
                    sim.active = plan.active.astype(np.float32)
                span.set_metadata(groups=len(plan.groups), rows_moved=moved)
        elif self._device_plan:
            with TraceAnnotation("fed.merge_program"):
                out = self.programs["merge_device"](
                    state, self._const(), xrow
                )
                if self._want_locals:
                    state, losses, A, act_new, x_locals = out
                else:
                    state, losses, A, act_new = out
                    x_locals = None
                A, act_new = jax.device_get((A, act_new))
            with TraceAnnotation("fed.merge_host") as span:
                groups, unmerged = groups_from_assignment(A, act_new)
                plan = plan_from_groups(
                    sim.K, groups, unmerged, sim.weights.astype(np.int64),
                    alpha=fl.alpha,
                )
                sim.merge_plan = plan
                if plan.groups:
                    # intermediary models mix with PRE-merge data shares;
                    # grab them before the bookkeeping folds weights into
                    # reps
                    w_pre = sim.weights.copy()
                    # controls were mixed on device; the host shell only
                    # moves shard rows, refreshes weights/active mirrors,
                    # and rebuilds the flat device buffers
                    moved = sim._merge_bookkeeping(plan)
                    if self._want_locals:
                        models = intermediary_models(
                            plan, x_locals, alpha=fl.alpha, data_sizes=w_pre
                        )
                        sim.on_merge(t, plan, models, state[0])
                else:
                    sim.active = plan.active.astype(np.float32)
                span.set_metadata(groups=len(plan.groups), rows_moved=moved)
        else:
            with TraceAnnotation("fed.merge_program"):
                state, losses, x_locals = self.programs["merge_host"](
                    state, self._const(), xrow
                )
                x_locals = jax.block_until_ready(x_locals)
            with TraceAnnotation("fed.merge_host") as span:
                plan = sim.policy.merge_plan(x_locals, sim.weights, sim.active)
                sim.merge_plan = plan

                def _rep(a):
                    # keep the carried state on the mesh's replicated layout
                    # so the next segment call reuses its compiled program
                    a = jnp.asarray(a)
                    if sim.mesh is not None:
                        a = jax.device_put(a, NamedSharding(sim.mesh, P()))
                    return a

                if plan.groups:
                    c_l = apply_merge_device(plan, state[2])
                    if sim.mesh is not None:
                        # apply_merge_device lets GSPMD infer the output
                        # layout; re-pin the stacked-client contract so the
                        # next segment call matches its compiled input
                        # shardings
                        c_l = jax.device_put(
                            c_l, SH.client_stack_shardings(sim.mesh, c_l)
                        )
                    w_pre = sim.weights.copy()
                    moved = sim._merge_bookkeeping(plan)
                    if self._want_locals:
                        models = intermediary_models(
                            plan, x_locals, alpha=fl.alpha, data_sizes=w_pre
                        )
                        sim.on_merge(t, plan, models, state[0])
                    state = (state[0], state[1], c_l,
                             _rep(sim.weights), _rep(sim.active), *state[5:])
                else:
                    sim.active = plan.active.astype(np.float32)
                    state = (*state[:4], _rep(sim.active), *state[5:])
                span.set_metadata(groups=len(plan.groups), rows_moved=moved)
        with TraceAnnotation("fed.eval", round=t):
            acc = float(sim.eval_fn(state[0]))
        wall = time.perf_counter() - wall0
        rec = self._record(
            t, acc, np.asarray(losses), active_pre, eff_mask[0],
            plan.groups, wall
        )
        sim.history.append(rec)
        if verbose:
            print(
                f"round {t:2d} acc={acc:.4f} loss={rec.mean_loss:.4f} "
                f"active={rec.active_nodes} sent={rec.updates_sent}"
                + (f" merged={plan.groups}" if plan.groups else "")
            )
        return state

    # ------------------------------------------------------------------
    def run(self, verbose: bool = False, start: int = 0,
            stop: Optional[int] = None) -> List[RoundRecord]:
        """Run rounds [start, stop) (default: all of them) from the
        simulator's current model, controls, weights and active set.
        Resuming mid-run carries no in-flight stale updates, so a start
        past 0 is refused for schedules with delays."""
        sim, fl = self.sim, self.fl
        T = fl.num_rounds if stop is None else stop
        if start > 0 and self._has_delay:
            raise ValueError("cannot resume a run with delayed updates")
        state = self._init_state()
        t = start
        while t < T:
            if t in self._merge_set:
                with TraceAnnotation("fed.merge_round", round=t):
                    state = self._run_merge_round(state, t, verbose)
                t += 1
            else:
                boundary = min([b for b in self._merge_set if b > t] + [T])
                end = min(boundary, t + fl.engine_max_segment)
                state = self._run_segment(state, t, end, verbose)
                t = end
        # leave the simulator's device state current for checkpoints etc.
        sim.params, sim.c_global, sim.c_locals = state[0], state[1], state[2]
        if sim.adversary is not None and sim.adversary.crafts:
            sim._adv_state = state[9]
        return sim.history
