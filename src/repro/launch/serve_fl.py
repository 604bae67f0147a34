"""Federation -> serving driver: train the servable LM under the Pearson
merge, checkpoint every merge round's intermediary models, then serve an
open-loop trace over the resulting replica cluster with a mid-trace
hot-swap to the next merge round.

The pieces this wires together (DESIGN.md §10):

  * ``FederatedSimulator.on_merge`` -> atomic ``save_pytree`` checkpoints:
    one file per intermediary model (the per-group ``sum_j alpha_j x_j``
    of paper line 45) plus the aggregated global model, collected into
    :class:`repro.serving.MergeCheckpoint` records.
  * ``ClusterRouter`` folds the merge plans into a client -> replica map;
    each replica is a :class:`ServeEngine` (fixed-slot continuous
    batching) over one intermediary model, unclustered clients hit the
    GLOBAL replica.
  * ``serve_trace`` replays an open-loop request trace against the
    replicas by wall clock; a ``CheckpointWatcher`` polled between ticks
    adopts the next merge round the moment its manifest lands on disk —
    in-flight requests keep their slots (measured stall + checkpoint-to-
    adoption latency, staleness semantics on ``ServeEngine.swap_params``).
  * ``sequential_oracle`` is the no-batching baseline: the same requests,
    one at a time, through ``launch.serve.generate``.

  PYTHONPATH=src python -m repro.launch.serve_fl           # small demo
  PYTHONPATH=src python -m repro.launch.serve_fl --smoke   # CI-sized
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.checkpoint.io import save_pytree
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.experiment import ExperimentSpec, build_simulator
from repro.launch.serve import generate
from repro.models import model as M
from repro.serving import (
    GLOBAL,
    CheckpointWatcher,
    ClusterRouter,
    MergeCheckpoint,
    ReplicaSet,
    Request,
    ServeEngine,
    SwapReport,
    diurnal_requests,
    load_model,
    poisson_requests,
    swap_replicas,
    write_checkpoint_manifest,
)
from repro.serving.fl_model import serve_config


def fl_spec(num_clients: int = 8, rounds: int = 4,
            merge_at: Tuple[int, ...] = (1, 2), seed: int = 0,
            pipeline: str = "engine", smoke: bool = False) -> ExperimentSpec:
    """The servable-LM federation spec. ``threshold=-1.0`` makes the
    greedy Pearson grouping deterministic (any correlation qualifies), so
    every merge round actually forms groups — the serving bench needs at
    least two checkpoint events, not a statistical maybe."""
    n_per = 40 if smoke else 60
    return ExperimentSpec(
        model="xlstm_lm",
        dataset="synthetic_tokens",
        n_train=num_clients * 2 * n_per,
        n_test=64 if smoke else 128,
        data_kwargs={"num_classes": 4, "seq_len": 16},
        partition="class_pairs",
        partition_kwargs={"n_per": n_per},
        num_clients=num_clients,
        lr_local=0.1,
        merge_at=merge_at,
        threshold=-1.0,
        max_group_size=3,
        rounds=rounds,
        local_epochs=1,
        steps_per_epoch=2,
        batch_size=8 if smoke else 16,
        pipeline=pipeline,
        seed=seed,
    )


def federate_and_checkpoint(spec: ExperimentSpec, ckpt_dir: str):
    """Run the federation with a checkpointing ``on_merge`` hook.

    Returns (sim, ckpts, history): one :class:`MergeCheckpoint` per merge
    round that formed groups, files written atomically under
    ``ckpt_dir``."""
    os.makedirs(ckpt_dir, exist_ok=True)
    sim = build_simulator(spec)
    ckpts: List[MergeCheckpoint] = []

    def hook(t, plan, models, global_params):
        rep_paths: Dict[int, str] = {}
        for rep, model in models.items():
            path = os.path.join(ckpt_dir, f"round{t:03d}_rep{rep:04d}.npz")
            save_pytree(path, model, step=t)
            rep_paths[int(rep)] = path
        gpath = os.path.join(ckpt_dir, f"round{t:03d}_global.npz")
        save_pytree(gpath, global_params, step=t)
        ckpt = MergeCheckpoint(round=int(t), rep_paths=rep_paths,
                               global_path=gpath, groups=plan.groups)
        # manifest LAST: a CheckpointWatcher that sees it can load
        # every referenced npz
        write_checkpoint_manifest(ckpt_dir, ckpt)
        ckpts.append(ckpt)

    sim.on_merge = hook
    history = sim.run()
    return sim, ckpts, history


def build_replicas(ckpt: MergeCheckpoint, template, cfg, num_clients: int,
                   num_slots: int = 8, capacity: int = 64,
                   warm: bool = True, **engine_kwargs) -> ReplicaSet:
    """One ServeEngine per intermediary model + the GLOBAL replica, router
    primed with the checkpoint's merge plan. ``warm=True`` pre-compiles
    the swap-adoption program per engine (a same-weights swap), so the
    first measured hot-swap times the transfer, not XLA. Extra
    ``engine_kwargs`` (block_size, ...) pass through to every engine."""
    router = ClusterRouter(num_clients)
    router.update(ckpt.groups)
    engines = {
        GLOBAL: ServeEngine(load_model(ckpt.global_path, template), cfg,
                            num_slots=num_slots, capacity=capacity,
                            **engine_kwargs)
    }
    for rep, path in ckpt.rep_paths.items():
        engines[rep] = ServeEngine(load_model(path, template), cfg,
                                   num_slots=num_slots, capacity=capacity,
                                   **engine_kwargs)
    if warm:
        for eng in engines.values():
            eng.swap_params(
                jax.tree_util.tree_map(lambda a: a.copy(), eng.params)
            )
            eng.swaps = 0
    return ReplicaSet(engines, router)


def warm_trace(replicas: ReplicaSet, requests: List[Request]) -> None:
    """Compile every program the trace will hit (admission per distinct
    prompt length, the fused step) before the clock starts."""
    lens = sorted({len(r.prompt) for r in requests})
    for key, eng in replicas.engines.items():
        for i, L in enumerate(lens):
            eng.try_admit(Request(
                rid=-1 - i, client_id=0,
                prompt=np.zeros(L, np.int32), max_new_tokens=2,
            ))
        eng.run_to_completion()


def serve_trace(
    replicas: ReplicaSet,
    requests: List[Request],
    watcher: Optional[CheckpointWatcher] = None,
    template=None,
    min_inflight: int = 2,
) -> dict:
    """Replay ``requests`` open-loop by wall clock. A
    :class:`CheckpointWatcher` is polled between ticks: when a new merge
    round's manifest lands on disk, the replicas hot-swap to it — deferred
    until at least ``min_inflight`` requests are in flight (or the trace
    is exhausted), so the staleness path is actually exercised. The swap
    is ARRIVAL-driven, not scheduled: the trace has no knowledge of when
    (or whether) federation publishes a round."""
    reqs = sorted(requests, key=lambda r: r.arrival)
    n = len(reqs)
    pending_swap: Optional[Tuple[MergeCheckpoint, float]] = None
    swap_report: Optional[SwapReport] = None
    finished: List[Tuple[int, object]] = []
    i = 0
    t0 = time.perf_counter()
    while i < n or not replicas.idle:
        now = time.perf_counter() - t0
        while i < n and reqs[i].arrival <= now:
            replicas.submit(reqs[i])
            i += 1
        if watcher is not None and pending_swap is None:
            pending_swap = watcher.poll()
        if (pending_swap is not None
                and (replicas.num_inflight >= min_inflight or i >= n)):
            ckpt, written_at = pending_swap
            inflight_rids = {
                a.request.rid
                for eng in replicas.engines.values()
                for a in eng.slots if a is not None
            }
            swap_report = swap_replicas(replicas, ckpt, template,
                                        ckpt_written_at=written_at)
            pending_swap = None
            watcher = None  # one adoption per trace: later rounds ignored
        stepped = replicas.tick(now)
        finished.extend(stepped)
        if not stepped and replicas.idle and i < n:
            # idle gap before the next arrival: don't busy-spin
            gap = reqs[i].arrival - (time.perf_counter() - t0)
            if gap > 0:
                time.sleep(min(gap, 0.002))
    wall = time.perf_counter() - t0

    lat = np.asarray([a.finished_at - a.request.arrival
                      for _, a in finished])
    toks = int(sum(len(a.tokens) for _, a in finished))
    out = {
        "requests": len(finished),
        "new_tokens": toks,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(toks / wall, 2),
        "p50_ms": round(1e3 * float(np.percentile(lat, 50)), 2),
        "p99_ms": round(1e3 * float(np.percentile(lat, 99)), 2),
        "steps": {int(k): e.steps for k, e in replicas.engines.items()},
        # over-capacity requests gracefully turned away mid-trace
        "rejected": len(replicas.rejected),
        "rejected_rids": sorted(a.request.rid for _, a in replicas.rejected),
    }
    if swap_report is not None:
        done_rids = {a.request.rid for _, a in finished}
        out["swap"] = {
            "round": swap_report.round,
            "max_stall_ms": round(swap_report.max_stall_ms, 3),
            "total_stall_ms": round(swap_report.total_stall_ms, 3),
            "inflight_before": swap_report.inflight_before,
            "inflight_survived": len(inflight_rids & done_rids),
            "reassigned_to_global": swap_report.reassigned_to_global,
            # manifest-on-disk -> all replicas on new weights
            "ckpt_to_adoption_ms": round(swap_report.ckpt_to_adoption_ms, 3),
        }
    return out


def saturated_throughput(params, cfg, requests: List[Request],
                         num_slots: int = 8, capacity: int = 64,
                         **engine_kwargs) -> dict:
    """Peak decode throughput of one continuous-batching engine: every
    request is already queued at t=0 (offered load >> capacity), so slots
    stay full and tokens/sec measures the fused step, not the arrival
    process — the number to compare against ``sequential_oracle``. Extra
    ``engine_kwargs`` (block_size, ...) pass through; the engine may
    return None from try_admit on pool exhaustion, which just holds the
    request at the head of the queue until an eviction."""
    eng = ServeEngine(params, cfg, num_slots=num_slots, capacity=capacity,
                      **engine_kwargs)
    for L in sorted({len(r.prompt) for r in requests}):
        eng.try_admit(Request(rid=-1, client_id=0,
                              prompt=np.zeros(L, np.int32),
                              max_new_tokens=2))
    eng.run_to_completion()
    queue = list(requests)
    toks = 0
    done = 0
    t0 = time.perf_counter()
    while queue or eng.num_active:
        while queue and eng.free_slots():
            a = eng.try_admit(queue[0])
            if a is None:  # page pool exhausted: wait for an eviction
                break
            queue.pop(0)
            if a.done:
                toks += len(a.tokens)
                done += 1
        for fin in eng.step():
            toks += len(fin.tokens)
            done += 1
    wall = time.perf_counter() - t0
    return {
        "requests": done,
        "new_tokens": toks,
        "num_slots": num_slots,
        "wall_s": round(wall, 4),
        "steps": eng.steps,
        "tokens_per_s": round(toks / wall, 2),
        "rejected": eng.rejects,
        "admitted": done - eng.rejects,
        "over_capacity_admits": eng.over_capacity_admits,
    }


def sequential_oracle(params, cfg, requests: List[Request],
                      capacity: int = 64) -> dict:
    """No-batching baseline: the same requests, one at a time, through the
    lockstep ``generate`` oracle (closed loop — throughput only; open-loop
    latency against a sequential server would be unbounded queueing)."""
    # warm one generate per distinct prompt length
    for L in sorted({len(r.prompt) for r in requests}):
        generate(params, cfg, {"tokens": np.zeros((1, L), np.int32)},
                 max_new_tokens=2, capacity=capacity)
    toks = 0
    t0 = time.perf_counter()
    for r in requests:
        out, _ = generate(params, cfg,
                          {"tokens": np.asarray(r.prompt, np.int32)[None]},
                          max_new_tokens=r.max_new_tokens, capacity=capacity)
        toks += int(out.shape[1])
    wall = time.perf_counter() - t0
    return {
        "requests": len(requests),
        "new_tokens": toks,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(toks / wall, 2),
    }


def run_serving_pipeline(
    smoke: bool = False,
    num_slots: int = 8,
    capacity: int = 64,
    num_requests: Optional[int] = None,
    rate: Optional[float] = None,
    traffic: str = "poisson",
    ckpt_dir: str = "ckpts_serving",
    seed: int = 0,
    pipeline: str = "engine",
    kv_block_size: int = 8,
) -> dict:
    """The full federation -> serving pipeline; returns the report dict
    (benchmarks/serving_bench.py writes it to BENCH_serving.json)."""
    cfg = serve_config()
    spec = fl_spec(seed=seed, pipeline=pipeline, smoke=smoke)
    n_req = num_requests or (12 if smoke else 64)
    rate = rate or (30.0 if smoke else 80.0)
    if smoke:
        num_slots, capacity = min(num_slots, 4), min(capacity, 32)

    t0 = time.perf_counter()
    sim, ckpts, history = federate_and_checkpoint(spec, ckpt_dir)
    fl_wall = time.perf_counter() - t0
    if len(ckpts) < 2:
        raise RuntimeError(
            f"expected >= 2 merge checkpoints, got {len(ckpts)} "
            f"(merge_at={spec.merge_at})"
        )

    template = M.init_params(jax.random.PRNGKey(0), cfg)
    replicas = build_replicas(ckpts[0], template, cfg, spec.num_clients,
                              num_slots=num_slots, capacity=capacity,
                              block_size=kv_block_size)
    gen = poisson_requests if traffic == "poisson" else diurnal_requests
    kw = dict(num_clients=spec.num_clients, vocab_size=cfg.vocab_size,
              max_new_tokens=8, seed=seed)
    if traffic == "poisson":
        requests = gen(n_req, rate, **kw)
    else:
        requests = gen(n_req, rate, peak_factor=3.0, period_s=2.0, **kw)
    mid = requests[len(requests) // 2]
    # longer than one slot's capacity: the shared page pool admits it
    requests = requests + [Request(
        rid=10_000, client_id=mid.client_id,
        prompt=np.zeros(4, np.int32), max_new_tokens=capacity + 1,
        arrival=mid.arrival,
    )]
    # the super-poison (> the whole pool): exercises the graceful-reject
    # path end to end
    requests = requests + [Request(
        rid=10_001, client_id=mid.client_id,
        prompt=np.zeros(4, np.int32),
        max_new_tokens=num_slots * capacity + 1,
        arrival=mid.arrival,
    )]
    warm_trace(replicas, requests)

    # arrival-driven adoption: the watcher sees rounds AFTER the one the
    # replicas were built from, so exactly ckpts[1] is adopted mid-trace
    watcher = CheckpointWatcher(ckpt_dir, after_round=ckpts[0].round)
    continuous = serve_trace(replicas, requests, watcher=watcher,
                             template=template)
    final_global = load_model(ckpts[-1].global_path, template)
    saturated = saturated_throughput(final_global, cfg, requests,
                                     num_slots=num_slots, capacity=capacity,
                                     block_size=kv_block_size)
    oracle = sequential_oracle(final_global, cfg, requests,
                               capacity=capacity)
    report = {
        "meta": {
            "arch": cfg.name,
            "num_slots": num_slots,
            "capacity": capacity,
            "kv_block_size": kv_block_size,
            "traffic": traffic,
            "rate_req_s": rate,
            "num_requests": n_req,
            "smoke": smoke,
            "spec": spec.describe(),
        },
        "federation": {
            "rounds": spec.rounds,
            "wall_s": round(fl_wall, 2),
            "final_accuracy": round(float(history[-1].accuracy), 4),
            "merge_rounds": [c.round for c in ckpts],
            "merge_groups": [list(map(list, c.groups)) for c in ckpts],
        },
        "continuous": continuous,
        "saturated": saturated,
        "oracle": oracle,
        # peak continuous-batching decode rate over the no-batching oracle
        # (the open-loop trace's tokens/sec is arrival-gated, so the
        # saturated engine is the honest throughput comparison)
        "throughput_speedup": round(
            saturated["tokens_per_s"] / oracle["tokens_per_s"], 3
        ),
    }
    return report


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--num-slots", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=64)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--traffic", choices=("poisson", "diurnal"),
                    default="poisson")
    ap.add_argument("--ckpt-dir", default="ckpts_serving")
    ap.add_argument("--pipeline", choices=("engine", "device"),
                    default="engine")
    ap.add_argument("--kv-block-size", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="also write the report json here")
    args = ap.parse_args()
    enable_compile_cache()
    report = run_serving_pipeline(
        smoke=args.smoke, num_slots=args.num_slots, capacity=args.capacity,
        num_requests=args.requests, rate=args.rate, traffic=args.traffic,
        ckpt_dir=args.ckpt_dir, seed=args.seed, pipeline=args.pipeline,
        kv_block_size=args.kv_block_size,
    )
    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
