"""Serving benchmark: continuous batching vs the sequential generate
oracle, over intermediary models produced by a real federation run, with
a mid-trace merge-round hot-swap.

What the report answers (schema below, asserted by the CI smoke leg and
tests/test_serving_engine.py):

  * peak tokens/sec of the fixed-slot continuous-batching engine
    (``saturated``, slots kept full) vs one-request-at-a-time ``generate``
    (``oracle``) on the same requests — ``throughput_speedup`` is the
    acceptance number (> 1 at num_slots >= 8);
  * open-loop p50/p99 latency under Poisson traffic routed across the
    cluster replicas (``continuous``);
  * hot-swap cost: per-replica stall in ms with requests in flight
    (``continuous.swap``), in-flight count surviving the swap, and the
    checkpoint-manifest-on-disk -> adoption latency (arrival-driven swap).

Output: ``BENCH_serving.json``.

  PYTHONPATH=src python -m benchmarks.serving_bench            # full
  PYTHONPATH=src python -m benchmarks.serving_bench --smoke    # CI leg
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

from repro.launch.serve_fl import run_serving_pipeline

SCHEMA_KEYS = ("meta", "federation", "continuous", "saturated", "oracle",
               "throughput_speedup")


def check_schema(report: dict) -> None:
    for k in SCHEMA_KEYS:
        assert k in report, f"missing report key: {k}"
    for k in ("tokens_per_s", "p50_ms", "p99_ms", "swap", "rejected"):
        assert k in report["continuous"], f"missing continuous key: {k}"
    swap = report["continuous"]["swap"]
    for k in ("round", "max_stall_ms", "inflight_before",
              "inflight_survived", "ckpt_to_adoption_ms"):
        assert k in swap, f"missing swap key: {k}"
    assert swap["inflight_survived"] == swap["inflight_before"], (
        "requests in flight at the hot-swap did not all complete"
    )
    assert swap["ckpt_to_adoption_ms"] > 0, (
        "arrival-driven swap must stamp manifest-to-adoption latency"
    )
    assert report["saturated"]["tokens_per_s"] > 0
    assert report["oracle"]["tokens_per_s"] > 0
    # the trace carries two poison requests by construction: rid 10_000
    # (> per-slot capacity) is ADMITTED by the shared page pool, while
    # rid 10_001 (> the whole pool) must still be rejected gracefully,
    # not crash the driver loop
    assert report["continuous"]["rejected"] >= 1
    assert 10_000 not in report["continuous"]["rejected_rids"], (
        "the page pool must admit the over-per-slot-capacity request"
    )
    assert 10_001 in report["continuous"]["rejected_rids"]


def run(smoke: bool = False, out: str = "BENCH_serving.json",
        num_slots: int = 8, seed: int = 0) -> dict:
    t0 = time.time()
    with tempfile.TemporaryDirectory(prefix="serving_bench_") as ckpt_dir:
        report = run_serving_pipeline(
            smoke=smoke, num_slots=num_slots, ckpt_dir=ckpt_dir, seed=seed,
        )
    report["wall_s"] = round(time.time() - t0, 1)
    check_schema(report)
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    c, s, o = report["continuous"], report["saturated"], report["oracle"]
    print(f"[serving_bench] {c['requests']} reqs -> {out} "
          f"({report['wall_s']}s)")
    print(f"  open-loop : {c['tokens_per_s']} tok/s "
          f"p50={c['p50_ms']}ms p99={c['p99_ms']}ms")
    print(f"  saturated : {s['tokens_per_s']} tok/s "
          f"({s['num_slots']} slots, {s['steps']} steps)")
    print(f"  oracle    : {o['tokens_per_s']} tok/s sequential")
    print(f"  speedup   : {report['throughput_speedup']}x  "
          f"swap stall max={c['swap']['max_stall_ms']}ms "
          f"inflight={c['swap']['inflight_before']} "
          f"adopt={c['swap']['ckpt_to_adoption_ms']}ms")
    return report


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (tiny trace, 4 slots)")
    ap.add_argument("--num-slots", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args()
    run(smoke=args.smoke, out=args.out, num_slots=args.num_slots,
        seed=args.seed)


if __name__ == "__main__":
    main()
