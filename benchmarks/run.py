"""Benchmark harness — one entry per paper table/figure, the kernel
micro-benchmarks and the merge pipeline. Output: section banners +
``name,value,derived`` CSV-ish lines. The on-chip benchmark is
``bench/run.py``.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --fast     # skip the full fig2 FL runs
"""
from __future__ import annotations

import argparse
import sys
import time


def _section(title):
    print(f"\n{'='*72}\n== {title}\n{'='*72}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced fig2 runs (smaller data, same protocol)")
    ap.add_argument("--force", action="store_true", help="ignore fig2 cache")
    args = ap.parse_args()
    t0 = time.time()

    _section("Fig 1 — non-IID partition (paper Fig. 1)")
    from benchmarks import fig1_partition
    fig1_partition.run()

    _section("Fig 2 — robustness: proposed vs SCAFFOLD (paper Fig. 2 / abstract)")
    from benchmarks import fig2_robustness
    fig2_robustness.run(fast=args.fast, force=args.force)

    _section("Comm savings from merging (paper §IV)")
    from benchmarks import comm_savings
    comm_savings.run()

    _section("Ablations — threshold / merge round / group size (paper §VI)")
    from benchmarks import ablations
    ablations.run()

    _section("Kernel micro-benchmarks")
    from benchmarks import kernels_bench
    kernels_bench.run()

    _section("Merge pipeline — streaming/device vs materialized/host")
    from benchmarks import merge_pipeline
    merge_pipeline.run()

    print(f"\ntotal bench wall time: {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
