#!/usr/bin/env python3
"""Rate sweep of an open-loop serving cell, on the chip, in one process:
set up once, then offer the cell's mix at each rate for ``--seconds`` and
report what was completed and how long requests waited. The knee is the
highest rate at which the backlog at the window's close stays small and
the time to first token does not grow with the window; the cell's traffic
file then holds a fixed rate below it. The benchmark's own runs never run
this.

    python3 bench/sweep.py --workload serve-qwen3-chat --rates 4 6 8 10 12 --out sweep.json

Writes its readings as JSON to ``--out`` and prints one line per rate.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import common, generator, run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True, help="JSON file for the readings")
    args = ap.parse_args(argv)
    manifest, cell, _e, config, mix = R.load_cell(args.workload)
    import jax

    common.require_tpu(jax, int(cell["chips"]))
    common.enable_compile_cache(jax)
    reference = common.load_module(common.BENCH / "configs" / config["reference"])
    ctx = R.Ctx(cell, config, mix, args.seed, args.seconds, 0, R.T0, jax,
                common.Compiles(jax), reference, ROOT / ".bench_trace" / "sweep")
    from bench.drivers import serve as S

    st = S.setup(ctx, args.seed)
    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    rows = []
    for rate in args.rates:
        m = dict(mix, rate_per_s=rate)
        ctx.mix = m
        items = generator.serving_items(m, args.seed, args.seconds,
                                        config["vocab_size"])
        o = S.offer(ctx, st, items, args.seconds)
        rec = S.summary(ctx, o)
        row = {"rate_per_s": rate, "sent": len(o["book"].due),
               "tokens_per_s": rec["tokens_in_window"] / o["window_s"],
               "ttft_p50_ms": 1e3 * common.percentile(rec["ttft_s"], 50),
               "ttft_p95_ms": 1e3 * common.percentile(rec["ttft_s"], 95),
               "itl_p50_ms": 1e3 * common.percentile(rec["itl_s"], 50),
               "itl_p95_ms": 1e3 * common.percentile(rec["itl_s"], 95),
               "queue_p50_ms": 1e3 * common.percentile(rec["queue_s"], 50),
               "backlog_at_close": o["backlog"], "drain_s": o["drain_s"],
               "mean_rows": (sum(rec["rows_per_step"]) / len(rec["rows_per_step"])
                             if rec["rows_per_step"] else 0.0),
               "compiles_in_window": o["compiles_in_window"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        out_path.write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
