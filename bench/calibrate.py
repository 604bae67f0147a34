#!/usr/bin/env python3
"""Readings that the limits of a cell are set from, on the chip, in one
process: for each seed, the numbers the program gives against the
reference (the lower readings), the control's (the reference one
precision below the configuration's, put in the program's place), and
each fault's that the cell can have (planted in the reference put in the
program's place).

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... --out readings.json

Writes its readings as JSON to ``--out`` and prints one line per seed.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import common, generator, run as R  # noqa: E402


def fed_readings(ctx, seed: int) -> dict:
    import jax.numpy as jnp
    from bench.drivers import fed as F

    st = F.prepare(ctx, seed)
    ref, c = ctx.reference, ctx.config
    args = (c, st["fed"], st["job"], st["params0"], st["shards"], st["seed32"])
    seen: dict = {}
    sim, hist, _ = F.run_job(ctx, st, st["programs"], seen)
    prog = F.readings(sim, hist, seen, st["job"]["merge_at"])
    del sim, hist
    out = {"program": F.check(ref, c, st, prog)}
    out["corr_margin"] = st["free"]["corr_margin"]
    out["control"] = F.check(ref, c, st, ref.follow(*args, dtype=jnp.bfloat16))
    for fault in ("half_batch", "unchanged"):
        out[fault] = F.check(ref, c, st, ref.follow(*args, fault=fault))
    return out


def serve_readings(ctx, seed: int, seconds: float) -> dict:
    from bench.drivers import serve as S

    st = S.setup(ctx, seed)
    items = generator.serving_items(ctx.mix, seed, seconds,
                                    ctx.config["vocab_size"])
    o = S.offer(ctx, st, items, seconds)
    served = S.served_sample(ctx, o, seed)
    w = st["w"]
    del st
    gc.collect()
    ref, m = ctx.reference, ctx.config
    out = {"requests": len(o["book"].done), "sampled": len(served),
           "sampled_tokens": sum(len(t) for _, t in served.values())}
    out["program"] = S.token_gaps(ref, m, w, served)
    out["control"] = S.token_gaps(ref, m, w, served, quant="fp8")
    # a token altered where it is produced: the middle token of each
    # sampled answer replaced by its neighbour in the vocabulary
    bad = copy.deepcopy(served)
    for prompt, toks in bad.values():
        i = len(toks) // 2
        toks[i] = (toks[i] + 1) % m["vocab_size"]
    out["token_altered"] = S.token_gaps(ref, m, w, bad)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", required=True, help="JSON file for the readings")
    args = ap.parse_args(argv)
    manifest, cell, _e, config, mix = R.load_cell(args.workload)
    import jax

    common.require_tpu(jax, int(cell["chips"]))
    common.enable_compile_cache(jax)
    compiles = common.Compiles(jax)
    reference = common.load_module(common.BENCH / "configs" / config["reference"])
    ctx = R.Ctx(cell, config, mix, args.seeds[0], args.seconds, 0, R.T0, jax,
                compiles, reference, ROOT / ".bench_trace" / "calibrate")
    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    readings = {}
    for seed in args.seeds:
        t = time.perf_counter()
        ctx.seed = seed
        if config["driver"] == "fed":
            r = fed_readings(ctx, seed)
        else:
            r = serve_readings(ctx, seed, args.seconds)
        r["seconds"] = time.perf_counter() - t
        readings[str(seed)] = r
        print(json.dumps({"seed": seed, **r}), flush=True)
        out_path.write_text(json.dumps(readings, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
