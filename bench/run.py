#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell's configuration file
(``configs[].file``) names the driver (``bench/drivers/<driver>.py``) and
the plain reference beside it; the traffic mix is ``bench/traffic/<mix>.json``;
each metric is read by ``bench/metrics/<metric>.py``. Adding a cell, a mix
or a metric adds files and entries; nothing here changes.

Set-up (data, weights, compiling or loading every program the window uses)
is timed as ``setup_s``; the window then runs for ``--seconds``. With
``--trace 1`` the profiler records the window and the per-layer metrics are
printed; otherwise the end-to-end ones. After the window the reference
checks what the timed path produced. The last stdout line is one JSON
object; the numbers compared, each beside its limit, are the last lines on
stderr and the last key of that object. With no TPU, or too few chips, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import common  # noqa: E402


class Ctx:
    """What a driver gets: the cell's data, the clock it started on, the
    compile counter, and the trace and span hooks."""

    def __init__(self, cell, config, mix, seed, seconds, trace, t0, jax,
                 compiles, reference, trace_dir):
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds, self.trace = int(seed), float(seconds), bool(trace)
        self.t0, self.jax, self.compiles = t0, jax, compiles
        self.reference = reference
        self.trace_dir = trace_dir
        self._window = None

    def span(self, name: str):
        """A host span in the profiler's trace (a no-op when not tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        return self.jax.profiler.TraceAnnotation(name)

    def start_trace(self):
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.jax.profiler.start_trace(str(self.trace_dir))
            self._window = self.jax.profiler.TraceAnnotation("bench.window")
            self._window.__enter__()

    def end_window(self):
        """Closes the traced window's span; the profiler runs on until
        ``stop_trace``, so that writing and reading the trace delays no
        work that the run still waits for."""
        if self.trace and self._window is not None:
            self.jax.effects_barrier()
            self._window.__exit__(None, None, None)
            self._window = None

    def stop_trace(self):
        """Stops the profiler and reduces its trace; None when not tracing."""
        if not self.trace:
            return None
        self.end_window()
        self.jax.profiler.stop_trace()
        from bench import trace as T
        return T.reduce_dir(self.trace_dir)

    def memory_peak(self) -> int:
        stats = self.jax.devices()[0].memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))


def load_cell(name: str):
    manifest = common.load_manifest()
    cell = common.find(manifest["workloads"], name, "workload")
    cfg_entry = common.find(manifest["configs"], cell["config"], "config")
    config = common.load_json(cfg_entry["file"])
    mix = json.loads(common.traffic_file(cell["traffic"]).read_text())
    return manifest, cell, cfg_entry, config, mix


def cell_metrics(manifest: dict, cell: str, kind: str):
    """The metrics of ``kind`` (end_to_end or per_layer) this cell reports."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell in m["workloads"]]


def read_metrics(metrics, run: dict) -> dict:
    out = {}
    for m in metrics:
        reader = common.load_module(common.BENCH / "metrics" / (m["name"] + ".py"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = execute(args.workload, args.seed, args.seconds, args.trace)
    except common.BenchError as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


def execute(workload: str, seed: int, seconds: float, trace: int,
            chip: bool = True, config=None, mix=None, peak=None) -> dict:
    """One run of one cell; returns the result line's object. ``chip``
    False skips the look for a chip (the benchmark's own tests, on the
    CPU), where ``config``, ``mix`` and ``peak`` may replace the cell's
    files and the table's peaks."""
    manifest, cell, _entry, cfg_file, mix_file = load_cell(workload)
    config = cfg_file if config is None else config
    mix = mix_file if mix is None else mix
    import jax

    if chip:
        devices = common.require_tpu(jax, int(cell["chips"]))
        peak = common.peaks(devices[0].device_kind)
    else:
        devices = jax.devices()[: int(cell["chips"])]
    dev = devices[0]
    cache = common.enable_compile_cache(jax) if chip else "off"
    compiles = common.Compiles(jax)
    reference = common.load_module(common.BENCH / "configs" / config["reference"])
    driver = common.load_module(common.BENCH / "drivers" / (config["driver"] + ".py"))
    trace_dir = ROOT / ".bench_trace" / workload
    ctx = Ctx(cell, config, mix, seed, seconds, trace, T0, jax, compiles,
              reference, trace_dir)
    print(f"bench: {workload} on {len(devices)} x {dev.device_kind}, "
          f"seed {seed}, compile cache {cache}", file=sys.stderr, flush=True)
    res = driver.run(ctx)
    return report(manifest, cell, config, res, peak, dev, len(devices),
                  trace, compiles)


def report(manifest, cell, config, res, peak, dev, n_dev, traced, compiles
           ) -> dict:
    limits = config["limits"]
    checks = [common.Check(k, float(v), float(limits[k]))
              for k, v in res["numbers"].items()]
    correct = all(ch.ok for ch in checks) and res["failed"] == 0
    run = dict(res, peaks=peak, config=config, cell=cell)
    kind = "per_layer" if traced else "end_to_end"
    metrics = read_metrics(cell_metrics(manifest, cell["name"], kind), run)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev, "memory_peak_bytes": int(res["memory_peak_bytes"])}
    out = {"correct": bool(correct), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics, "device": device}
    if traced and res.get("trace"):
        tr = res["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"][:10],
                            "idle_gaps": tr["idle_gaps"][:10]}
    out["checks"] = {ch.name: {"value": ch.value, "limit": ch.limit}
                     for ch in checks}
    print(f"bench: set-up {res['setup_s']:.3f} s, window {res['window_s']:.3f} s, "
          f"compiles in the window {res['compiles_in_window']}, "
          f"all compiles {json.dumps(compiles.snapshot())}", file=sys.stderr)
    print(f"bench: notes {json.dumps(res.get('notes', {}))}", file=sys.stderr)
    for ch in checks:
        print(f"check {ch.name} {ch.value!r} limit {ch.limit!r} "
              f"{'ok' if ch.ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    return out


if __name__ == "__main__":
    sys.exit(main())
