"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

The window is the benchmark's own host span ``bench.window``. On each chip
the device's operations are the events of its ``XLA Ops`` line; busy time
is the union of their intervals inside the window, averaged over the chips
that ran anything. Each operation's and each compiled program's time is
summed by name, and for the breakdown by program and short name. Custom calls (the compiled Pallas kernels) are also summed
by the program that ran them, so that a kernel is found by its program's
name whatever name the compiler gives the call. Each idle gap is named by
what the host was doing in it: the innermost host event that covers the
gap's middle, on the host thread that carries the benchmark's spans.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _short_op(name: str) -> str:
    """An operation's name without its HLO text: on a TPU the event's name
    is the whole instruction (``%fusion.3 = f32[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _is_custom_call(ev) -> bool:
    if "custom" in ev.name.lower():
        return True
    return any("custom_call" in str(v) or "custom-call" in str(v)
               for _k, v in ev.stats)


def reduce_planes(planes) -> dict:
    """planes: iterable of objects with ``name`` and ``lines``; each line has
    ``name`` and ``events`` with ``name``, ``start_ns``, ``duration_ns``."""
    host_lines, dev_planes = [], []
    for p in planes:
        if p.name.startswith("/device:") and "TPU" in p.name \
                and "SparseCore" not in p.name:
            dev_planes.append(p)
        elif p.name.startswith("/host:"):
            host_lines.extend(p.lines)
    window = None
    main = []
    for ln in host_lines:
        evs = list(ln.events)
        spans = [e for e in evs if e.name.startswith("bench.")]
        if spans:
            main.append(evs)
        for e in spans:
            if e.name == WINDOW_SPAN:
                window = (e.start_ns, e.start_ns + e.duration_ns)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = window
    busy_total, n_busy = 0.0, 0
    ops: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    modules: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    kernels: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    by_program: Dict[str, float] = defaultdict(float)  # "program/op" -> seconds
    first_busy = None
    custom: Dict[str, bool] = {}  # op name -> is a custom call (first event)
    for p in dev_planes:
        busy = []
        spans = []  # (start, end, program) of this chip's programs
        lines = {ln.name: ln for ln in p.lines}
        if MODULES_LINE in lines:
            for e in lines[MODULES_LINE].events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                spans.append((s, t, _module_name(e.name)))
                if t <= lo or s >= hi:
                    continue
                m = modules[_module_name(e.name)]
                m[0] += (min(t, hi) - max(s, lo)) * 1e-9
                m[1] += 1
        spans.sort()
        starts = [x[0] for x in spans]
        if OPS_LINE in lines:
            for e in lines[OPS_LINE].events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t <= lo or s >= hi:
                    continue
                busy.append((s, t))
                dt = (min(t, hi) - max(s, lo)) * 1e-9
                ops[e.name][0] += dt
                ops[e.name][1] += 1
                if e.name not in custom:
                    custom[e.name] = _is_custom_call(e)
                i = bisect.bisect_right(starts, s) - 1
                prog = spans[i][2] if i >= 0 and spans[i][1] >= t else "(no program)"
                by_program[f"{prog}/{_short_op(e.name)}"] += dt
                if custom[e.name]:
                    kernels[prog][0] += dt
                    kernels[prog][1] += 1
        busy = _union(_clip(busy, lo, hi))
        if busy:
            busy_total += sum(e - s for s, e in busy) * 1e-9
            n_busy += 1
            if first_busy is None:
                first_busy = busy
    gaps = []
    if first_busy is not None:
        edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:10]:
        mid = (s + e) // 2
        best = None
        for evs in main:
            for ev in evs:
                if ev.start_ns <= mid <= ev.start_ns + ev.duration_ns \
                        and ev.name != WINDOW_SPAN:
                    if best is None or ev.duration_ns < best.duration_ns:
                        best = ev
        named.append([best.name if best is not None else "(no host event)",
                      (e - s) * 1e-9])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / max(n_busy, 1),
        "chips_busy": n_busy,
        "ops": {k: {"s": v[0], "n": int(v[1])} for k, v in ops.items()},
        "modules": {k: {"s": v[0], "n": int(v[1])} for k, v in modules.items()},
        "kernels": {k: {"s": v[0], "n": int(v[1])} for k, v in kernels.items()},
        "device_ops": [[k, v] for k, v in sorted(
            by_program.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": named,
    }


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)


def reduce_dir(trace_dir) -> dict:
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not files:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(max(files))
