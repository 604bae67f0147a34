"""The program's own spans in a profiler trace (``.xplane.pb``).

The program marks its layers with ``jax.profiler.TraceAnnotation``s named
``fed.<part>`` and ``serve.<part>`` (listed in ``repro.program_spans``);
their keyword arguments are counters, labels or groups. They land in the
same trace as the benchmark's ``bench.*`` spans and the device's ``XLA
Ops``, on one clock. For every such span that starts inside
``bench.window``, by name: the count, the total seconds, the self seconds
(the total less the program spans nested in it on the same thread), the
device's idle seconds inside it (against the union of the operations of
the first chip that ran anything, clipped to the window), the median and
p95 of the durations, the sum of each numeric counter, how often it
nested in each other program span, and the same count, seconds, median and
idle seconds by each value of a group argument (``GROUPS``). Labels
(``LABELS``) name one event for a trace viewer and are not reduced.
Besides, for the window: its idle seconds, those that no program span
covers, and the longest of the latter named by the innermost host event on
the benchmark's thread.

A metric reader finds a span of its run with ``find``.
"""
from __future__ import annotations

import bisect
import glob
import importlib
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import common
from bench.trace import OPS_LINE, WINDOW_SPAN, _clip, _union

PROGRAM_SPAN = re.compile(r"^(fed|serve)\.[a-z_]+$")
TRACE_DIR = common.ROOT / ".bench_trace"  # where bench/run.py traces a cell
NAMED_GAPS = 5
LABELS = ("rid", "round")  # which request or round: for a trace viewer
GROUPS = ("admitted", "bucket", "view")  # the outcome or shape of the work


def _is_chip(plane) -> bool:
    return (plane.name.startswith("/device:") and "TPU" in plane.name
            and "SparseCore" not in plane.name)


def _busy(planes, lo: int, hi: int) -> List[Tuple[int, int]]:
    """The union of the first busy chip's operations inside the window."""
    for p in planes:
        if not _is_chip(p):
            continue
        for ln in p.lines:
            if ln.name == OPS_LINE:
                busy = _union(_clip([(e.start_ns, e.start_ns + e.duration_ns)
                                     for e in ln.events], lo, hi))
                if busy:
                    return busy
    return []


class _Busy:
    """Busy time inside any interval, by prefix sums over the union."""

    def __init__(self, busy: List[Tuple[int, int]]):
        self.starts = [s for s, _ in busy]
        self.ends = [e for _, e in busy]
        self.cum = [0]
        for s, e in busy:
            self.cum.append(self.cum[-1] + e - s)

    def _before(self, t: int) -> int:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0
        return self.cum[i - 1] + min(t, self.ends[i - 1]) - self.starts[i - 1]

    def within(self, s: int, e: int) -> int:
        return self._before(e) - self._before(s) if e > s else 0


def _subtract(a: List[Tuple[int, int]], b: List[Tuple[int, int]]):
    """Intervals of ``a`` not covered by ``b`` (both sorted and disjoint)."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _numeric(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def reduce_planes(planes) -> dict:
    """planes: iterable of objects with ``name`` and ``lines``; each line has
    ``name`` and ``events`` with ``name``, ``start_ns``, ``duration_ns`` and
    ``stats`` ((name, value) pairs)."""
    planes = list(planes)
    host = [list(ln.events) for p in planes if p.name.startswith("/host:")
            for ln in p.lines]
    window, bench_lines = None, []
    for evs in host:
        if any(e.name.startswith("bench.") for e in evs):
            bench_lines.append(evs)
        for e in evs:
            if e.name == WINDOW_SPAN:
                window = (e.start_ns, e.start_ns + e.duration_ns)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = window
    busy = _busy(planes, lo, hi)
    bz = _Busy(busy)

    durs: Dict[str, List[int]] = defaultdict(list)
    self_ns: Dict[str, int] = defaultdict(int)
    idle_ns: Dict[str, int] = defaultdict(int)
    args: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    within: Dict[str, Dict[str, int]] = defaultdict(lambda: defaultdict(int))
    # span name -> group argument -> its value -> [(duration, idle)]
    by: Dict[str, Dict[str, Dict[str, list]]] = defaultdict(
        lambda: defaultdict(lambda: defaultdict(list)))
    covered = []
    for evs in host:
        spans = sorted(((e.start_ns, -e.duration_ns, e) for e in evs
                        if lo <= e.start_ns < hi and PROGRAM_SPAN.match(e.name)),
                       key=lambda x: x[:2])
        stack: List[list] = []  # [end, name, nested ns]
        for s, neg, e in spans:
            end = s - neg
            while stack and stack[-1][0] <= s:
                top = stack.pop()
                self_ns[top[1]] -= top[2]
            if stack:
                stack[-1][2] += end - s
                within[e.name][stack[-1][1]] += 1
            stack.append([end, e.name, 0])
            durs[e.name].append(end - s)
            self_ns[e.name] += end - s
            cs, ce = max(s, lo), min(end, hi)
            idle = (ce - cs) - bz.within(cs, ce)
            idle_ns[e.name] += idle
            covered.append((cs, ce))
            for k, v in e.stats:
                if k in GROUPS:
                    by[e.name][k][str(v)].append((end - s, idle))
                elif k not in LABELS and _numeric(v):
                    args[e.name][k] += v
        for top in stack:
            self_ns[top[1]] -= top[2]

    idle = _subtract([(lo, hi)], busy)
    outside = _subtract(idle, _union(covered))
    outside.sort(key=lambda g: g[0] - g[1])
    mids = [(s + e) // 2 for s, e in outside[:NAMED_GAPS]]
    names: List[Optional[Tuple[int, str]]] = [None] * len(mids)
    for evs in bench_lines:
        for ev in evs:
            if ev.name == WINDOW_SPAN:
                continue
            for i, m in enumerate(mids):
                if ev.start_ns <= m <= ev.start_ns + ev.duration_ns and (
                        names[i] is None or ev.duration_ns < names[i][0]):
                    names[i] = (ev.duration_ns, ev.name)
    return {
        "window_s": (hi - lo) * 1e-9,
        "idle_s": sum(e - s for s, e in idle) * 1e-9,
        "idle_outside_spans_s": sum(e - s for s, e in outside) * 1e-9,
        "idle_outside_spans": [
            [n[1] if n is not None else "(no host event)", (e - s) * 1e-9]
            for n, (s, e) in zip(names, outside)],
        "spans": {
            name: {"n": len(d), "s": sum(d) * 1e-9,
                   "self_s": self_ns[name] * 1e-9,
                   "idle_s": idle_ns[name] * 1e-9,
                   "p50_s": common.percentile(d, 50) * 1e-9,
                   "p95_s": common.percentile(d, 95) * 1e-9,
                   "args": dict(args[name]), "within": dict(within[name]),
                   "by": {k: {v: _group(ev) for v, ev in sorted(vals.items())}
                          for k, vals in by[name].items()}}
            for name, d in durs.items()},
    }


def _group(events: List[Tuple[int, int]]) -> dict:
    d = [t for t, _ in events]
    return {"n": len(d), "s": sum(d) * 1e-9,
            "p50_s": common.percentile(d, 50) * 1e-9,
            "idle_s": sum(i for _, i in events) * 1e-9}


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)


_CACHE: Dict[tuple, dict] = {}


def of(run: dict) -> Optional[dict]:
    """The span reduction of a traced run; None when the run was not
    traced. A reduced trace that already carries ``spans`` is used as it
    is; otherwise the trace ``bench/run.py`` wrote for the cell is read
    (once for all the run's readers), and must be the one whose window the
    run reduced."""
    tr = run.get("trace")
    if not tr:
        return None
    if "spans" in tr:
        return tr
    files = glob.glob(f"{TRACE_DIR / run['cell']['name']}/**/*.xplane.pb",
                      recursive=True)
    if not files:
        raise common.BenchError(f"no .xplane.pb of {run['cell']['name']} "
                                f"under {TRACE_DIR}")
    path = max(files)
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = reduce_file(path)
    red = _CACHE[key]
    if abs(red["window_s"] - tr["window_s"]) > 1e-6:
        raise common.BenchError(f"{path} is not the trace of this run: its "
                                f"window is {red['window_s']} s, the run's "
                                f"{tr['window_s']} s")
    return red


def declared() -> tuple:
    """The span names the program under test lists (``repro.program_spans``);
    empty for a program from before its spans."""
    try:
        return importlib.import_module("repro.program_spans").NAMES
    except ModuleNotFoundError:
        return ()


def find(run: dict, name: str, done: bool) -> Optional[dict]:
    """The reduction's entry for the program span ``name``: None where the
    run was not traced, did none of the span's work (``done`` false), or
    ran a program that lists no spans (``declared``: one from before
    them). A traced run that did the work and holds no such span is an
    error: the span's name changed or its annotation went, and its metric
    would fall silent."""
    red = of(run) if done else None
    if red is None:
        return None
    spans = red["spans"]
    if name in spans:
        return spans[name]
    if not declared():
        return None
    raise common.BenchError(f"the run did the work of span {name!r}, but "
                            f"the trace has only {sorted(spans)}")
