#!/usr/bin/env python3
"""Device time of a traced run's programs by named scope.

    python3 bench/scopes.py TRACE.xplane.pb

A ``jax.named_scope`` reaches each HLO instruction's ``op_name``, but a TPU
trace's device events name only the instruction. The profiler embeds every
compiled program's HLO proto in the ``/host:metadata`` plane; this reads
the instruction names and op_names from there (with a small protobuf wire
reader, so nothing beyond JAX is needed) and sums, for the programs in
``SCOPES``, the union of the device time of their operations inside
``bench.window`` on the first chip that ran anything, by the innermost of
the program's scopes in each op_name. Loops and calls are left out: the
operations of their bodies are events of their own.
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterator, List, Tuple

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.trace import (MODULES_LINE, OPS_LINE, WINDOW_SPAN, _clip,  # noqa: E402
                         _module_name, _short_op, _union)

# the named scopes each program sets (core/engine.merge_device,
# serving/engine._paged_step and models/model.decode_step)
SCOPES = {
    "jit_merge_device": ("train", "similarity", "plan", "mix"),
    "jit_step": ("view", "embed", "layers", "mixer", "ffn", "head",
                 "write_back", "sample"),
}
CONTAINERS = ("while", "conditional", "call")
NONE = "(no scope)"
ALL = "(all)"


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field, raw bytes for a fixed one."""
    buf, i = memoryview(buf), 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, v


def _get(buf, field: int):
    return next((v for f, v in _fields(buf) if f == field), b"")


def _op_names(hlo_proto) -> Dict[str, str]:
    """instruction name -> op_name, from an ``xla.HloProto``
    (hlo_module 1 > computations 3 > instructions 2 > name 1, metadata 7 >
    op_name 2)."""
    out = {}
    for comp in (v for f, v in _fields(_get(hlo_proto, 1)) if f == 3):
        for ins in (v for f, v in _fields(comp) if f == 2):
            name, op = "", ""
            for f, v in _fields(ins):
                if f == 1:
                    name = bytes(v).decode()
                elif f == 7:
                    op = bytes(_get(v, 2)).decode()
            out[name] = op
    return out


def hlo_op_names(data: bytes) -> Dict[str, Dict[str, str]]:
    """For each program of ``SCOPES`` the trace embeds, by its name with
    fingerprint (``jit_step(123...)``): instruction name -> op_name. In an
    ``XSpace`` (planes 1) the metadata plane (name 2) maps (event_metadata
    4, entries key 1 and value 2) to ``XEventMetadata``s (name 2) whose
    first stat (stats 5) holds the proto as bytes (bytes_value 6)."""
    out = {}
    for plane in (v for f, v in _fields(data) if f == 1):
        if bytes(_get(plane, 2)) != b"/host:metadata":
            continue
        for entry in (v for f, v in _fields(plane) if f == 4):
            meta = _get(entry, 2)
            name = bytes(_get(meta, 2)).decode()
            if _module_name(name) not in SCOPES:
                continue
            stat = _get(meta, 5)
            if stat:
                out[name] = _op_names(_get(stat, 6))
    return out


def _scope(op_name: str, scopes) -> str:
    parts = op_name.split("/")
    for part in reversed(parts):
        if part in scopes:
            return part
    return NONE


def reduce_planes(planes, op_names: Dict[str, Dict[str, str]]) -> dict:
    """{program: {scope: seconds, ALL: seconds}} and the count of
    operations whose instruction the program's proto does not name."""
    planes = list(planes)
    lo = hi = None
    for p in planes:
        if p.name.startswith("/host:"):
            for ln in p.lines:
                for e in ln.events:
                    if e.name == WINDOW_SPAN:
                        lo, hi = e.start_ns, e.start_ns + e.duration_ns
    if lo is None:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    for p in planes:
        if not (p.name.startswith("/device:") and "TPU" in p.name
                and "SparseCore" not in p.name):
            continue
        lines = {ln.name: ln for ln in p.lines}
        if OPS_LINE not in lines or MODULES_LINE not in lines:
            continue
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in lines[MODULES_LINE].events)
        if not mods:
            continue
        starts = [m[0] for m in mods]
        per: Dict[Tuple[str, str], List[Tuple[int, int]]] = defaultdict(list)
        unnamed = 0
        j = 0
        for e in sorted(lines[OPS_LINE].events, key=lambda e: e.start_ns):
            s, t = e.start_ns, e.start_ns + e.duration_ns
            while j + 1 < len(starts) and starts[j + 1] <= s:
                j += 1
            mod = mods[j][2] if mods[j][0] <= s and t <= mods[j][1] else None
            program = _module_name(mod) if mod else None
            if program not in SCOPES:
                continue
            short = _short_op(e.name)
            if short.split(".")[0] in CONTAINERS:
                continue
            names = op_names.get(mod, {})
            if short not in names:
                unnamed += 1
            scope = _scope(names.get(short, ""), SCOPES[program])
            for key in ((program, scope), (program, ALL)):
                per[key].extend(_clip([(s, t)], lo, hi))
        out: Dict[str, Dict[str, float]] = defaultdict(dict)
        for (program, scope), iv in sorted(per.items()):
            out[program][scope] = sum(b - a for a, b in _union(iv)) * 1e-9
        return {"programs": dict(out), "unnamed_ops": unnamed}
    return {"programs": {}, "unnamed_ops": 0}


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData

    data = Path(path).read_bytes()
    return reduce_planes(ProfileData.from_serialized_xspace(data).planes,
                         hlo_op_names(data))


def main(argv: List[str]) -> int:
    r = reduce_file(argv[0])
    for program, scopes in r["programs"].items():
        total = scopes[ALL]
        for scope, s in sorted(scopes.items(), key=lambda kv: -kv[1]):
            print(f"{program} {scope} {s:.6f} s {100 * s / total:.2f}%")
    print(f"operations their program's proto does not name: {r['unnamed_ops']}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
