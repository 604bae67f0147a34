"""What every cell shares: the manifest, the cell's files, the device check,
the table of peaks, the compile counter and the result line."""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(RuntimeError):
    """The run cannot be made as the cell asks; no result is printed."""


def load_manifest() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path.name} at {ROOT}")
    return json.loads(path.read_text())


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path: Path):
    """Import a file by path (config references and metric readers have
    names that are not Python identifiers)."""
    mod_name = "bench_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(BENCH)))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise BenchError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(rel: str) -> dict:
    path = ROOT / rel
    if not path.is_file():
        raise BenchError(f"missing {rel}")
    return json.loads(path.read_text())


def traffic_file(name: str) -> Path:
    p = BENCH / "traffic" / (name + ".json")
    if not p.is_file():
        raise BenchError(f"no traffic file for mix {name!r} under bench/traffic/")
    return p


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak rates of one chip, keyed by ``device_kind`` as JAX reports it.
    A device that is not in the table is an error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return {k: float(v) for k, v in table[device_kind].items()}


def require_tpu(jax, chips: int):
    """The devices this cell runs on. Exits (no result) when JAX finds no
    TPU or fewer chips than the cell asks for: never a CPU fallback."""
    devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devices[0].platform if devices else 'nothing'}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def enable_compile_cache(jax) -> str:
    """JAX's persistent compilation cache at one fixed directory inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), holding every
    program however quick it was to compile, so that a warm run compiles
    nothing."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Compiles:
    """Counts backend compiles and persistent-cache hits and misses."""

    def __init__(self, jax):
        self.n = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.n, "compile_s": self.seconds,
                "cache_hits": self.hits, "cache_misses": self.misses}


@dataclass
class Check:
    """One number compared with the reference, beside its limit. The run is
    correct when every number is at or under its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between ranks."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
