"""BENCHMARK.json against the rules it is read by: names and units of the
allowed characters, every cell's files found by name, and a run length the
full check can afford."""
import json
import re

import pytest

from bench import common

B = json.loads((common.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
METRICS = B["end_to_end"] + B["per_layer"]


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "bench/run.py"]
    assert all(PATH.match(p) and ".." not in p for p in B["paths"])
    assert len((common.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("entry", B["configs"] + B["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
            assert "\t" not in entry[key]


def test_names_are_unique():
    for group in (B["configs"], B["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    cells = {w["name"] for w in B["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    assert (common.BENCH / "metrics" / (metric["name"] + ".py")).is_file()
    if metric in B["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        moves = next(m for m in B["end_to_end"] if m["name"] == metric["moves"])
        # every cell that reads it reports the metric it moves
        assert set(metric["workloads"]) <= set(moves.get("workloads", cells))
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


@pytest.mark.parametrize("cell", B["workloads"], ids=lambda w: w["name"])
def test_cells(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    cfg = next(c for c in B["configs"] if c["name"] == cell["config"])
    conf = json.loads((common.ROOT / cfg["file"]).read_text())
    assert (common.BENCH / "configs" / conf["reference"]).is_file()
    assert (common.BENCH / "drivers" / (conf["driver"] + ".py")).is_file()
    assert common.traffic_file(cell["traffic"]).is_file()
    e2e = [m for m in B["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert any(cell["name"] in m.get("workloads", []) for m in B["per_layer"])
    assert set(conf["limits"]) and all(v >= 0 for v in conf["limits"].values())


def test_configs():
    files = [c["file"] for c in B["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in B["workloads"]}
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["file"].startswith("bench/") and (common.ROOT / c["file"]).is_file()
        assert len(c["reduced"]) <= 16
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_run_length_fits_the_full_check():
    rs = B["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24
    total = (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200
    assert total <= 43200
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(1, len(B["workloads"]) // 2)
