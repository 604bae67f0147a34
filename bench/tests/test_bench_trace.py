"""The trace reduction: busy time is the union of the device's operations
inside the benchmark's window, operations and programs are summed by name,
and idle gaps are named by the host's innermost event."""
from dataclasses import dataclass, field
from pathlib import Path
from typing import List

import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"


@dataclass
class Ev:
    name: str
    start_ns: int
    duration_ns: int
    stats: tuple = ()


@dataclass
class Line:
    name: str
    events: List[Ev] = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: List[Line] = field(default_factory=list)


def planes():
    ms = 1_000_000
    host = Plane("/host:CPU", [
        Line("python", [Ev("bench.window", 0, 100 * ms),
                        Ev("bench.tick", 10 * ms, 30 * ms),
                        Ev("PjitFunction(step)", 12 * ms, 2 * ms),
                        Ev("bench.wait", 36 * ms, 18 * ms),
                        Ev("bench.tick", 50 * ms, 40 * ms)]),
        Line("other", [Ev("ThreadpoolListener", 0, 100 * ms)]),
    ])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_step(12)", 15 * ms, 20 * ms),
                             Ev("jit_admit(3)", 55 * ms, 30 * ms)]),
        Line("XLA Ops", [Ev("fusion.1", 15 * ms, 10 * ms),
                         Ev("k.7", 20 * ms, 15 * ms,  # overlaps; a kernel
                            (("long_name", "k.7 = custom-call(...)"),)),
                         Ev("flash", 55 * ms, 30 * ms),
                         Ev("late", 99 * ms, 5 * ms)]),         # clipped
    ])
    sparse = Plane("/device:TPU:0 SparseCore 0", [Line("XLA Ops", [Ev("x", 0, 100 * ms)])])
    return [host, dev, sparse]


def test_busy_is_the_union_inside_the_window():
    r = T.reduce_planes(planes())
    assert r["window_s"] == pytest.approx(0.1)
    # [15, 35] + [55, 85] + [99, 100] ms
    assert r["busy_s"] == pytest.approx(0.051)
    assert r["chips_busy"] == 1


def test_ops_and_programs_by_name():
    r = T.reduce_planes(planes())
    assert r["ops"]["k.7"] == {"s": pytest.approx(0.015), "n": 1}
    # the custom call, found inside the program that ran it
    assert r["kernels"] == {"jit_step": {"s": pytest.approx(0.015), "n": 1}}
    assert r["ops"]["late"]["s"] == pytest.approx(0.001)
    assert r["modules"]["jit_step"]["s"] == pytest.approx(0.02)
    assert r["modules"]["jit_admit"]["n"] == 1
    assert r["device_ops"][0][0] == "jit_admit/flash"


def test_gaps_are_named_by_the_host():
    r = T.reduce_planes(planes())
    # [35, 55]: its middle in the wait; [0, 15] and [85, 99]: only the
    # window itself covers their middles
    assert r["idle_gaps"] == [["bench.wait", pytest.approx(0.02)],
                              ["(no host event)", pytest.approx(0.015)],
                              ["(no host event)", pytest.approx(0.014)]]


def test_no_window_span_is_an_error():
    p = planes()
    p[0].lines[0].events = p[0].lines[0].events[1:]
    with pytest.raises(ValueError, match="bench.window"):
        T.reduce_planes(p)


def test_recorded_trace():
    """A trace the profiler wrote (on the CPU, so it has host planes and no
    TPU plane): the window span is found and nothing counts as device
    busy time."""
    r = T.reduce_file(str(DATA / "cpu_small.xplane.pb"))
    assert 0 < r["window_s"] < 1
    assert r["busy_s"] == 0 and r["chips_busy"] == 0
    assert r["ops"] == {} and r["idle_gaps"] == []


@pytest.mark.parametrize("renamed", [False, True])
def test_kernel_roofline_is_found_or_the_run_fails(renamed):
    """A roofline reader finds its kernel's custom calls by the program that
    ran them; where the run did the kernel's work and the trace no longer
    names it, the reader fails instead of leaving the metric out."""
    from bench import common

    p = planes()
    if renamed:
        p[1].lines[0].events[0].name = "jit_decode(12)"
    trace = T.reduce_planes(p)
    m = {"num_attention_heads": 16, "num_key_value_heads": 8, "head_dim": 128,
         "num_hidden_layers": 28}
    run = {"trace": trace, "peaks": {"bf16_flops_per_s": 1.97e14,
                                     "hbm_bytes_per_s": 8.19e11},
           "record": {"decode_lengths": [300, 700], "model": m}}
    reader = common.load_module(common.BENCH / "metrics" / "paged_decode_roofline.py")
    if renamed:
        with pytest.raises(common.BenchError, match="paged"):
            reader.read(run)
    else:
        assert 0 < reader.read(run) < 100
