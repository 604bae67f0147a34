"""The program's spans in a trace: counted by name inside the benchmark's
window, their self time less the spans nested in them, the device's idle
time inside them, their counters summed and their group arguments split
by value; the readers of the per-layer metrics built on them; and the
device time of the programs by named scope."""
from pathlib import Path

import pytest

from bench import common
from bench import spans as S
from bench import trace as T
from bench.tests.test_bench_trace import Ev, Line, Plane

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def planes():
    host = Plane("/host:CPU", [
        Line("python", [
            Ev("fed.eval", -10 * MS, 5 * MS, (("round", 3),)),   # before the window
            Ev("bench.window", 0, 100 * MS),
            Ev("fed.merge_round", 10 * MS, 50 * MS, (("round", 4),)),
            Ev("fed.merge_program", 10 * MS, 15 * MS),
            Ev("fed.merge_host", 25 * MS, 20 * MS,
               (("groups", 3), ("rows_moved", 1200))),
            Ev("fed.upload_shards", 30 * MS, 14 * MS, (("nbytes", 1000),)),
            Ev("$federation.py:400 _upload_shards", 30 * MS, 14 * MS),
            Ev("fed.eval", 45 * MS, 15 * MS, (("round", 4),)),
            Ev("fed.eval", 70 * MS, 10 * MS, (("round", 5), ("tag", "x"))),
            Ev("serve.py:88 offer", 62 * MS, 4 * MS),  # not a program span
            Ev("bench.job_setup", 85 * MS, 10 * MS),
            Ev("fed.eval", 100 * MS, 10 * MS, (("round", 6),)),  # after it
        ]),
        Line("other", [Ev("fed.upload_shards", 82 * MS, 2 * MS,
                          (("nbytes", 24),))]),
    ])
    dev = Plane("/device:TPU:0", [
        Line("XLA Ops", [Ev("fusion.1", 12 * MS, 12 * MS),
                         Ev("fusion.2", 46 * MS, 4 * MS),
                         Ev("fusion.3", 72 * MS, 2 * MS)]),
    ])
    return [host, dev]


def test_spans_inside_the_window_by_name():
    r = S.reduce_planes(planes())
    sp = r["spans"]
    assert set(sp) == {"fed.merge_round", "fed.merge_program", "fed.merge_host",
                       "fed.upload_shards", "fed.eval"}
    ev = sp["fed.eval"]
    assert ev["n"] == 2 and ev["s"] == pytest.approx(0.025)
    assert ev["p50_s"] == pytest.approx(0.0125)
    assert ev["p95_s"] == pytest.approx(0.01475)
    # the round is a label, the text argument no number: neither is summed
    assert ev["args"] == {} and ev["by"] == {}


def test_self_time_leaves_out_nested_spans():
    sp = S.reduce_planes(planes())["spans"]
    assert sp["fed.merge_round"]["self_s"] == pytest.approx(0.0)
    assert sp["fed.merge_host"]["self_s"] == pytest.approx(0.006)
    assert sp["fed.upload_shards"]["self_s"] == pytest.approx(0.016)
    assert sp["fed.upload_shards"]["within"] == {"fed.merge_host": 1}
    assert sp["fed.merge_host"]["within"] == {"fed.merge_round": 1}
    assert sp["fed.merge_round"]["within"] == {}


def test_device_idle_inside_spans():
    r = S.reduce_planes(planes())
    sp = r["spans"]
    assert sp["fed.merge_program"]["idle_s"] == pytest.approx(0.003)
    assert sp["fed.eval"]["idle_s"] == pytest.approx(0.011 + 0.008)
    assert sp["fed.merge_round"]["idle_s"] == pytest.approx(0.034)
    # the window: 18 ms busy; [10, 60], [70, 80] and [82, 84] ms are spans
    assert r["idle_s"] == pytest.approx(0.082)
    assert r["idle_outside_spans_s"] == pytest.approx(0.082 - 0.034 - 0.008 - 0.002)


def test_counters_are_summed_across_threads():
    sp = S.reduce_planes(planes())["spans"]
    assert sp["fed.upload_shards"]["n"] == 2
    assert sp["fed.upload_shards"]["args"] == {"nbytes": 1024}
    assert sp["fed.merge_host"]["args"] == {"groups": 3, "rows_moved": 1200}


def test_group_arguments_split_the_spans_by_value():
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 0, 100 * MS),
        Ev("serve.admit", 0, 20 * MS, (("rid", 7), ("prompt", 512),
                                       ("admitted", 1))),
        Ev("serve.admit", 20 * MS, 1 * MS, (("rid", 8), ("prompt", 256),
                                            ("admitted", 0))),
        Ev("serve.admit", 40 * MS, 30 * MS, (("rid", 8), ("prompt", 256),
                                             ("admitted", 1))),
        Ev("serve.step", 70 * MS, 10 * MS, (("rows", 2), ("bucket", 2),
                                            ("view", 64), ("bt_upload", 1))),
        Ev("serve.step", 80 * MS, 12 * MS, (("rows", 3), ("bucket", 4),
                                            ("view", 64), ("bt_upload", 0))),
        Ev("serve.step", 92 * MS, 8 * MS, (("rows", 2), ("bucket", 2),
                                           ("view", 128), ("bt_upload", 0))),
    ])])
    dev = Plane("/device:TPU:0", [Line("XLA Ops", [Ev("fusion.1", 70 * MS,
                                                      20 * MS)])])
    sp = S.reduce_planes([host, dev])["spans"]
    admit, step = sp["serve.admit"], sp["serve.step"]
    assert admit["args"] == {"prompt": 1024}  # the request id is a label
    assert admit["by"]["admitted"]["1"] == {
        "n": 2, "s": pytest.approx(0.05), "p50_s": pytest.approx(0.025),
        "idle_s": pytest.approx(0.05)}
    assert admit["by"]["admitted"]["0"]["n"] == 1
    assert step["args"] == {"rows": 7, "bt_upload": 1}
    assert {k: g["n"] for k, g in step["by"]["bucket"].items()} == {"2": 2, "4": 1}
    assert step["by"]["bucket"]["2"]["p50_s"] == pytest.approx(0.009)
    assert step["by"]["bucket"]["4"]["idle_s"] == pytest.approx(0.002)
    assert step["by"]["view"]["128"]["s"] == pytest.approx(0.008)


def test_idle_outside_spans_is_named_by_the_host():
    r = S.reduce_planes(planes())
    # [0, 10], [60, 70], [80, 82], [84, 100] ms; the longest's middle lies
    # in the job's set-up, the next one's only in the window
    names = r["idle_outside_spans"]
    assert names[0] == ["bench.job_setup", pytest.approx(0.016)]
    assert [n for n, _s in names[1:3]] == ["(no host event)", "serve.py:88 offer"]


def test_no_window_span_is_an_error():
    p = planes()
    p[0].lines[0].events = [e for e in p[0].lines[0].events
                            if e.name != "bench.window"]
    with pytest.raises(ValueError, match="bench.window"):
        S.reduce_planes(p)


def test_recorded_cpu_trace_keeps_the_device_reduction():
    """The device reduction of the committed CPU trace, pinned key by key:
    the program's spans are read beside it, never in place of it."""
    r = T.reduce_file(str(DATA / "cpu_small.xplane.pb"))
    assert r == {"window_s": pytest.approx(0.0008937), "busy_s": 0.0,
                 "chips_busy": 0, "ops": {}, "modules": {}, "kernels": {},
                 "device_ops": [], "idle_gaps": []}
    s = S.reduce_file(str(DATA / "cpu_small.xplane.pb"))
    assert s["spans"] == {} and s["window_s"] == r["window_s"]


# ---- the readers

FED_RECORD = {"jobs": [{"rounds": [{"merge": t == 4} for t in range(10)]},
                       {"rounds": [{"merge": t == 4} for t in range(10)]}]}
SERVE_RECORD = {"rows_per_step": [2, 3, 3], "prompts_in_window": [128, 512]}


def span(n, s, p50=None, idle=0.0, args=None, by=None):
    return {"n": n, "s": s, "self_s": s, "idle_s": idle,
            "p50_s": s / n if p50 is None else p50, "p95_s": s / n,
            "args": args or {}, "within": {}, "by": by or {}}


def group(n, s, p50):
    return {"n": n, "s": s, "p50_s": p50, "idle_s": 0.0}


FED_SPANS = {"fed.eval": span(20, 2.2), "fed.upload_shards":
             span(4, 0.96, args={"nbytes": 4 * 188_400_000}),
             "fed.merge_host": span(2, 0.6), "fed.segment": span(4, 13.0)}
SERVE_SPANS = {"serve.step": span(3, 0.3, p50=0.08, idle=0.006),
               "serve.admit": span(3, 0.061, p50=0.02, by={"admitted": {
                   "1": group(2, 0.06, 0.025), "0": group(1, 0.001, 0.001)}})}

READERS = [  # metric, record, span it reads, value
    ("fed.eval_ms", FED_RECORD, FED_SPANS, "fed.eval", 110.0),
    ("fed.upload_ms", FED_RECORD, FED_SPANS, "fed.upload_shards", 240.0),
    ("fed.upload_mb_per_round", FED_RECORD, FED_SPANS, "fed.upload_shards", 37.68),
    ("fed.merge_host_ms", FED_RECORD, FED_SPANS, "fed.merge_host", 300.0),
    ("serve.step_ms", SERVE_RECORD, SERVE_SPANS, "serve.step", 80.0),
    ("serve.admit_ms", SERVE_RECORD, SERVE_SPANS, "serve.admit", 25.0),
    ("serve.step_idle_share", SERVE_RECORD, SERVE_SPANS, "serve.step", 2.0),
]


def reader(name):
    return common.load_module(common.BENCH / "metrics" / (name + ".py"))


def run_with(record, spans):
    return {"trace": {"window_s": 36.0, "spans": spans}, "record": record,
            "cell": {"name": "c"}}


@pytest.mark.parametrize("metric,record,spans,name,value", READERS,
                         ids=[r[0] for r in READERS])
def test_reader(metric, record, spans, name, value):
    assert reader(metric).read(run_with(record, spans)) == pytest.approx(value)


@pytest.mark.parametrize("metric,record,spans,name,value", READERS,
                         ids=[r[0] for r in READERS])
def test_reader_fails_when_its_span_is_gone(metric, record, spans, name, value):
    """A program that lists its spans, traced doing the work, without this
    one (renamed, or its annotation gone, with or without the rest): the
    reader fails instead of leaving its metric out."""
    rest = {k: v for k, v in spans.items() if k != name}
    for held in (rest, {}):
        with pytest.raises(common.BenchError, match=name):
            reader(metric).read(run_with(record, held))


def test_a_program_from_before_its_spans_lists_none(tmp_path):
    """``declared`` on a checkout whose ``repro`` namespace package has no
    ``program_spans`` (as before the spans) gives nothing, and on this
    one the spans its code opens."""
    import os
    import subprocess
    import sys

    (tmp_path / "repro").mkdir()
    (tmp_path / "repro" / "sharding.py").write_text("")
    code = ("import sys; sys.path[:0] = sys.argv[1:]; "
            "from bench import spans; print(len(spans.declared()))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    root = str(common.ROOT)
    for src, n in ((tmp_path, 0), (common.ROOT / "src", len(S.declared()))):
        out = subprocess.run([sys.executable, "-c", code, root, str(src)],
                             env=env, capture_output=True, text=True, check=True)
        assert int(out.stdout) == n
    assert len(S.declared()) == 9


def test_admission_reader_fails_without_its_outcome():
    """Admissions in the window, but no serve.admit span says one took a
    slot: the outcome counter is gone, so the reader fails."""
    refused = {"serve.admit": span(2, 0.002, by={"admitted": {
        "0": group(2, 0.002, 0.001)}})}
    with pytest.raises(common.BenchError, match="admitted=1"):
        reader("serve.admit_ms").read(run_with(SERVE_RECORD, refused))


@pytest.mark.parametrize("metric,record,spans,name,value", READERS,
                         ids=[r[0] for r in READERS])
def test_reader_is_silent_without_spans_or_work(metric, record, spans, name,
                                                value, monkeypatch):
    """None for an untraced run, for a run that did none of the span's
    work, and for a program that lists no spans (one from before them)."""
    r = reader(metric)
    assert r.read({"trace": None, "record": record}) is None
    idle = {"jobs": []} if "jobs" in record else {"rows_per_step": [],
                                                  "prompts_in_window": []}
    assert r.read(run_with(idle, spans)) is None
    assert name in S.declared()
    monkeypatch.setattr(S, "declared", lambda: ())
    assert r.read(run_with(record, {})) is None


def test_a_run_reads_its_own_trace_file(tmp_path, monkeypatch):
    """Without spans in the reduced trace, the reader reduces the trace
    file the run wrote for its cell, and refuses one of another window."""
    import jax

    monkeypatch.setattr(S, "TRACE_DIR", tmp_path)
    jax.profiler.start_trace(str(tmp_path / "cell"))
    with jax.profiler.TraceAnnotation("bench.window"):
        for t in range(3):
            with jax.profiler.TraceAnnotation("fed.eval", round=t):
                jax.numpy.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    files = sorted(tmp_path.glob("cell/**/*.xplane.pb"))
    window = T.reduce_file(str(files[-1]))["window_s"]
    run = {"trace": {"window_s": window}, "record": FED_RECORD,
           "cell": {"name": "cell"}}
    assert S.of(run)["spans"]["fed.eval"]["n"] == 3
    assert reader("fed.eval_ms").read(run) > 0
    run["trace"]["window_s"] = window + 1.0
    with pytest.raises(common.BenchError, match="not the trace of this run"):
        S.of(run)


def test_recorded_tpu_trace():
    """The small trace ``make_tpu_small.py`` recorded on a TPU v5e: a
    federation job of 3 rounds with its merge at round 1, then a paged
    engine serving four requests. The program's spans carry their
    counters, the device ran inside the program spans, and the kernels
    are found by name in the programs their roofline readers search."""
    from bench.readers import kernel_seconds

    path = str(DATA / "tpu_small.xplane.pb")
    red, dev = S.reduce_file(path), T.reduce_file(path)
    assert dev["chips_busy"] == 1 and red["window_s"] == dev["window_s"]
    sp = red["spans"]
    assert {k: v["n"] for k, v in sp.items()} == {
        "fed.upload_shards": 2, "fed.segment": 2, "fed.eval": 3,
        "fed.merge_round": 1, "fed.merge_program": 1, "fed.merge_host": 1,
        "serve.admit": 4, "serve.step": 11, "serve.evict": 4}
    # 1,169 rows of 28 x 28 f32 images and int32 labels, and 10 clients'
    # int32 row lengths and offsets, at set-up and after the merge
    assert sp["fed.upload_shards"]["args"] == {"nbytes": 2 * (1169 * 3140 + 80)}
    assert sp["fed.upload_shards"]["within"] == {"fed.merge_host": 1}
    assert sp["fed.merge_host"]["args"] == {"groups": 3, "rows_moved": 748}
    assert sp["fed.segment"]["args"] == {"rounds": 2}
    assert sp["serve.admit"]["args"] == {"prompt": 128 + 64 + 128 + 64}
    assert sp["serve.step"]["args"]["rows"] == 26
    # rows 4, 4, 4, 3, 3 at bucket 4, then 2, 2, 1, 1, 1, 1 at bucket 2;
    # depth 256 (16 pages) while a prompt of 128 decodes, then 128
    by = sp["serve.step"]["by"]
    assert {k: g["n"] for k, g in by["bucket"].items()} == {"2": 6, "4": 5}
    assert {k: g["n"] for k, g in by["view"].items()} == {"16": 7, "8": 4}
    assert sp["serve.evict"]["args"] == {"rows": 4}
    for name in ("fed.segment", "fed.merge_program", "serve.step"):
        assert sp[name]["idle_s"] < sp[name]["s"]
    assert sp["fed.upload_shards"]["idle_s"] == pytest.approx(
        sp["fed.upload_shards"]["s"])
    names = {T._short_op(k).rsplit(".", 1)[0] for k in dev["ops"]}
    assert {"pearson_gram", "paged_decode_attn", "flash_prefill"} <= names
    for program, kernel in (("merge_device", "pearson"), ("jit_step", "paged"),
                            ("jit_admit", "flash_prefill")):
        assert kernel_seconds(dev, program, kernel)[0] > 0


def _msg(*fields):
    """A protobuf message of (field number, int | bytes | message) fields."""
    def varint(n):
        out = b""
        while n >= 0x80:
            out, n = out + bytes([n & 0x7F | 0x80]), n >> 7
        return out + bytes([n])
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def test_op_names_from_an_hlo_proto():
    from bench import scopes as SC

    ins = [_msg((1, "fusion.3"), (2, "fusion"), (5, 7),
                (7, _msg((1, "dot"), (2, "jit(step)/layers/mixer/dot_general")))),
           _msg((1, "copy.1"), (2, "copy"))]
    proto = _msg((1, _msg((1, "jit_step"), (3, _msg((1, "main"),
                                                     *[(2, i) for i in ins])))))
    assert SC._op_names(proto) == {
        "fusion.3": "jit(step)/layers/mixer/dot_general", "copy.1": ""}
    space = _msg((1, _msg((2, "/host:CPU"))), (1, _msg(
        (2, "/host:metadata"),
        (4, _msg((1, 5), (2, _msg((2, "jit_step(42)"), (5, _msg((6, proto))))))),
        (4, _msg((1, 6), (2, _msg((2, "jit_other(1)"), (5, _msg((6, proto))))))))))
    assert SC.hlo_op_names(space) == {"jit_step(42)": SC._op_names(proto)}
    # the innermost of the program's scopes names the operation
    assert SC._scope("jit(step)/layers/mixer/dot_general",
                     SC.SCOPES["jit_step"]) == "mixer"
    assert SC._scope("jit(step)/copy", SC.SCOPES["jit_step"]) == SC.NONE


def test_recorded_tpu_trace_by_named_scope():
    """The small TPU trace keeps the HLO protos of the merge program and
    the decode step, so each of their device operations is named by the
    scope it ran in."""
    from bench import scopes as SC

    r = SC.reduce_file(str(DATA / "tpu_small.xplane.pb"))
    assert r["unnamed_ops"] == 0
    merge, step = r["programs"]["jit_merge_device"], r["programs"]["jit_step"]
    for scopes in (merge, step):
        parts = sum(v for k, v in scopes.items() if k != SC.ALL)
        assert parts == pytest.approx(scopes[SC.ALL], rel=1e-6)
    assert {"train", "similarity", "plan", "mix"} <= set(merge)
    assert merge["train"] > 0.8 * merge[SC.ALL]
    assert {"mixer", "ffn", "head", "layers"} <= set(step)
    # the whole decode step, its kernel included, is the trace's jit_step
    dev = T.reduce_file(str(DATA / "tpu_small.xplane.pb"))
    assert step[SC.ALL] <= dev["modules"]["jit_step"]["s"] + 1e-9
