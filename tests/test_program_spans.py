"""The program's own spans, recorded by the profiler on the CPU and reduced
as the benchmark reduces a traced run: the compiled round engine's rounds,
evaluations, shard uploads and merge round, and the paged serving engine's
admissions, steps and evictions."""
import jax
import numpy as np
import pytest

from bench import spans as S
from bench import trace as T
from repro import program_spans
from repro.kernels.decode_attn.ops import pages_per_block
from repro.models import model as M
from repro.serving.engine import ServeEngine
from repro.serving.fl_model import serve_config
from repro.serving.traffic import Request

from test_engine import _make


def _traced(tmp_path, work):
    """Runs ``work`` inside the benchmark's window under the profiler and
    returns the span reduction and the device reduction of its trace, and
    the arguments of each program span in order, by name."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            out = work()
    finally:
        jax.profiler.stop_trace()
    path = str(max(tmp_path.glob("**/*.xplane.pb")))
    events = {}
    for p in jax.profiler.ProfileData.from_file(path).planes:
        for ln in p.lines:
            for e in sorted(ln.events, key=lambda e: e.start_ns):
                if S.PROGRAM_SPAN.match(e.name):
                    events.setdefault(e.name, []).append(dict(e.stats))
    return out, S.reduce_file(path), T.reduce_file(path), events


def _shard_bytes(shards):
    rows = sum(x.nbytes + y.nbytes for x, y in shards)
    return rows + 2 * 4 * len(shards)  # row lengths and offsets, int32


def test_federation_spans(tmp_path):
    rounds, merge_at = 5, 2

    def job():
        sim = _make("engine", rounds=rounds, merge_at=(merge_at,))
        nbytes = _shard_bytes(sim.shards)
        return sim, sim.run(), nbytes

    (sim, hist, nbytes), red, dev, events = _traced(tmp_path, job)
    assert hist[merge_at].merged_groups, "the test's merge round merged nothing"
    assert dev["window_s"] == pytest.approx(red["window_s"])
    sp = red["spans"]
    assert set(sp) == set(program_spans.FED)
    assert sp["fed.eval"]["n"] == rounds
    assert [e["round"] for e in events["fed.eval"]] == list(range(rounds))
    assert sp["fed.eval"]["args"] == {}  # the round is a label
    # segments [0, 2) and [3, 5) around the merge round
    assert sp["fed.segment"]["n"] == 2
    assert sp["fed.segment"]["args"]["rounds"] == rounds - 1
    # at set-up and after the merge; merging moves rows, not their bytes
    up = sp["fed.upload_shards"]
    assert up["n"] == 2 and up["args"]["nbytes"] == 2 * nbytes
    assert up["within"] == {"fed.merge_host": 1}
    for name in ("fed.merge_program", "fed.merge_host"):
        assert sp[name]["n"] == 1 and sp[name]["within"] == {"fed.merge_round": 1}
    host = sp["fed.merge_host"]
    assert host["args"]["groups"] == len(hist[merge_at].merged_groups)
    assert host["args"]["rows_moved"] > 0
    assert host["self_s"] < host["s"]
    assert sp["fed.eval"]["within"] == {"fed.merge_round": 1}
    assert [e["round"] for e in events["fed.merge_round"]] == [merge_at]
    # training rounds time the segment only; the merge round includes its eval
    seg_s = sp["fed.segment"]["s"]
    assert sum(r.wall_s for r in hist if r.round != merge_at) == pytest.approx(
        seg_s, rel=0.05)
    assert hist[merge_at].wall_s <= sp["fed.merge_round"]["s"]


def test_serving_spans(tmp_path):
    cfg = serve_config("qwen3-1.7b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg, num_slots=4, capacity=16,
                      kv_layout="paged", block_size=4)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=10 + i, client_id=0, max_new_tokens=n,
                    prompt=rng.integers(0, cfg.vocab_size, L).astype(np.int32))
            for i, (L, n) in enumerate([(5, 3), (7, 6), (3, 4)])]

    # 50 positions need 13 pages of 4; the three admitted hold 8 of 16
    big = Request(rid=99, client_id=0, max_new_tokens=20,
                  prompt=np.zeros(30, np.int32))

    def serve():
        rows, admitted, evicted = [], [], 0
        for r in reqs:
            assert eng.try_admit(r) is not None
            admitted.append(r.rid)
        assert eng.try_admit(big) is None
        while eng.num_active:
            rows.append(eng.num_active)
            evicted += len(eng.step())
        return rows, admitted, evicted

    (rows, admitted, evicted), red, _dev, events = _traced(tmp_path, serve)
    sp = red["spans"]
    assert set(sp) == set(program_spans.SERVE)
    admit = sp["serve.admit"]
    assert [(e["rid"], e["admitted"]) for e in events["serve.admit"]] == [
        (rid, 1) for rid in admitted] + [(big.rid, 0)]
    assert admit["by"]["admitted"]["1"]["n"] == len(admitted)
    assert admit["args"]["prompt"] == sum(len(r.prompt) for r in reqs + [big])
    step = sp["serve.step"]
    assert step["n"] == len(rows) and step["args"]["rows"] == sum(rows)
    assert [e["rows"] for e in events["serve.step"]] == rows
    assert all(e["bucket"] >= e["rows"] for e in events["serve.step"])
    assert sum(g["n"] for g in step["by"]["bucket"].values()) == len(rows)
    # the table goes up after each admission and eviction, not every step
    assert 1 <= step["args"]["bt_upload"] < len(rows)
    # a request decodes at positions L .. L+n-2 (prefill gave its first
    # token); each step's rows attend ceil((pos+1)/bs) live pages
    bs = eng.block_size
    assert step["args"]["kv_pages"] == sum(
        -(-(pos + 1) // bs)
        for r in reqs
        for pos in range(len(r.prompt), len(r.prompt) + r.max_new_tokens - 1))
    # the kernel's grid: every row of the bucket, every block of the view
    kv = eng.arena[0]["k"]  # (layers, pages, bs, Kv, D)
    assert step["args"]["kv_blocks"] == sum(
        e["bucket"] * -(-e["view"] // pages_per_block(
            bs, kv.shape[3], kv.shape[4], kv.dtype.itemsize, e["view"]))
        for e in events["serve.step"])
    ev = sp["serve.evict"]
    assert ev["args"]["rows"] == evicted == len(reqs)
    assert ev["within"] == {"serve.step": ev["n"]}
