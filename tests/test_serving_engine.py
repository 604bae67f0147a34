"""Serving subsystem tests: continuous-batching parity against the
sequential generate oracle, slot lifecycle, routing, traffic, the
merge-round hot-swap contract, and the federation -> serving checkpoint
bridge (on_merge hook, both pipelines)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_pytree, save_pytree
from repro.launch.experiment import ExperimentSpec, build_simulator
from repro.launch.serve import generate
from repro.models import init_params
from repro.serving import (
    GLOBAL,
    ClusterRouter,
    MergeCheckpoint,
    ReplicaSet,
    Request,
    ServeEngine,
    diurnal_requests,
    load_model,
    poisson_requests,
    swap_replicas,
)
from repro.serving.fl_model import serve_config

CAP = 32


@pytest.fixture(scope="module")
def cfg():
    return serve_config()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(jax.random.PRNGKey(0), cfg)


def _prompts(cfg, lens=(4, 8, 4, 8), seed=0):
    r = np.random.default_rng(seed)
    return [r.integers(0, cfg.vocab_size, size=L).astype(np.int32)
            for L in lens]


def _oracle(params, cfg, prompt, max_new):
    toks, _ = generate(params, cfg, {"tokens": np.asarray(prompt)[None]},
                       max_new_tokens=max_new, capacity=CAP)
    return np.asarray(toks)[0].tolist()


# ---------------------------------------------------------------------------
# continuous batching parity vs the sequential oracle
# ---------------------------------------------------------------------------

def test_batched_parity_vs_generate(params, cfg):
    """Simultaneous admission of mixed prompt lengths: every slot's tokens
    equal the batch-1 generate oracle, token for token."""
    prompts = _prompts(cfg)
    oracle = [_oracle(params, cfg, p, 6) for p in prompts]
    eng = ServeEngine(params, cfg, num_slots=4, capacity=CAP)
    actives = [
        eng.try_admit(Request(rid=i, client_id=0, prompt=p,
                              max_new_tokens=6))
        for i, p in enumerate(prompts)
    ]
    eng.run_to_completion()
    assert [a.tokens for a in actives] == oracle


def test_staggered_admission_parity(params, cfg):
    """A request admitted while others are mid-decode still matches the
    oracle — per-slot positions/lengths are exact, not shared."""
    prompts = _prompts(cfg)
    oracle = [_oracle(params, cfg, p, 6) for p in prompts]
    eng = ServeEngine(params, cfg, num_slots=4, capacity=CAP)
    a0 = eng.try_admit(Request(rid=0, client_id=0, prompt=prompts[0],
                               max_new_tokens=6))
    eng.step()
    eng.step()
    a1 = eng.try_admit(Request(rid=1, client_id=0, prompt=prompts[1],
                               max_new_tokens=6))
    eng.run_to_completion()
    assert a0.tokens == oracle[0]
    assert a1.tokens == oracle[1]


def test_slot_eviction_and_reuse(params, cfg):
    """A full engine rejects admission; an evicted slot's state is fully
    overwritten on re-admit (parity for the reusing request)."""
    prompts = _prompts(cfg)
    eng = ServeEngine(params, cfg, num_slots=2, capacity=CAP)
    eng.try_admit(Request(rid=0, client_id=0, prompt=prompts[0],
                          max_new_tokens=4))
    eng.try_admit(Request(rid=1, client_id=0, prompt=prompts[1],
                          max_new_tokens=4))
    assert eng.try_admit(Request(rid=2, client_id=0, prompt=prompts[2],
                                 max_new_tokens=4)) is None
    eng.run_to_completion()
    assert eng.num_active == 0
    c = eng.try_admit(Request(rid=2, client_id=0, prompt=prompts[2],
                              max_new_tokens=4))
    eng.run_to_completion()
    assert c.tokens == _oracle(params, cfg, prompts[2], 4)


def test_eos_and_single_token_finish(params, cfg):
    prompts = _prompts(cfg)
    first = _oracle(params, cfg, prompts[0], 1)[0]
    eng = ServeEngine(params, cfg, num_slots=2, capacity=CAP)
    # eos == the first generated token: finished at admission, no slot held
    a = eng.try_admit(Request(rid=0, client_id=0, prompt=prompts[0],
                              max_new_tokens=8, eos_id=first))
    assert a.done and a.tokens == [first] and eng.num_active == 0
    b = eng.try_admit(Request(rid=1, client_id=0, prompt=prompts[0],
                              max_new_tokens=1))
    assert b.done and b.tokens == [first] and eng.num_active == 0


def test_admission_capacity_guard(params, cfg):
    """An over-capacity request is rejected gracefully (marked done, no
    slot touched, counted) — the driver loop and later admissions
    proceed."""
    eng = ServeEngine(params, cfg, num_slots=1, capacity=8)
    a = eng.try_admit(Request(rid=0, client_id=0,
                              prompt=np.zeros(6, np.int32),
                              max_new_tokens=4))
    assert a.rejected and a.done and a.tokens == []
    assert eng.rejects == 1 and eng.num_active == 0
    # the engine still serves fitting requests afterwards
    b = eng.try_admit(Request(rid=1, client_id=0,
                              prompt=np.zeros(4, np.int32),
                              max_new_tokens=2))
    assert not b.rejected
    eng.run_to_completion()
    assert len(b.tokens) == 2


def test_replica_set_counts_rejects(params, cfg):
    """A poison request in a routed queue is counted and drained, and the
    requests behind it still complete."""
    router = ClusterRouter(2)
    rs = ReplicaSet(
        {GLOBAL: ServeEngine(params, cfg, num_slots=2, capacity=CAP)},
        router,
    )
    p = _prompts(cfg)[0]
    rs.submit(Request(rid=0, client_id=0, prompt=p, max_new_tokens=2))
    rs.submit(Request(rid=1, client_id=0, prompt=np.zeros(4, np.int32),
                      max_new_tokens=2 * CAP + 1))  # past the pool
    rs.submit(Request(rid=2, client_id=0, prompt=p, max_new_tokens=2))
    while not rs.idle:
        rs.tick()
    assert [a.request.rid for _, a in rs.rejected] == [1]
    assert sorted(a.request.rid for _, a in rs.finished) == [0, 2]


# ---------------------------------------------------------------------------
# router / traffic
# ---------------------------------------------------------------------------

def test_router_composes_across_merge_rounds():
    r = ClusterRouter(8)
    assert r.replica_for(5) == GLOBAL
    r.update([(0, 1, 2), (3, 4)])
    assert r.replica_for(1) == 0 and r.replica_for(4) == 3
    assert r.replica_for(7) == GLOBAL
    # rep 3 itself merges into rep 0: its clients must follow
    r.update([(0, 3)])
    assert r.replica_for(4) == 0 and r.replica_for(3) == 0
    assert r.replica_ids() == [0]


def test_replica_set_routes_and_falls_back(params, cfg):
    router = ClusterRouter(4)
    router.update([(0, 1)])
    eng = ServeEngine(params, cfg, num_slots=2, capacity=CAP)
    geng = ServeEngine(params, cfg, num_slots=2, capacity=CAP)
    rs = ReplicaSet({GLOBAL: geng, 0: eng}, router)
    p = _prompts(cfg)[0]
    assert rs.submit(Request(rid=0, client_id=1, prompt=p,
                             max_new_tokens=2)) == 0
    assert rs.submit(Request(rid=1, client_id=3, prompt=p,
                             max_new_tokens=2)) == GLOBAL
    # a routed-to cluster with no live engine falls back to GLOBAL
    router.update([(2, 3)])
    assert rs.submit(Request(rid=2, client_id=3, prompt=p,
                             max_new_tokens=2)) == GLOBAL
    while not rs.idle:
        rs.tick()
    assert len(rs.finished) == 3


def test_traffic_deterministic_and_bucketed():
    a = poisson_requests(16, 50.0, num_clients=8, vocab_size=64, seed=3)
    b = poisson_requests(16, 50.0, num_clients=8, vocab_size=64, seed=3)
    assert [r.arrival for r in a] == [r.arrival for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    from repro.serving.traffic import LEN_BUCKETS
    assert {len(r.prompt) for r in a} <= set(LEN_BUCKETS)
    assert all(a[i].arrival <= a[i + 1].arrival for i in range(len(a) - 1))
    d = diurnal_requests(16, 20.0, peak_factor=3.0, period_s=1.0,
                         num_clients=8, vocab_size=64, seed=3)
    assert len(d) == 16
    assert all(d[i].arrival <= d[i + 1].arrival for i in range(len(d) - 1))


# ---------------------------------------------------------------------------
# hot swap
# ---------------------------------------------------------------------------

def test_hot_swap_parity_and_inflight_survival(params, cfg, tmp_path):
    """The hot-swap contract: (1) requests in flight at the swap keep
    their slots and complete; (2) a request admitted after the swap is
    token-identical to a fresh engine restarted from the checkpoint."""
    p_new = init_params(jax.random.PRNGKey(123), cfg)
    path = str(tmp_path / "merged.npz")
    save_pytree(path, p_new, step=1)
    prompts = _prompts(cfg)

    eng = ServeEngine(params, cfg, num_slots=2, capacity=CAP)
    survivor = eng.try_admit(Request(rid=0, client_id=0, prompt=prompts[0],
                                     max_new_tokens=10))
    eng.step()
    eng.step()
    stall = eng.swap_params(load_model(path, p_new))
    assert stall >= 0.0 and eng.swaps == 1
    fresh = eng.try_admit(Request(rid=1, client_id=0, prompt=prompts[1],
                                  max_new_tokens=6))
    eng.run_to_completion()
    # (1) the in-flight request survived the swap and ran to its budget
    assert len(survivor.tokens) == 10
    # (2) restart-from-checkpoint parity for the post-swap admission
    restarted = ServeEngine(load_model(path, p_new), cfg, num_slots=2,
                            capacity=CAP)
    ref = restarted.try_admit(Request(rid=9, client_id=0, prompt=prompts[1],
                                      max_new_tokens=6))
    restarted.run_to_completion()
    assert fresh.tokens == ref.tokens
    assert fresh.tokens == _oracle(p_new, cfg, prompts[1], 6)


def test_swap_replicas_reassigns_missing_reps(params, cfg, tmp_path):
    p_new = init_params(jax.random.PRNGKey(7), cfg)
    gpath = str(tmp_path / "g.npz")
    rpath = str(tmp_path / "r0.npz")
    save_pytree(gpath, p_new)
    save_pytree(rpath, p_new)
    router = ClusterRouter(6)
    router.update([(0, 1), (2, 3)])
    rs = ReplicaSet(
        {GLOBAL: ServeEngine(params, cfg, 2, CAP),
         0: ServeEngine(params, cfg, 2, CAP),
         2: ServeEngine(params, cfg, 2, CAP)},
        router,
    )
    ckpt = MergeCheckpoint(round=2, rep_paths={0: rpath},
                           global_path=gpath, groups=((0, 2),))
    report = swap_replicas(rs, ckpt, params)
    # rep 2 was merged away: it now serves the global model, and its
    # clients route to rep 0
    assert report.reassigned_to_global == [2]
    assert router.replica_for(3) == 0
    assert set(report.stall_s) == {GLOBAL, 0, 2}


# ---------------------------------------------------------------------------
# federation -> serving bridge (on_merge hook + checkpoints)
# ---------------------------------------------------------------------------

def _fl_spec(pipeline, **kw):
    base = dict(
        model="linear", dataset="blobs", n_train=6 * 120, n_test=200,
        data_kwargs={"num_classes": 4, "dim": 8},
        partition="class_pairs", partition_kwargs={"n_per": 120},
        num_clients=6, lr_local=0.1, rounds=3, merge_at=(1,),
        threshold=-1.0, local_epochs=1, steps_per_epoch=2, batch_size=16,
        pipeline=pipeline, seed=0, alpha="data",
    )
    base.update(kw)
    return ExperimentSpec(**base)


def _run_with_hook(pipeline, **kw):
    sim = build_simulator(_fl_spec(pipeline, **kw))
    events = []

    def hook(t, plan, models, global_params):
        events.append((
            t, plan.groups,
            {k: jax.tree_util.tree_map(lambda a: np.asarray(a).copy(), v)
             for k, v in models.items()},
        ))

    sim.on_merge = hook
    sim.run()
    return events


def test_on_merge_hook_pipeline_parity():
    """The hook fires once per group-forming merge round on BOTH pipelines
    and yields the same groups and (to fp tolerance) the same intermediary
    models — the data-alpha mix uses pre-merge weights in each."""
    ev_d = _run_with_hook("device")
    ev_e = _run_with_hook("engine")
    assert len(ev_d) == 1 and len(ev_e) == 1
    (td, gd, md), (te, ge, me) = ev_d[0], ev_e[0]
    assert (td, gd) == (te, ge) and sorted(md) == sorted(me)
    assert sorted(md) == [int(g[0]) for g in gd]
    for k in md:
        for a, b in zip(jax.tree_util.tree_leaves(md[k]),
                        jax.tree_util.tree_leaves(me[k])):
            np.testing.assert_allclose(a, b, atol=1e-4)


def test_on_merge_hook_not_fired_without_groups():
    # threshold 1.1 is unreachable: no groups, no hook
    assert _run_with_hook("device", threshold=1.1) == []
    assert _run_with_hook("engine", threshold=1.1) == []


def test_on_merge_hook_blocked_engine_rejected():
    sim = build_simulator(_fl_spec(
        "engine", num_clients=8, n_train=8 * 120,
        merge_policy="pearson-blocked", block_size=4, threshold=0.3,
    ))
    sim.on_merge = lambda *a: None
    with pytest.raises(ValueError, match="blocked"):
        sim.run()


def test_merged_model_checkpoint_roundtrip_bf16(tmp_path):
    """The serving bridge artifact: an intermediary model cast to bf16
    round-trips bit-exactly through the atomic checkpoint (bf16 leaves go
    through the uint16 view path)."""
    events = _run_with_hook("device")
    _t, groups, models = events[0]
    rep = int(groups[0][0])
    model_bf16 = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.bfloat16), models[rep]
    )
    path = str(tmp_path / "intermediary.npz")
    save_pytree(path, model_bf16, step=1)
    loaded, step = load_pytree(path, model_bf16)
    assert step == 1
    for a, b in zip(jax.tree_util.tree_leaves(model_bf16),
                    jax.tree_util.tree_leaves(loaded)):
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        )


@pytest.mark.slow
def test_serving_pipeline_smoke(tmp_path):
    """The full federation -> serve -> swap pipeline (the CI leg runs this
    via benchmarks.serving_bench --smoke)."""
    from repro.launch.serve_fl import run_serving_pipeline
    report = run_serving_pipeline(smoke=True,
                                  ckpt_dir=str(tmp_path / "ckpts"))
    assert report["continuous"]["swap"]["inflight_survived"] == \
        report["continuous"]["swap"]["inflight_before"]
    assert report["saturated"]["tokens_per_s"] > 0
    assert len(report["federation"]["merge_rounds"]) >= 2
