"""Compiled round engine (core/engine.py) tests: trajectory parity with
the per-round device pipeline across every registered scenario, the
on-device merge planner vs the host greedy grouping (property test),
segmentation invariance, the mesh-aware scan, and the Pearson backend
auto-selection satellite."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core import AlgoConfig, FederatedSimulator, FLConfig
from repro.core.merging import (
    build_merge_plan,
    device_merge_plan,
    groups_from_assignment,
    plan_from_groups,
)
from repro.core.pearson import pearson_tree
from repro.core.scenarios import build_scenario, round_tables

from test_federation import _acc, _blobs, _init, _loss, _shards, NUM_CLIENTS


def _make(pipeline, scenario="normal", rounds=6, merge_at=(2,), seed=0,
          threshold=0.3, mesh=None, scenario_kw=None, **fl_kw):
    x_te, y_te = _blobs(500, seed + 99)
    fl = FLConfig(
        algo=AlgoConfig(algorithm="scaffold", lr_local=0.1),
        num_rounds=rounds, local_epochs=2, steps_per_epoch=5, batch_size=16,
        merge_at=merge_at, threshold=threshold, pipeline=pipeline, seed=seed,
        **fl_kw,
    )
    sc = build_scenario(scenario, NUM_CLIENTS, seed, **(scenario_kw or {}))
    return FederatedSimulator(
        init_params_fn=_init, loss_fn=_loss,
        eval_fn=lambda p: _acc(p, x_te, y_te),
        client_shards=_shards(seed), fl=fl, scenario=sc, mesh=mesh,
    )


def _assert_history_parity(dev, eng, atol=0.0):
    """Engine must reproduce the device pipeline's RoundRecord history:
    all integer accounting and merge groups exactly; accuracy/mean_loss
    exactly, except where a documented tolerance applies (``atol`` > 0 for
    network-delay scenarios: the engine accumulates stale arrivals in f32
    on device where the oracle applies them sequentially in f64)."""
    assert len(dev) == len(eng)
    for d, e in zip(dev, eng):
        assert d.round == e.round
        assert d.active_nodes == e.active_nodes
        assert d.updates_sent == e.updates_sent
        assert d.bytes_sent == e.bytes_sent
        assert d.active_nodes_end == e.active_nodes_end
        assert d.merged_groups == e.merged_groups
    acc_d = np.asarray([r.accuracy for r in dev])
    acc_e = np.asarray([r.accuracy for r in eng])
    ml_d = np.asarray([r.mean_loss for r in dev])
    ml_e = np.asarray([r.mean_loss for r in eng])
    if atol == 0.0:
        np.testing.assert_array_equal(acc_d, acc_e)
        np.testing.assert_array_equal(ml_d, ml_e)
    else:
        np.testing.assert_allclose(acc_d, acc_e, atol=atol)
        np.testing.assert_allclose(ml_d, ml_e, atol=atol)


# ---------------------------------------------------------------------------
# engine vs device-pipeline trajectory parity, all registered scenarios
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "scenario,atol",
    [
        ("normal", 0.0),
        ("packet_loss", 0.0),
        ("drop", 0.0),
        # documented tolerance: f32 device ring buffer vs f64 host queue
        ("network_delay", 1e-6),
        ("poisoning", 0.0),
        ("adverse", 0.0),
    ],
)
def test_engine_matches_device_pipeline(scenario, atol):
    dev = _make("device", scenario).run()
    eng = _make("engine", scenario).run()
    assert any(r.merged_groups for r in dev)  # the run actually merged
    _assert_history_parity(dev, eng, atol=atol)


def test_engine_host_plan_fallback_policies():
    """Policies without a device similarity program (cosine) or with
    custom planning (random-pairs, none) go through the host-planned merge
    boundary; trajectories still match the device pipeline exactly."""
    for policy, thr in (("cosine", 0.9), ("random-pairs", 0.3), ("none", 0.3)):
        dev = _make("device", merge_policy=policy, threshold=thr).run()
        eng = _make("engine", merge_policy=policy, threshold=thr).run()
        _assert_history_parity(dev, eng)


def test_engine_segmentation_invariance():
    """Chopping the scan into shorter segments must not change anything:
    segment boundaries are an execution detail, not semantics."""
    ref = _make("engine", merge_at=(2, 4)).run()
    short = _make("engine", merge_at=(2, 4), engine_max_segment=1).run()
    _assert_history_parity(ref, short)


@pytest.mark.parametrize("pipeline", ["engine", "device"])
def test_resume_matches_full_run(pipeline):
    """Running [0, 2), the merge round, then [3, 6) gives the full run's
    history: each run() call resumes from the simulator's state."""
    ref = _make(pipeline).run()
    sim = _make(pipeline)
    for t0, t1 in ((0, 2), (2, 3), (3, 6)):
        sim.run(start=t0, stop=t1)
    _assert_history_parity(ref, sim.history)


@pytest.mark.parametrize("ref_pipeline,pods", [("engine", 1), ("device", None)])
def test_engine_resume_from_loaded_state(ref_pipeline, pods):
    """Each engine round started from another run's state (load_state
    places it in the engine's layout) reproduces that run's round: an
    unmeshed run followed on a pods=1 mesh, and the per-round device
    pipeline followed unmeshed."""
    from repro.launch.mesh import make_fl_mesh

    ref = _make(ref_pipeline)
    follower = _make("engine", mesh=pods and make_fl_mesh(pods=pods))
    for t in range(ref.fl.num_rounds):
        follower.load_state(*jax.device_get(
            (ref.params, ref.c_global, ref.c_locals)))
        follower.run(start=t, stop=t + 1)
        ref.run(start=t, stop=t + 1)
    assert any(r.merged_groups for r in ref.history)
    _assert_history_parity(ref.history, follower.history)


def test_engine_resume_refused_with_delays():
    from repro.core.engine import RoundEngine

    eng = RoundEngine(_make("engine", "network_delay"))
    with pytest.raises(ValueError, match="delayed"):
        eng.run(start=1)


def test_engine_merge_edge_schedules():
    """Merge at round 0 and back-to-back merge rounds exercise the
    boundary logic (zero-length segments between merges)."""
    for merge_at in ((0,), (2, 3)):
        dev = _make("device", merge_at=merge_at).run()
        eng = _make("engine", merge_at=merge_at).run()
        _assert_history_parity(dev, eng)


def test_engine_mesh_mode_matches_default_device():
    """Pod-sharded engine (pods=1 mesh in-process; pods=2 runs in the slow
    subprocess suite) reproduces the unmeshed device pipeline."""
    from repro.launch.mesh import make_fl_mesh

    dev = _make("device").run()
    eng = _make("engine", mesh=make_fl_mesh(pods=1)).run()
    _assert_history_parity(dev, eng)


def test_engine_partial_participation_parity():
    """Partial participation through the pre-drawn uniform table: the
    engine composes each round's participant subset on host (active is
    constant within a segment) with the same selection rule the per-round
    loop uses — histories match exactly, including post-merge rounds
    where the active set the rule draws from has shrunk."""
    dev = _make("device", participation=0.5).run()
    eng = _make("engine", participation=0.5).run()
    assert any(r.updates_sent < r.active_nodes for r in dev)
    _assert_history_parity(dev, eng)


def test_engine_stale_ring_converges():
    """Network delay through the fixed-capacity device ring buffer: the
    run converges and delayed rounds show reduced senders."""
    hist = _make("engine", "network_delay", rounds=8).run()
    assert any(r.updates_sent < NUM_CLIENTS for r in hist)
    assert hist[-1].accuracy > 0.8


# ---------------------------------------------------------------------------
# on-device merge planner vs host greedy grouping (property test)
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(
    k=st.integers(2, 12),
    thr_pct=st.integers(-50, 95),
    group_size=st.integers(2, 4),
    active_seed=st.integers(0, 10_000),
    sym=st.integers(0, 1),
    data_alpha=st.integers(0, 1),
)
def test_device_planner_matches_host_greedy(k, thr_pct, group_size,
                                            active_seed, sym, data_alpha):
    """device_merge_plan replicates merge_clients + plan_from_groups:
    same groups, same active mask, same merge matrix — on arbitrary
    (including asymmetric) similarity matrices, partial active masks and
    both alpha modes."""
    rng = np.random.default_rng(active_seed)
    corr = rng.uniform(-1, 1, (k, k)).astype(np.float32)
    if sym:
        corr = ((corr + corr.T) / 2).astype(np.float32)
    np.fill_diagonal(corr, 1.0)
    thr = float(np.float32(thr_pct / 100.0))
    # keep entries off the threshold: the host compares f32 >= f64, the
    # device f32 >= f32 — a knife-edge value is ambiguous by construction
    corr = np.where(np.abs(corr - thr) < 1e-5, thr + 1e-3, corr)
    corr = corr.astype(np.float32)
    active = (rng.random(k) > 0.25).astype(np.float32)
    sizes = rng.integers(1, 100, k)
    alpha = "data" if data_alpha else "uniform"

    host = build_merge_plan(corr, sizes, thr, group_size,
                            active.astype(bool), alpha)
    W, A, act = device_merge_plan(
        jnp.asarray(corr), jnp.asarray(active),
        jnp.asarray(sizes, jnp.float32),
        threshold=thr, max_group_size=group_size, alpha=alpha,
    )
    groups, unmerged = groups_from_assignment(np.asarray(A), np.asarray(act))
    dev = plan_from_groups(k, groups, unmerged, sizes, alpha)
    assert dev.groups == host.groups
    assert dev.unmerged == host.unmerged
    np.testing.assert_array_equal(dev.active, host.active)
    np.testing.assert_allclose(np.asarray(W), host.W, atol=1e-6)


# ---------------------------------------------------------------------------
# pre-drawn scenario tables
# ---------------------------------------------------------------------------


def test_round_tables_match_simulator_schedules():
    """The stacked (T, K) tables reproduce _round_masks round by round."""
    sim = _make("device", "adverse", rounds=5)
    tb = round_tables(sim.scenario, sim.K, 5, sim.fl.steps_per_epoch,
                      sim.fl.local_steps)
    for t in range(5):
        steps_mask, round_mask, poison = sim._round_masks(t)
        np.testing.assert_array_equal(tb.steps_mask[t], steps_mask)
        np.testing.assert_array_equal(tb.round_mask[t], round_mask)
        np.testing.assert_array_equal(tb.poison, poison)


# ---------------------------------------------------------------------------
# satellite: Pearson backend auto-selection + deprecated flag
# ---------------------------------------------------------------------------


def test_pearson_backend_auto_selects_by_platform():
    fl = FLConfig(num_rounds=1)
    # CI/test platform is CPU: auto resolves to the jnp accumulation
    assert fl.pearson_backend == "auto"
    assert fl.pearson_kernel == (jax.default_backend() in ("tpu", "gpu"))
    assert FLConfig(num_rounds=1, pearson_backend="pallas").pearson_kernel
    assert not FLConfig(num_rounds=1, pearson_backend="jnp").pearson_kernel


def test_use_kernel_pearson_deprecated_alias():
    # the deprecated flag still works on its own (kept verbatim)
    fl = FLConfig(num_rounds=1, use_kernel_pearson=True)
    assert fl.pearson_kernel and fl.use_kernel_pearson is True
    assert not FLConfig(num_rounds=1, use_kernel_pearson=False).pearson_kernel
    # agreement with an explicit backend is fine
    assert FLConfig(num_rounds=1, use_kernel_pearson=True,
                    pearson_backend="pallas").pearson_kernel
    with pytest.raises(ValueError, match="conflicting Pearson backend"):
        FLConfig(num_rounds=1, use_kernel_pearson=True, pearson_backend="jnp")
    with pytest.raises(ValueError, match="pearson_backend"):
        FLConfig(num_rounds=1, pearson_backend="cuda-graphs")


def test_pearson_fused_scan_matches_loop():
    """The single-lax.scan packed-chunk accumulation agrees with the
    per-leaf loop (different accumulation order: f32 rounding tolerance),
    including under subsampling and bf16 inputs."""
    rng = np.random.default_rng(0)
    tree = {
        f"l{i}": jnp.asarray(rng.normal(size=(8, 700 + 53 * i)).astype(np.float32))
        for i in range(10)
    }
    loop = np.asarray(pearson_tree(tree))
    fused = np.asarray(pearson_tree(tree, fused=True))
    np.testing.assert_allclose(loop, fused, atol=1e-6)
    loop_s = np.asarray(pearson_tree(tree, sample=1500, seed=7))
    fused_s = np.asarray(pearson_tree(tree, sample=1500, seed=7, fused=True))
    np.testing.assert_allclose(loop_s, fused_s, atol=1e-6)
    fused_bf16 = np.asarray(
        pearson_tree(tree, fused=True, compute_dtype=jnp.bfloat16)
    )
    np.testing.assert_allclose(loop, fused_bf16, atol=0.05)
    # the packed scan is a jnp path: combining it with the Pallas kernel
    # is an explicit error, never a silent fallback
    with pytest.raises(ValueError, match="fused"):
        pearson_tree(tree, fused=True, use_kernel=True)


def test_engine_spec_pipeline_accepted():
    """pipeline='engine' round-trips through the declarative spec API."""
    from repro.launch.experiment import ExperimentSpec, validate_spec

    spec = ExperimentSpec(pipeline="engine")
    validate_spec(spec)
    assert ExperimentSpec.from_json(spec.to_json()).pipeline == "engine"
    with pytest.raises(ValueError, match="pipeline"):
        validate_spec(ExperimentSpec(pipeline="turbo"))


def test_engine_host_parity_under_static_sign_flip():
    """Engine vs the numpy host oracle under poisoning(sign_flip_ids=...):
    schedule-driven accounting agrees exactly up to the merge round; the
    merge itself and everything after are behavioral only, because the
    host pipeline draws a DIFFERENT batch stream by design and the
    poisoned similarities sit near the threshold — but the attack's dent
    must show on both trajectories."""
    hists = {}
    kw = {"client_ids": (), "sign_flip_ids": (0,), "sign_flip_scale": 8.0}
    for pipeline in ("engine", "host"):
        sim = _make(pipeline, scenario="poisoning", scenario_kw=dict(kw),
                    rounds=6, threshold=0.6, seed=3)
        hists[pipeline] = sim.run()
    eng, host = hists["engine"], hists["host"]
    assert len(eng) == len(host) == 6
    # pre-merge rounds: full participation, identical accounting
    for e, h in zip(eng[:3], host[:3]):
        assert e.round == h.round
        assert e.active_nodes == h.active_nodes == NUM_CLIENTS
        assert e.updates_sent == h.updates_sent == NUM_CLIENTS
        assert e.bytes_sent == h.bytes_sent
        assert abs(e.accuracy - h.accuracy) < 0.1
    # both pipelines merge at the scheduled round and keep their reduced
    # populations consistent with their own groups thereafter
    for hist in (eng, host):
        assert hist[2].merged_groups
        retired = sum(len(g) - 1 for g in hist[2].merged_groups)
        assert hist[2].active_nodes_end == NUM_CLIENTS - retired
        for r in hist[3:]:
            assert r.active_nodes == r.updates_sent == hist[2].active_nodes_end
    # the sign-flip attacker dents both trajectories (clean runs end ~0.99)
    assert eng[-1].accuracy < 0.8 and host[-1].accuracy < 0.8
