#!/usr/bin/env python3
"""Chip smoke test: drive the repo's two main paths once on a TPU and check
what comes out.

  python chip_smoke.py             one chip: the federation and serving phases
  python chip_smoke.py --chips 4   four chips: only the pod-sharded federation,
                                   against the same run on one device

Everything runs in this one process; no child touches JAX.

federation  The paper's path at its own width: cnn_mnist on synthetic MNIST,
            10 clients, SCAFFOLD, Pearson merging at round 2 of 5, through
            ``run_experiment`` with the compiled round engine. Checks:
            - the config resolves to the compiled Pearson kernel and the
              engine's merge programs that ran hold it (``tpu_custom_call``);
            - teacher-forced, every engine round reproduces the per-round
              device pipeline's from the same state (``STEP_*``);
            - the merge groups equal the host oracle pipeline's, and each
              round's accuracy is within ACC_TOL of it;
            - on the merge round's local models, the kernel's K x K
              correlation is within CORR_TOL of ``pearson_matrix`` at
              "highest" matmul precision, and both give the same groups.
serving     qwen3-1.7b at its published widths in bf16, weights drawn from
            --seed. ``ServeEngine(kv_layout="paged")`` answers 8 requests
            (prompts of 128-512 tokens, 32 new tokens each, 8 slots,
            capacity 1024, page size 16); the admission and decode
            programs that ran hold the compiled flash-prefill and
            paged-decode kernels. Checks each request against
            ``launch/serve.generate`` on the jnp attention backends: the
            prefill logits within LOGIT_TOL, and every served token within
            LOGIT_TOL of the reference's best logit given the served prefix.
pods        (--chips 4) the pod-sharded engine: ``make_fl_mesh(4)``, 8
            clients, 5 rounds with the merge at round 2, against the same
            spec on one device: free-running, the counts and groups equal
            and accuracy and loss within POD_ACC_TOL and POD_LOSS_RTOL;
            teacher-forced, every round within the ``STEP_*`` tolerances;
            and the stacked client axis spread over all four devices.

Earlier lines carry smoke timings (wall and compile seconds; not benchmark
numbers). The last line is one JSON object, {"ok": true, "device": {...}}.
With no TPU, or when any phase or check fails, the script exits non-zero
and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Per-round test accuracy, engine vs host oracle. The two pipelines draw
# different batch streams by design (jax.random gather on device, numpy on
# host), so they form the same groups but train apart: on the CPU, over
# seeds 0-4, the largest per-round gap was 0.135 (round 0, while accuracy
# still climbs by ~0.3 a round; 0.078 at most after the merge). 0.2 is 1.5x
# that, and an engine that trains wrongly stays near chance (0.1).
ACC_TOL = 0.2
# Kernel correlation vs the two-pass reference at "highest" precision. In
# f32 the kernel's one-pass gram over this CNN's 2.1e5 coordinates agrees
# with the two-pass form to 6e-7 (CPU, interpret mode, seeds 0-4); 1e-4 is
# 170x that, and a correlation that moves less cannot flip a merge group
# unless it sits within 1e-4 of the threshold.
CORR_TOL = 1e-4
# bf16 logits, kernel attention vs jnp attention. The two paths round the
# attention output differently in every layer (the kernel accumulates in
# f32, the jnp path in bf16). With random weights the logits have standard
# deviation 0.02 * sqrt(2048) = 0.9; on the CPU (interpret-mode kernels,
# published widths, fewer layers) the largest prefill difference was 0.040
# at 2 layers and 0.064 at 8, which grows like sqrt(depth) to ~0.12 at 28.
# 0.25 is twice that and a quarter of the logits' spread; a wrong mask or
# page moves logits by their whole spread.
LOGIT_TOL = 0.25
# Teacher-forced comparisons (``follow``): before each round one run takes
# the other's model and controls, so a gap is one round's difference and
# not earlier rounds' rounding compounded by training (which is chaotic
# here: a 1e-5 relative change of the initial weights moves round 4's
# mean loss by 6%, on the CPU). On the CPU the engine reproduces the
# per-round device pipeline exactly, and the 4-way mesh reproduces one
# device to 1.4e-7 of each state leaf. Planted faults in the follower
# (half of the clients' updates dropped, or half of each batch replaced)
# move accuracy by 0.058-0.27, the model by 0.82-1.27 and the controls by
# 1.26-2.27 of their largest value. The tolerances sit 6-80x under those;
# the controls get more room because SCAFFOLD divides a client's update by
# the local learning rate, which magnifies rounding of the model ~60x.
STEP_ACC_TOL = 0.01
STEP_LOSS_RTOL = 1e-2
STEP_MODEL_RTOL = 1e-2
STEP_CONTROL_RTOL = 0.1
# Pod-sharded vs one-device runs, free-running over all 5 rounds. They
# draw the same batches; only f32 summation order differs (per-device
# programs over 2 clients instead of 8, the cross-device mean), and the
# chaotic training above amplifies it after the merge (on a v5e 2x2 at
# "highest" precision: 1.2e-4 of the mean loss at round 2, 1.1e-2 at
# round 3). So this comparison can only bound the drift. On one v5e at
# "highest", a 1-ulp change of the initial weights parts a run from
# itself by up to 0.009 accuracy and 3.6% of the loss over these 5 rounds,
# a 1e-5 relative change by 0.016 and 16%; the tolerances are about 3x and
# 2x the latter. The teacher-forced check above is the tight one. Both run
# at "highest" matmul precision: at the TPU's default, f32 matmuls take one
# bf16 pass, which parts the programs by 0.028 accuracy within 2 rounds.
POD_ACC_TOL = 0.05
POD_LOSS_RTOL = 0.3


class Compiles:
    """Counts backend compiles, their seconds and persistent-cache hits."""

    def __init__(self):
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

    def snapshot(self):
        return (self.n, self.seconds, self.hits, self.misses)


def timed(name, compiles, fn, *args, **kwargs):
    """Run one phase; print its smoke timing line."""
    n0, s0, h0, m0 = compiles.snapshot()
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    n1, s1, h1, m1 = compiles.snapshot()
    print(f"smoke timing (not a benchmark) {name}: wall {wall:.1f} s, "
          f"{n1 - n0} compiles {s1 - s0:.1f} s, persistent cache "
          f"{h1 - h0} hits / {m1 - m0} misses", flush=True)
    return out


def check(ok, what):
    if not ok:
        raise AssertionError(what)


class Programs:
    """Keeps the StableHLO of every program this process runs: JAX dumps
    each module it hands to the compiler or finds in the persistent cache
    (``jax_dump_ir_to``). A Pallas kernel compiled for the TPU, not run by
    the interpreter, shows in it as a ``tpu_custom_call``."""

    def __init__(self, path: str):
        self.path = Path(path)
        jax.config.update("jax_dump_ir_to", str(self.path))
        jax.config.update("jax_include_debug_info_in_dumps", False)

    def mark(self) -> set:
        return set(self.path.iterdir())

    def assert_kernel(self, mark: set, fn_name: str, what: str):
        """Every program of the jitted function ``fn_name`` run since
        ``mark`` holds a compiled Pallas kernel, and at least one ran."""
        ran = [p for p in set(self.path.iterdir()) - mark
               if p.name.endswith(f"_jit_{fn_name}_compile.mlir")]
        check(ran, f"{what}: no program of {fn_name} ran")
        for p in ran:
            check("tpu_custom_call" in p.read_text(),
                  f"{what}: no compiled Pallas kernel in {p.name}")


# ---------------------------------------------------------------------------
# federation
# ---------------------------------------------------------------------------


def federation_spec(seed: int, **kw):
    from repro.launch.experiment import ExperimentSpec

    base = dict(model="cnn_mnist", dataset="synthetic_mnist", num_clients=10,
                algo="scaffold", merge_policy="pearson", rounds=5,
                merge_at=(2,), pipeline="engine", seed=seed)
    return ExperimentSpec(**{**base, **kw})


def compare_records(hist, ref, acc_tol: float, loss_rtol: float, what: str):
    """Counts and merge groups equal in every round; accuracy within
    ``acc_tol`` and mean loss within ``loss_rtol`` of ``ref``'s. Returns
    the largest accuracy and relative loss gaps."""
    exact = ("round", "active_nodes", "updates_sent", "bytes_sent",
             "active_nodes_end", "merged_groups")
    acc_gap = loss_gap = 0.0
    for r, o in zip(hist, ref, strict=True):
        check(all(getattr(r, f) == getattr(o, f) for f in exact),
              f"{what}, round {r.round}: records differ: {r} vs {o}")
        acc_gap = max(acc_gap, abs(r.accuracy - o.accuracy))
        loss_gap = max(loss_gap,
                       abs(r.mean_loss - o.mean_loss) / abs(o.mean_loss))
    print(f"{what}: accuracy {[r.accuracy for r in hist]} vs "
          f"{[o.accuracy for o in ref]}, mean loss "
          f"{[r.mean_loss for r in hist]} vs {[o.mean_loss for o in ref]}; "
          f"largest gaps {acc_gap:.4g} accuracy, {loss_gap:.4g} relative loss",
          flush=True)
    check(acc_gap <= acc_tol and loss_gap <= loss_rtol,
          f"{what}: accuracy/loss differ by more than {acc_tol} / {loss_rtol}")
    return acc_gap, loss_gap


def rel_gap(a, b) -> float:
    """Largest |a - b| over the leaves of two pytrees, each relative to
    the largest |b| of its leaf."""
    return max(
        float(np.max(np.abs(x - y)) / max(float(np.max(np.abs(y))), 1e-30))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b), strict=True))


def follow(ref, sim, what: str) -> dict:
    """Teacher forcing: before each round ``sim`` takes ``ref``'s model
    and controls, then both run that one round. A gap is then one round's
    worth of difference, not earlier rounds' rounding compounded by
    training. Checks the RoundRecords and the state after every round
    against the ``STEP_*`` tolerances; returns the largest state gaps."""
    gaps = {"params": 0.0, "controls": 0.0}
    for t in range(ref.fl.num_rounds):
        sim.load_state(*jax.device_get(
            (ref.params, ref.c_global, ref.c_locals)))
        sim.run(start=t, stop=t + 1)
        ref.run(start=t, stop=t + 1)
        a, b = jax.device_get(((sim.params, sim.c_global, sim.c_locals),
                               (ref.params, ref.c_global, ref.c_locals)))
        gaps["params"] = max(gaps["params"], rel_gap(a[0], b[0]))
        gaps["controls"] = max(gaps["controls"], rel_gap(a[1:], b[1:]))
        check(np.array_equal(sim.weights, ref.weights)
              and np.array_equal(sim.active, ref.active),
              f"{what}, round {t}: weights or active set differ")
    compare_records(sim.history, ref.history, STEP_ACC_TOL, STEP_LOSS_RTOL,
                    what)
    print(f"{what}: largest state gap after a round {gaps['params']:.4g} "
          f"model, {gaps['controls']:.4g} controls", flush=True)
    check(gaps["params"] <= STEP_MODEL_RTOL
          and gaps["controls"] <= STEP_CONTROL_RTOL,
          f"{what}: state differs by more than {STEP_MODEL_RTOL} (model) "
          f"or {STEP_CONTROL_RTOL} (controls)")
    return gaps


def federation_phase(seed: int, programs: Programs) -> dict:
    from repro.core.pearson import client_param_matrix, pearson_matrix
    from repro.launch.experiment import build_simulator, run_experiment

    spec = federation_spec(seed)
    mark = programs.mark()
    sim, hist = run_experiment(spec, verbose=False)
    fl = sim.fl
    check(fl.pearson_kernel, "FLConfig.pearson_kernel is off")
    check(not fl.pearson_interpret, "FLConfig.pearson_interpret is on")
    programs.assert_kernel(mark, "merge_device", "engine merge program")

    # the per-round device pipeline draws the same batches as the engine
    follow(build_simulator(dataclasses.replace(spec, pipeline="device")),
           build_simulator(spec),
           "federation: engine following the per-round device pipeline")

    # the host oracle on the same spec; keep its merge-round inputs
    host = build_simulator(dataclasses.replace(spec, pipeline="host"))
    seen = {}
    plan_fn = host.policy.merge_plan

    def capture(x_locals, weights, active):
        seen.update(x=x_locals, weights=weights.copy(), active=active.copy())
        return plan_fn(x_locals, weights, active)

    host.policy.merge_plan = capture
    host_hist = host.run()

    t = spec.merge_at[0]
    groups, host_groups = hist[t].merged_groups, host_hist[t].merged_groups
    print(f"federation: merge groups engine {groups} host {host_groups}")
    check(groups and groups == host_groups, "merge groups differ from host")
    acc = np.asarray([r.accuracy for r in hist])
    host_acc = np.asarray([r.accuracy for r in host_hist])
    print(f"federation: accuracy engine {acc.tolist()} host "
          f"{host_acc.tolist()}")
    check(np.all(np.abs(acc - host_acc) <= ACC_TOL),
          f"accuracy differs from host by more than {ACC_TOL}")

    corr = np.asarray(jax.jit(sim.policy.device_similarity)(seen["x"]))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(pearson_matrix(client_param_matrix(seen["x"])))
    corr_diff = float(np.max(np.abs(corr - ref)))
    plan = host.policy.plan
    kernel_groups = plan(corr, seen["weights"], seen["active"]).groups
    ref_groups = plan(ref, seen["weights"], seen["active"]).groups
    print(f"federation: kernel vs highest-precision Pearson max |diff| "
          f"{corr_diff:.3e}; groups kernel {kernel_groups} reference "
          f"{ref_groups}")
    check(kernel_groups == ref_groups,
          "the kernel's correlation changes a merge group")
    check(corr_diff <= CORR_TOL, f"Pearson differs by more than {CORR_TOL}")
    return {"groups": [list(g) for g in groups], "corr_diff": corr_diff,
            "acc_diff": float(np.max(np.abs(acc - host_acc)))}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def serving_requests(vocab: int, seed: int, lengths, max_new: int):
    from repro.serving.traffic import Request

    rng = np.random.default_rng(seed)
    return [
        Request(rid=i, client_id=0, max_new_tokens=max_new,
                prompt=rng.integers(0, vocab, n).astype(np.int32))
        for i, n in enumerate(lengths)
    ]


def serving_phase(cfg, seed: int, programs: Programs,
                  lengths=(128, 256, 384, 512) * 2,
                  max_new: int = 32, slots: int = 8, capacity: int = 1024,
                  page: int = 16) -> dict:
    from repro.launch.serve import (
        decode_step_fn, generate, prefill_fn, states_from_prefill,
    )
    from repro.models import model as M
    from repro.serving import ServeEngine

    ref_cfg = dataclasses.replace(cfg, decode_attn_backend="jnp",
                                  prefill_backend="jnp")
    params = jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    reqs = serving_requests(cfg.vocab_size, seed, lengths, max_new)

    mark = programs.mark()
    eng = ServeEngine(params, cfg, num_slots=slots, capacity=capacity,
                      kv_layout="paged", block_size=page)
    for r in reqs:
        check(eng.try_admit(r) is not None, f"request {r.rid} not admitted")
    served = {a.request.rid: a.tokens for a in eng.run_to_completion()}
    check(sorted(served) == [r.rid for r in reqs]
          and all(len(t) == max_new for t in served.values()),
          "not every request was answered in full")
    programs.assert_kernel(mark, "admit", "admission prefill")
    programs.assert_kernel(mark, "step", "paged decode step")

    step = decode_step_fn(ref_cfg)
    worst_logit, worst_gap, agree = 0.0, 0.0, []
    for r in reqs:
        batch = {"tokens": jnp.asarray(r.prompt[None])}
        logits, raw = prefill_fn(ref_cfg)(params, batch)
        served_logits, _ = prefill_fn(cfg)(params, batch)
        worst_logit = max(worst_logit, float(jnp.max(jnp.abs(
            served_logits.astype(jnp.float32) - logits.astype(jnp.float32)))))
        # the reference's logits along the SERVED tokens: each served token
        # must be a near-best choice of the reference given the same prefix
        states = states_from_prefill(ref_cfg, raw, len(r.prompt), capacity)
        gaps = []
        for i, tok in enumerate(served[r.rid]):
            lg = logits[0].astype(jnp.float32)
            gaps.append(jnp.max(lg) - lg[tok])
            if i + 1 < max_new:
                logits, states = step(
                    params, states, jnp.asarray([tok], jnp.int32),
                    jnp.asarray([len(r.prompt) + i], jnp.int32))
        worst_gap = max(worst_gap, float(jnp.max(jnp.stack(gaps))))
        ref_tokens, _ = generate(params, ref_cfg, batch,
                                 max_new_tokens=max_new, capacity=capacity)
        same = np.asarray(ref_tokens[0]) == np.asarray(served[r.rid])
        agree.append(int(np.argmin(same)) if not same.all() else max_new)
    print(f"serving: {len(served)} requests x {max_new} tokens; prefill "
          f"logits kernel vs jnp max |diff| {worst_logit:.4f}; served token "
          f"vs reference best logit max gap {worst_gap:.4f}; leading tokens "
          f"equal to generate() per request {agree}")
    check(worst_logit <= LOGIT_TOL,
          f"prefill logits differ by more than {LOGIT_TOL}")
    check(worst_gap <= LOGIT_TOL,
          f"a served token is more than {LOGIT_TOL} below the reference's")
    return {"logit_diff": worst_logit, "token_gap": worst_gap,
            "tokens_equal": agree}


# ---------------------------------------------------------------------------
# pods (four chips)
# ---------------------------------------------------------------------------


def pod_phase(seed: int, pods: int) -> dict:
    from repro.launch.experiment import MESHES, build_simulator, run_experiment
    from repro.launch.mesh import make_fl_mesh

    if "pods" not in MESHES:
        MESHES.register("pods")(lambda: make_fl_mesh(pods))
    spec = federation_spec(seed, num_clients=8)
    meshed = dataclasses.replace(spec, mesh="pods")
    with jax.default_matmul_precision("highest"):
        sim, hist = run_experiment(meshed, verbose=False)
        _, one = run_experiment(spec, verbose=False)
        compare_records(hist, one, POD_ACC_TOL, POD_LOSS_RTOL,
                        f"pods: {pods}-way mesh vs one device")
        follow(build_simulator(spec), build_simulator(meshed),
               f"pods: {pods}-way mesh following one device")

    # the stacked client axis (controls of all 8 clients, leaf by leaf)
    # holds 8 / pods clients on each device, not all 8 on the first
    devices = set(jax.devices())
    for path, leaf in jax.tree_util.tree_leaves_with_path(sim.c_locals):
        shards = leaf.addressable_shards
        rows = {s.data.shape[0] for s in shards}
        check({s.device for s in shards} == devices and len(shards) == pods
              and rows == {leaf.shape[0] // pods},
              f"client axis of {jax.tree_util.keystr(path)} {leaf.shape} "
              f"is not split over the {pods} devices: {rows} rows")
    print(f"pods: client axis of every control leaf split "
          f"{spec.num_clients // pods} clients per device over {pods} devices")
    return {"pods": pods}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} devices")
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"chip_smoke: {len(devices)} x {dev.device_kind}, compile cache "
          f"{enable_compile_cache()}", flush=True)
    compiles = Compiles()
    t0 = time.perf_counter()
    if args.chips == 4:
        timed("pods", compiles, pod_phase, args.seed, 4)
    else:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_ir_") as ir_dir:
            programs = Programs(ir_dir)
            timed("federation", compiles, federation_phase, args.seed,
                  programs)
            timed("serving", compiles, serving_phase,
                  get_config("qwen3-1.7b"), args.seed, programs)
    n, secs, hits, misses = compiles.snapshot()
    print(f"smoke timing (not a benchmark) total: wall "
          f"{time.perf_counter() - t0:.1f} s, {n} compiles {secs:.1f} s, "
          f"persistent cache {hits} hits / {misses} misses", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
