"""Federation cells: the program's compiled round engine runs whole jobs of
the paper's experiment back to back for the window.

Set-up makes the data and the initial model from the seed and runs two
whole jobs (``FederatedSimulator`` + ``RoundEngine.run``), which compiles
every program the window uses. The window then runs fresh jobs from the
same seeded start, sharing those compiled programs, until ``seconds`` have
passed; the job that is running then is finished and counted. The first
job of the window is the checked one: its merge round's program reads back
the state that enters it and the state it hands on. After the window the
reference follows that job from the same data and initial model, and the
numbers it is compared on are each held to a limit from the configuration
file.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from bench import common, generator

FREE_ROUNDS = 3  # rounds the reference follows free-running from the seed
# Rounds whose loss is compared: by the third round the gap that rounding
# opens overlaps the control's and every planted fault's (PERF.md section 2).
LOSS_ROUNDS = 2


def make_digits(jax, jnp, seed: int, n_train: int, n_test: int, size: int,
                classes: int):
    """Synthetic digits, made on the device in one call: each class is a
    smooth random 28x28 pattern, each sample its class's pattern shifted by
    up to two pixels, scaled and with pixel noise, clipped to [0, 1]."""

    @jax.jit
    def make(key):
        k_t, k_y, k_s, k_c, k_n = jax.random.split(key, 5)
        coarse = jax.random.normal(k_t, (classes, size // 4, size // 4))
        tmpl = jax.image.resize(coarse, (classes, size, size), "cubic")
        tmpl = jax.nn.relu(tmpl)
        tmpl = tmpl / jnp.max(tmpl, axis=(1, 2), keepdims=True)
        n = n_train + n_test
        y = jax.random.randint(k_y, (n,), 0, classes)
        shift = jax.random.randint(k_s, (n, 2), -2, 3)
        scale = jax.random.uniform(k_c, (n,), minval=0.7, maxval=1.0)
        x = jax.vmap(lambda t, s: jnp.roll(t, s, axis=(0, 1)))(tmpl[y], shift)
        x = x * scale[:, None, None]
        x = x + 0.15 * jax.random.normal(k_n, x.shape)
        return jnp.clip(x, 0.0, 1.0)[..., None].astype(jnp.float32), y

    x, y = make(jax.random.PRNGKey(seed))
    x, y = np.asarray(x), np.asarray(y, np.int32)
    return x[:n_train], y[:n_train], x[n_train:], y[n_train:]


def _norm_gap(prog: Dict, ref: Dict, base: Dict = None) -> float:
    """Worst leaf of |‖a‖ - ‖b‖| over the larger of the reference leaf's norm
    and the median leaf's; with ``base``, the norms are of the change from
    it. Leaves whose reference norm is under a thousandth of the median
    leaf's (round-off only) are left out."""
    import jax

    def norms(tree):
        leaves = jax.tree_util.tree_leaves(tree)
        if base is not None:
            leaves = [np.asarray(a, np.float64) - np.asarray(b, np.float64)
                      for a, b in zip(leaves, jax.tree_util.tree_leaves(base))]
        return np.asarray([np.linalg.norm(np.asarray(a, np.float64).ravel())
                           for a in leaves])

    p, r = norms(prog), norms(ref)
    med = float(np.median(r))
    keep = r >= 1e-3 * med
    return float(np.max(np.abs(p[keep] - r[keep]) / np.maximum(r[keep], med)))


def compare(prog: dict, free: dict, forced: dict, params0) -> Dict[str, float]:
    """The numbers held to limits. Free-running from the same data and
    initial model, over the job's first three rounds: the loss of the
    first two, the model's change in the first round (the first update as the server
    applies it) and its change over the three. Later rounds are not
    followed free: training here is chaotic, and the gap that rounding
    alone opens grows about tenfold a round. Teacher-forced from the state
    before the merge round: the merge round's loss, the change of the model
    and of the global control in it, the merged controls, and the plan."""
    if len(prog["rounds"]) < FREE_ROUNDS:
        raise common.BenchError(f"the checked job ran fewer than {FREE_ROUNDS} "
                                "rounds before its merge round")
    pm, fm = prog["merge"], forced["merge"]
    gp = sorted(tuple(g) for g in pm["groups"])
    gf = sorted(tuple(g) for g in fm["groups"])
    mismatch = len(set(gp) ^ set(gf))
    mismatch += int(np.sum(np.asarray(pm["weights"]) != np.asarray(fm["weights"])))
    mismatch += int(np.sum(np.asarray(pm["active"]) != np.asarray(fm["active"])))
    out = {f"loss_gap_r{t}": abs(prog["losses"][t] - free["losses"][t])
           / abs(free["losses"][t]) for t in range(LOSS_ROUNDS)}
    out["first_update_gap"] = _norm_gap(prog["rounds"][0], free["rounds"][0], params0)
    out["model_change_gap"] = _norm_gap(prog["rounds"][FREE_ROUNDS - 1],
                                        free["rounds"][FREE_ROUNDS - 1], params0)
    lmp, lmf = prog["losses"][-1], forced["losses"][-1]
    out["merge_loss_gap"] = abs(lmp - lmf) / abs(lmf)
    out["merge_model_gap"] = _norm_gap(pm["params"], fm["params"],
                                       prog["segment"]["params"])
    out["merge_global_control_gap"] = _norm_gap(pm["c_global"], fm["c_global"],
                                                prog["segment"]["c_global"])
    out["merged_control_gap"] = _norm_gap(pm["c_locals"], fm["c_locals"])
    out["plan_mismatch"] = float(mismatch)
    return out


def check(ref, c: dict, st: dict, out: dict) -> Dict[str, float]:
    """Compare a job's readings (the program's, or in calibration the
    control's or a fault's) with the reference: free-running from the
    seed's data and model, and through the merge round from the state the
    job reached before it."""
    args = (c, st["fed"], st["job"], st["params0"], st["shards"], st["seed32"])
    free = st.get("free") or ref.follow(*args)
    st["free"] = free
    forced = ref.follow(*args, start=out["segment"])
    return compare(out, free, forced, st["params0"])


def watched(programs: dict, seen: dict) -> dict:
    """``programs`` with two programs wrapped so that what the checked job
    produces is read back to the host: the model after each round of its
    first scan segment, and the state that enters the merge round and the
    state it hands on (the program donates both to the next call)."""
    import jax

    real_seg, real_merge = programs["segment"], programs["merge_device"]

    def take(state):
        return jax.device_get({"params": state[0], "c_global": state[1],
                               "c_locals": state[2]})

    def segment(state, const, xs):
        out = real_seg(state, const, xs)
        if "rounds" not in seen:
            stack = jax.device_get(out[1][0])
            n = len(jax.tree_util.tree_leaves(stack)[0])
            seen["rounds"] = [jax.tree_util.tree_map(lambda a: a[t], stack)
                              for t in range(n)]
        return out

    def merge_device(state, const, xrow):
        seen["segment"] = take(state)
        out = real_merge(state, const, xrow)
        seen["merge"] = take(out[0])
        return out

    return dict(programs, segment=segment, merge_device=merge_device)


def run_job(ctx, st: dict, programs: dict, seen: dict = None):
    """One whole job, as the window runs it; with ``seen``, the checked job.
    Returns the simulator, its history and the job's set-up time."""
    from repro.core.engine import RoundEngine

    t0 = time.perf_counter()
    with ctx.span("bench.job_setup"):
        sim = st["new_job"]()
        eng = RoundEngine(sim, programs=programs if seen is None
                          else watched(programs, seen))
    t1 = time.perf_counter()
    with ctx.span("bench.job_rounds"):
        hist = eng.run()
    return sim, hist, t1 - t0


def readings(sim, hist, seen: dict, m: int) -> dict:
    """What the checked job produced, for the comparison: the losses up to
    its merge round, the state before and after that round, the weights and
    active set it left and the groups it merged."""
    if "merge" not in seen:
        raise common.BenchError("the checked job ran no merge round on the device")
    return {"losses": [r.mean_loss for r in hist[: m + 1]],
            "rounds": seen["rounds"],
            "segment": seen["segment"],
            "merge": {**seen["merge"],
                      "weights": np.asarray(sim.weights, np.float32),
                      "active": np.asarray(sim.active, np.float32),
                      "groups": [list(g) for g in hist[m].merged_groups]}}


def prepare(ctx, seed: int) -> dict:
    """Data and initial model from ``seed``, and the compiled programs: one
    job builds them, and a second runs the way the window's checked job
    does, so that nothing the window does is done for the first time
    inside it."""
    import jax
    import jax.numpy as jnp
    from repro.configs import cnn_mnist
    from repro.core.engine import RoundEngine
    from repro.core.federation import FederatedSimulator, FLConfig
    from repro.core.scaffold import AlgoConfig
    from repro.models import cnn_accuracy, cnn_loss

    c, fed, job = ctx.config, ctx.config["federation"], generator.fed_job(ctx.mix)
    ref = ctx.reference
    ccfg = cnn_mnist.config()
    for k in ("image_size", "channels", "kernel_size", "hidden", "num_classes"):
        if getattr(ccfg, k) != c[k]:
            raise common.BenchError(f"program's CNN {k}={getattr(ccfg, k)}, "
                                    f"configuration {c[k]}")
    if list(ccfg.conv_features) != list(c["conv_features"]) or ccfg.dtype != c["dtype"]:
        raise common.BenchError("program's CNN widths or dtype differ from the configuration")
    seed32 = seed % (2**31 - 1)

    x_tr, y_tr, x_te, y_te = make_digits(
        jax, jnp, seed32, job["train_samples"], job["test_samples"],
        c["image_size"], c["num_classes"])
    parts = generator.class_shards(y_tr, job["num_clients"],
                                 job["shards_per_client"],
                                 generator.rng_for(seed, 0x5A4D))
    shards = [(x_tr[p], y_tr[p]) for p in parts]
    params0 = jax.device_get(jax.jit(lambda k: ref.init_params(c, k))(
        jax.random.PRNGKey(seed32 ^ 0x1234)))
    fl = FLConfig(
        algo=AlgoConfig(algorithm=fed["algorithm"], lr_local=fed["lr_local"],
                        lr_global=fed["lr_global"]),
        num_rounds=job["rounds"], local_epochs=job["local_epochs"],
        steps_per_epoch=job["steps_per_epoch"], batch_size=job["batch_size"],
        merge_policy=fed["merge_policy"], merge_at=(job["merge_at"],),
        threshold=fed["threshold"], max_group_size=fed["max_group_size"],
        alpha=fed["alpha"], pipeline="engine", seed=seed32)

    def new_job():
        return FederatedSimulator(
            init_params_fn=lambda _key: jax.tree_util.tree_map(jnp.asarray, params0),
            loss_fn=lambda p, b: cnn_loss(p, ccfg, b),
            eval_fn=lambda p: cnn_accuracy(p, ccfg, x_te, y_te),
            client_shards=shards, fl=fl)

    eng = RoundEngine(new_job())
    eng.run()
    programs = eng.programs
    del eng
    gc.collect()
    st = {"new_job": new_job, "programs": programs, "params0": params0,
          "shards": shards, "seed32": seed32, "job": job, "fed": fed}
    run_job(ctx, st, programs, {})
    return st


def run(ctx) -> dict:
    st = prepare(ctx, ctx.seed)
    setup_s = time.perf_counter() - ctx.t0
    programs, job, m = st["programs"], st["job"], st["job"]["merge_at"]

    # ---- the window; its first job is the checked one
    c0 = ctx.compiles.snapshot()["compiles"]
    jobs: List[dict] = []
    seen: dict = {}
    ctx.start_trace()
    t_w = time.perf_counter()
    while True:
        sim, hist, job_setup_s = run_job(ctx, st, programs,
                                         None if jobs else seen)
        j2 = time.perf_counter()
        if not jobs:
            prog = readings(sim, hist, seen, m)
        jobs.append({"setup_s": job_setup_s,
                     "rounds": [{"round": r.round, "wall_s": r.wall_s,
                                 "merge": r.round == m,
                                 "active": r.active_nodes} for r in hist]})
        del sim, hist
        if j2 - t_w >= ctx.seconds:
            break
    window_s = time.perf_counter() - t_w
    trace = ctx.stop_trace()
    compiles_in_window = ctx.compiles.snapshot()["compiles"] - c0
    mem_peak = ctx.memory_peak()

    # ---- the check, once the program's state is gone
    gc.collect()
    c = ctx.config
    numbers = check(ctx.reference, c, st, prog)
    notes = {"corr_margin": st["free"]["corr_margin"],
             "groups": len(prog["merge"]["groups"])}
    steps = job["local_epochs"] * job["steps_per_epoch"]
    from bench import flops
    return {
        "setup_s": setup_s,
        "window_s": window_s,
        "attempted": sum(len(j["rounds"]) for j in jobs),
        "failed": 0,
        "numbers": numbers,
        "notes": notes,
        "compiles_in_window": compiles_in_window,
        "memory_peak_bytes": mem_peak,
        "trace": trace,
        "record": {
            "jobs": jobs,
            "samples_per_client_round": steps * job["batch_size"],
            "train_flops_per_sample": flops.cnn_train_flops(c),
            "param_count": flops.cnn_param_count(c),
            "num_clients": job["num_clients"],
        },
    }
