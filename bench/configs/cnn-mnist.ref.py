"""Plain reference of the federation cells: the paper's CNN trained by
SCAFFOLD over K clients, with Pearson merging at one round.

Written from the paper's description and the configuration file alone; it
imports nothing of the program under test. Every client trains under
``vmap``; the merge is a plain host loop. ``dtype`` is the precision the
reference computes in: float32 for the reference, bfloat16 for the
control. ``fault`` plants one of the faults the check must catch, for the
benchmark's own tests and calibration.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def init_params(c: dict, key) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Normal weights scaled by 1/sqrt(fan-in), zero biases."""
    ks = jax.random.split(key, len(c["conv_features"]) + 2)
    p, c_in, size, k = {}, c["channels"], c["image_size"], c["kernel_size"]
    for i, c_out in enumerate(c["conv_features"]):
        p[f"conv{i}"] = {
            "w": jax.random.normal(ks[i], (k, k, c_in, c_out)) / np.sqrt(k * k * c_in),
            "b": jnp.zeros((c_out,))}
        c_in, size = c_out, size // 2
    flat = size * size * c_in
    p["fc1"] = {"w": jax.random.normal(ks[-2], (flat, c["hidden"])) / np.sqrt(flat),
                "b": jnp.zeros((c["hidden"],))}
    p["fc2"] = {"w": jax.random.normal(ks[-1], (c["hidden"], c["num_classes"]))
                / np.sqrt(c["hidden"]),
                "b": jnp.zeros((c["num_classes"],))}
    return p


def forward(p, x, n_conv: int):
    """x (B, H, W, C) -> logits. SAME 3x3 convolution, ReLU and a 2x2 max
    pool per block; then dense, ReLU, dense."""
    for i in range(n_conv):
        x = jax.lax.conv_general_dilated(
            x, p[f"conv{i}"]["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + p[f"conv{i}"]["b"]
        x = jax.nn.relu(x)
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["fc1"]["w"] + p["fc1"]["b"])
    return x @ p["fc2"]["w"] + p["fc2"]["b"]


def loss(p, x, y, n_conv: int, half_batch: bool = False):
    """Mean cross-entropy over the batch (over its first half when the
    half-batch fault is planted)."""
    if half_batch:
        x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
    z = forward(p, x, n_conv).astype(jnp.float32)
    return jnp.mean(jax.nn.logsumexp(z, -1) - jnp.take_along_axis(z, y[:, None], 1)[:, 0])


# ---------------------------------------------------------------------------
# one round
# ---------------------------------------------------------------------------


def _round_fn(n_conv: int, steps: int, batch: int, lr: float, lr_g: float,
              dtype, fault: Optional[str]):
    """SCAFFOLD (option II): each client runs ``steps`` steps of
    x <- x - lr (g + c - c_i) on batches drawn uniformly with replacement
    from its own rows, then c_i <- c_i - c + (x - x_i) / (steps lr). The
    server adds lr_g times the data-weighted mean change of the models, and
    the mean change of the controls over the clients that took part."""

    def client(x_g, c_g, c_i, xb, yb):
        def step(x, b):
            bx, by = b
            lval, g = jax.value_and_grad(loss)(x, bx, by, n_conv,
                                               fault == "half_batch")
            x = jax.tree_util.tree_map(
                lambda xx, gg, cg, ci: (xx - lr * (gg + cg - ci)).astype(dtype),
                x, g, c_g, c_i)
            return x, lval

        x_f, losses = jax.lax.scan(step, x_g, (xb, yb))
        c_new = jax.tree_util.tree_map(
            lambda ci, cg, xg, xf: (ci - cg + (xg - xf) / (steps * lr)).astype(dtype),
            c_i, c_g, x_g, x_f)
        return x_f, c_new, jnp.mean(losses)

    def round_fn(x_g, c_g, c_l, xs, ys, off, lens, key, weights, active):
        idx = jax.random.randint(key, (lens.shape[0], steps, batch), 0,
                                 jnp.maximum(lens, 1)[:, None, None])
        idx = jnp.minimum(off[:, None, None] + idx, xs.shape[0] - 1)
        xb = jnp.take(xs, idx, axis=0).astype(dtype)
        yb = jnp.take(ys, idx, axis=0)
        x_loc, c_new, losses = jax.vmap(client, in_axes=(None, None, 0, 0, 0))(
            x_g, c_g, c_l, xb, yb)
        w = weights * active
        wn = w / jnp.sum(w)
        k_act = jnp.sum(active)

        def bc(v, t):
            return v.reshape((-1,) + (1,) * (t.ndim - 1)).astype(t.dtype)

        x_new = jax.tree_util.tree_map(
            lambda g, xl: (g + lr_g * jnp.sum(bc(wn, xl) * (xl - g[None]), 0)).astype(dtype),
            x_g, x_loc)
        c_g_new = jax.tree_util.tree_map(
            lambda g, cn, co: (g + jnp.sum(bc(active, cn) * (cn - co), 0) / k_act).astype(dtype),
            c_g, c_new, c_l)
        c_l_new = jax.tree_util.tree_map(
            lambda cn, co: jnp.where(bc(active, cn) > 0, cn, co), c_new, c_l)
        if fault == "unchanged":  # the round hands back the state it got
            return x_g, c_g, c_l, x_loc, losses
        return x_new, c_g_new, c_l_new, x_loc, losses

    return jax.jit(round_fn)


# ---------------------------------------------------------------------------
# merging
# ---------------------------------------------------------------------------


def pearson(x_loc) -> np.ndarray:
    """Pearson correlation between the clients' flattened models, over every
    parameter, two-pass (centred first) in float32 at full precision."""
    X = jnp.concatenate([v.astype(jnp.float32).reshape(v.shape[0], -1)
                         for v in jax.tree_util.tree_leaves(x_loc)], axis=1)
    Z = X - jnp.mean(X, axis=1, keepdims=True)
    with jax.default_matmul_precision("highest"):
        cov = Z @ Z.T / X.shape[1]
    sd = jnp.sqrt(jnp.diag(cov))
    corr = np.asarray(cov / jnp.maximum(jnp.outer(sd, sd), 1e-30), np.float64)
    np.fill_diagonal(corr, 1.0)
    return corr


def greedy_groups(corr: np.ndarray, active: np.ndarray, threshold: float,
                  max_group: int):
    """The paper's grouping: in index order, each active client not yet
    grouped takes up to max_group - 1 further ungrouped active clients, in
    index order, whose correlation with it reaches the threshold. Returns
    the groups (representative first) and the clients left alone."""
    K = len(active)
    used = np.zeros(K, bool)
    groups, alone = [], []
    for i in range(K):
        if used[i] or active[i] <= 0:
            continue
        partners = [j for j in range(K)
                    if j != i and not used[j] and active[j] > 0
                    and corr[i, j] >= np.float32(threshold)][: max_group - 1]
        if partners:
            g = [i] + partners
            used[g] = True
            groups.append(g)
        else:
            alone.append(i)
    return groups, alone


# ---------------------------------------------------------------------------
# a job, followed round by round
# ---------------------------------------------------------------------------


def follow(c: dict, fed: dict, job: dict, params0, shards: List, seed: int,
           dtype=jnp.float32, fault: Optional[str] = None,
           start: Optional[dict] = None) -> dict:
    """Run the job's rounds up to and including its merge round from
    ``params0`` on ``shards`` (a list of (x, y) per client). Returns each
    round's mean loss over the active clients and, after the last round
    before the merge and after the merge round, the model, the global
    control, the clients' controls, their weights and active set, and the
    groups. With ``start`` (the model and controls after the last round
    before the merge) only the merge round is run, from that state."""
    n_conv = len(c["conv_features"])
    steps = job["local_epochs"] * job["steps_per_epoch"]
    rnd = _round_fn(n_conv, steps, job["batch_size"], fed["lr_local"],
                    fed["lr_global"], dtype, fault)
    K = len(shards)
    merge_at = job["merge_at"]
    shards = [(np.asarray(x), np.asarray(y)) for x, y in shards]

    def flat(sh):
        lens = np.asarray([len(y) for _, y in sh], np.int32)
        off = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
        return (jnp.asarray(np.concatenate([x for x, _ in sh])),
                jnp.asarray(np.concatenate([y for _, y in sh])),
                jnp.asarray(off), jnp.asarray(lens))

    x_g = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), params0)
    c_g = jax.tree_util.tree_map(jnp.zeros_like, x_g)
    c_l = jax.tree_util.tree_map(lambda a: jnp.zeros((K,) + a.shape, dtype), x_g)
    first = 0
    if start is not None:
        x_g, c_g, c_l = (jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), start[k])
                         for k in ("params", "c_global", "c_locals"))
        first = merge_at
    weights = np.asarray([len(y) for _, y in shards], np.float32)
    active = np.ones(K, np.float32)
    bkey = jax.random.PRNGKey(seed)
    xs, ys, off, lens = flat(shards)
    out = {"losses": [], "rounds": []}
    for t in range(first, merge_at + 1):
        x_g, c_g, c_l, x_loc, losses = rnd(
            x_g, c_g, c_l, xs, ys, off, lens, jax.random.fold_in(bkey, t),
            jnp.asarray(weights), jnp.asarray(active))
        losses = np.asarray(losses, np.float64)
        out["losses"].append(float(np.sum(losses * active) / active.sum()))
        if t < merge_at:
            out["rounds"].append(jax.device_get(x_g))
        if t == merge_at - 1:
            out["segment"] = jax.device_get(
                {"params": x_g, "c_global": c_g, "c_locals": c_l})
    corr = pearson(x_loc)
    groups, alone = greedy_groups(corr, active, fed["threshold"],
                                  fed["max_group_size"])
    off_diag = corr[np.ix_(active > 0, active > 0)][~np.eye(int(active.sum()), dtype=bool)]
    out["corr_margin"] = float(np.min(np.abs(off_diag - fed["threshold"])))
    W = np.zeros((K, K), np.float64)
    new_w = np.zeros(K, np.float32)
    new_active = np.zeros(K, np.float32)
    for g in groups:
        W[g[0], g] = 1.0 / len(g)
        new_w[g[0]] = weights[g].sum()
        new_active[g[0]] = 1.0
    for i in alone:
        W[i, i] = 1.0
        new_w[i] = weights[i]
        new_active[i] = 1.0
    c_l = jax.tree_util.tree_map(
        lambda a: np.tensordot(W, np.asarray(a, np.float64), axes=1), jax.device_get(c_l))
    out["merge"] = {"params": jax.device_get(x_g), "c_global": jax.device_get(c_g),
                    "c_locals": c_l, "weights": new_w, "active": new_active,
                    "groups": [list(map(int, g)) for g in groups]}
    return out
