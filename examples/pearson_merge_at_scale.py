"""The paper's technique at pod scale: stream (reduced) LLM clients'
stacked parameter trees leaf-by-leaf through the Pearson kernel, build the
merge plan, and apply it to the stacked client states on device — the
exact code path the multi-pod federation uses across the 'pod' mesh axis.
No (K, M) concatenation and no host round-trip: only the K x K correlation
ever leaves the device.

  PYTHONPATH=src python examples/pearson_merge_at_scale.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import apply_merge_device, build_merge_plan, pearson_tree
from repro.models import init_params
from repro.utils import tree_size


def main():
    cfg = get_config("qwen3-1.7b").reduced()
    K = 6  # six pod-clients
    keys = jax.random.split(jax.random.PRNGKey(0), K)

    # clients 0-2 share a basin (same init + small noise); 3-5 independent
    base = init_params(keys[0], cfg)
    clients = []
    for i in range(K):
        if i < 3:
            p = jax.tree_util.tree_map(
                lambda x, k=keys[i]: x + 0.01 * jax.random.normal(k, x.shape, x.dtype),
                base,
            )
        else:
            p = init_params(keys[i], cfg)
        clients.append(p)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *clients)
    print(f"{K} clients x {tree_size(base):,} params each")

    # the paper's step 1: K x K Pearson matrix, streamed per leaf through
    # the Pallas kernel (bf16 read, f32 accumulate — one HBM pass)
    corr = np.asarray(
        pearson_tree(stacked, compute_dtype=jnp.bfloat16, use_kernel=True)
    )
    print("correlation matrix:\n", corr.round(3))

    # step 2: greedy grouping + merge matrix
    plan = build_merge_plan(corr, data_sizes=[1] * K, threshold=0.7, max_group_size=3)
    print("groups:", plan.groups, "unmerged:", plan.unmerged)

    # step 3: merge client states on device, buffers donated (params shown;
    # controls merge identically)
    merged = apply_merge_device(plan, stacked)
    print("active nodes:", int(plan.active.sum()), "of", K,
          f"-> cross-pod updates per round drop {K}->{int(plan.active.sum())}")


if __name__ == "__main__":
    main()
