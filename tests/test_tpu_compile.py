"""Compile the main path's Pallas kernels for a described TPU v5e, with no
chip attached: the Pearson kernel on the CNN's leaf shapes at K=10 and on
a K=1024 chunk, and the serving attention kernels at qwen3-1.7b widths in
bf16. A compile that passes is not a run; these tests catch what the
chip's compiler refuses (VMEM overruns, unaligned tiles, kernels XLA
cannot partition) before any chip time is spent.

The topology is described inside module-scoped fixtures, never while a
module is imported: only one process may load the TPU library, and only
the worker that is given this file does."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import cnn_mnist, get_config
from repro.core.pearson import pearson_tree
from repro.kernels.decode_attn.ops import decode_attention, paged_decode_attention
from repro.kernels.flash_prefill.ops import flash_prefill_attention
from repro.kernels.pearson.ops import pearson_chunk
from repro.models import cnn_init

QWEN = get_config("qwen3-1.7b")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_kernel(name, fn, *args):
    """Compile ``fn`` for the described chip; assert the Pallas kernel is
    in the program as a compiled custom call (not interpreted) under its
    stable ``name``, the name the device trace gives it. JAX still runs
    on the CPU here, so each test forces the compiled kernel."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{name}(\.\d+)? = .*custom-call", text), name
    return text


def _made_arrays(text, count):
    """The instructions of a compiled program, fused ones included, that
    are not parameters and make an array of ``count`` elements."""
    made = []
    for m in re.finditer(r"^\s*(?:ROOT )?(%\S+) = (.*?) ([\w-]+)\(", text, re.M):
        if m.group(3) == "parameter":
            continue
        for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(2)):
            if np.prod([int(d) for d in dims.split(",") if d]) == count:
                made.append(m.group(1))
    return made


def _cnn_clients(K, sharding_of):
    params = jax.eval_shape(lambda k: cnn_init(k, cnn_mnist.config()),
                            jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((K,) + a.shape, a.dtype,
                                       sharding=sharding_of(a.ndim + 1)),
        params)


def test_pearson_tree_cnn_k10(one_chip):
    stacked = _cnn_clients(10, lambda nd: one_chip)
    _compiled_kernel(
        "pearson_gram", lambda x: pearson_tree(x, use_kernel=True, interpret=False), stacked)


def test_pearson_tree_cnn_pod_sharded(topo):
    """The pod-sharded engine's similarity program: the kernel runs inside
    a shard_map over the 4-chip mesh (XLA cannot partition it)."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("pod",))
    stacked = _cnn_clients(
        8, lambda nd: NamedSharding(mesh, P("pod", *([None] * (nd - 1)))))
    _compiled_kernel(
        "pearson_gram", lambda x: pearson_tree(x, use_kernel=True, interpret=False,
                               mesh=mesh),
        stacked)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pearson_chunk_k1024(one_chip, dtype):
    x = jax.ShapeDtypeStruct((1024, 1 << 16), dtype, sharding=one_chip)
    _compiled_kernel("pearson_gram",
                     lambda v: pearson_chunk(v, interpret=False), x)


def test_decode_attention_qwen(one_chip):
    B, S, D = 8, 2048, QWEN.head_dim
    bf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    _compiled_kernel(
        "decode_attn", lambda q, k, v, n: decode_attention(q, k, v, n, backend="pallas"),
        bf(B, QWEN.num_heads, D), bf(B, S, QWEN.num_kv_heads, D),
        bf(B, S, QWEN.num_kv_heads, D), lengths)


@pytest.mark.parametrize("page", [8, 16])
def test_paged_decode_attention_qwen(one_chip, page):
    B, D, cap = 8, QWEN.head_dim, 1024
    pages = B * cap // page + 1  # the serving pool plus its trash page
    bf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    pool = (pages, page, QWEN.num_kv_heads, D)
    text = _compiled_kernel(
        "paged_decode_attn", lambda q, k, v, bt, n: paged_decode_attention(q, k, v, bt, n,
                                                      backend="pallas"),
        bf(B, QWEN.num_heads, D), bf(*pool), bf(*pool), i32(B, cap // page), i32(B))
    # the kernel reads the pools where they lie: no copy, transpose or pad
    assert _made_arrays(text, np.prod(pool)) == []


@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m", "recurrentgemma-2b"])
def test_paged_decode_attention_untiled_pages(one_chip, arch):
    """Pages that are not whole tiles of the HBM layout (64-wide heads; one
    bf16 KV head) reach the kernel through its grid pipeline."""
    cfg = get_config(arch)
    B, D, cap, page = 8, cfg.head_dim, 1024, 16
    pool = (B * cap // page + 1, page, cfg.num_kv_heads, D)
    bf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    _compiled_kernel(
        "paged_decode_attn", lambda q, k, v, bt, n: paged_decode_attention(q, k, v, bt, n,
                                                      backend="pallas"),
        bf(B, cfg.num_heads, D), bf(*pool), bf(*pool), i32(B, cap // page), i32(B))


def test_flash_prefill_attention_qwen(one_chip):
    S, D = 512, QWEN.head_dim
    bf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    _compiled_kernel(
        "flash_prefill", lambda q, k, v: flash_prefill_attention(q, k, v, interpret=False),
        bf(1, S, QWEN.num_heads, D),
        bf(1, S, QWEN.num_kv_heads, D), bf(1, S, QWEN.num_kv_heads, D))
