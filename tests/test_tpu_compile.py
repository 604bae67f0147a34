"""Compile the main path's Pallas kernels for a described TPU v5e, with no
chip attached: the Pearson kernel on the CNN's leaf shapes at K=10 and on
a K=1024 chunk, the serving attention kernels at qwen3-1.7b widths in
bf16, and the paged engine's whole decode step around them. A compile
that passes is not a run; these tests catch what the chip's compiler
refuses (VMEM overruns, unaligned tiles, kernels XLA cannot partition,
pool-sized copies) before any chip time is spent.

The topology is described inside module-scoped fixtures, never while a
module is imported: only one process may load the TPU library, and only
the worker that is given this file does."""
import dataclasses
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
from repro.models import model as M
from repro.serving.engine import _paged_step

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


# instructions that name a buffer they were given: no array of their own
_ALIASING = {"parameter", "tuple", "get-tuple-element", "bitcast", "while"}
# updates that XLA makes in place, or else precedes with an explicit copy
_IN_PLACE = {"dynamic-update-slice", "scatter"}
_INSTRUCTION = re.compile(r"^\s*(ROOT )?(%\S+) = (.*?) ([\w-]+)\(([^)]*)\)(.*)$")


def _made_arrays(text, count):
    """The instructions of a compiled program, fused ones included, that
    make an array of ``count`` elements. Left out are the instructions
    that only name a buffer (``_ALIASING``) and in-place updates: an
    ``_IN_PLACE`` instruction, a bitcast of one, and each output of a
    fusion whose root computes it so. Where XLA cannot update a buffer in
    place, it copies it first, and the copy is an instruction that makes
    the array. A fusion is judged by what it outputs, whatever its root."""
    found, roots, computation = [], {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?(%\S+) .*\{$", line)
        m = _INSTRUCTION.match(line)
        if head:
            computation = head.group(1)
        elif m:
            found.append(m)
            if m.group(1):
                roots[computation] = m.group(2)
    by_name = {m.group(2): m for m in found}

    def in_place(name):
        m = by_name[name]
        return m.group(4) in _IN_PLACE or (
            m.group(4) == "bitcast" and in_place(m.group(5).split(",")[0]))

    def sizes(shape):
        return [np.prod([int(d) for d in dims.split(",") if d])
                for dims in re.findall(r"\w+\[([\d,]*)\]", shape)]

    made = []
    for m in found:
        name, shape, op = m.group(2), m.group(3), m.group(4)
        if op == "fusion":
            root = by_name[roots[re.search(r"calls=(%[\w.-]+)",
                                           m.group(6)).group(1)]]
            outputs = (re.findall(r"%[\w.-]+", root.group(5))
                       if root.group(4) == "tuple" else [root.group(2)])
            made += [name for n, out in zip(sizes(shape), outputs)
                     if n == count and not in_place(out)]
        elif op not in _ALIASING and not in_place(name):
            made += [name for n in sizes(shape) if n == count]
    return made


# A fused program in miniature: a pool updated in place (a scatter, and a
# bitcast of a dynamic-update-slice), beside copies of it that a bitcast
# or a tuple root hides.
_POOLS_HLO = """\
HloModule pools

%in_place (param_0: bf16[4,8], param_1: bf16[1,8], param_2: s32[]) -> bf16[32] {
  %param_0 = bf16[4,8]{1,0} parameter(0)
  %param_1 = bf16[1,8]{1,0} parameter(1)
  %param_2 = s32[] parameter(2)
  %dynamic-update-slice.1 = bf16[4,8]{1,0} dynamic-update-slice(%param_0, %param_1, %param_2, %param_2)
  ROOT %bitcast.1 = bf16[32]{0} bitcast(%dynamic-update-slice.1)
}

%copy_bitcast (param_0: bf16[4,8]) -> bf16[32] {
  %param_0 = bf16[4,8]{1,0} parameter(0)
  %transpose.1 = bf16[8,4]{1,0} transpose(%param_0), dimensions={1,0}
  ROOT %bitcast.2 = bf16[32]{0} bitcast(%transpose.1)
}

%update_and_copy (param_0: bf16[4,8], param_1: bf16[4,8], param_2: bf16[1,8], param_3: s32[]) -> (bf16[4,8], bf16[4,8]) {
  %param_0 = bf16[4,8]{1,0} parameter(0)
  %param_1 = bf16[4,8]{1,0} parameter(1)
  %param_2 = bf16[1,8]{1,0} parameter(2)
  %param_3 = s32[] parameter(3)
  %dynamic-update-slice.2 = bf16[4,8]{1,0} dynamic-update-slice(%param_0, %param_2, %param_3, %param_3)
  %negate.1 = bf16[4,8]{1,0} negate(%param_1)
  ROOT %tuple.1 = (bf16[4,8]{1,0}, bf16[4,8]{1,0}) tuple(%dynamic-update-slice.2, %negate.1)
}

ENTRY %main (k: bf16[4,8], v: bf16[4,8], new: bf16[1,8], at: s32[], idx: s32[1,1]) -> (bf16[32], bf16[32], bf16[4,8], bf16[4,8]) {
  %k = bf16[4,8]{1,0} parameter(0)
  %v = bf16[4,8]{1,0} parameter(1)
  %new = bf16[1,8]{1,0} parameter(2)
  %at = s32[] parameter(3)
  %idx = s32[1,1]{1,0} parameter(4)
  %scatter.1 = bf16[4,8]{1,0} scatter(%k, %idx, %new), update_window_dims={1}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%add
  %fusion.1 = bf16[32]{0} fusion(%scatter.1, %new, %at), kind=kLoop, calls=%in_place
  %fusion.2 = bf16[32]{0} fusion(%v), kind=kLoop, calls=%copy_bitcast
  %fusion.3 = (bf16[4,8]{1,0}, bf16[4,8]{1,0}) fusion(%k, %v, %new, %at), kind=kLoop, calls=%update_and_copy
  %get-tuple-element.1 = bf16[4,8]{1,0} get-tuple-element(%fusion.3), index=0
  %get-tuple-element.2 = bf16[4,8]{1,0} get-tuple-element(%fusion.3), index=1
  ROOT %tuple.2 = (bf16[32]{0}, bf16[32]{0}, bf16[4,8]{1,0}, bf16[4,8]{1,0}) tuple(%fusion.1, %fusion.2, %get-tuple-element.1, %get-tuple-element.2)
}
"""


def test_made_arrays_sees_copies_behind_bitcast_and_tuple_roots():
    """The pool-sized arrays a program makes are its copies, however the
    fusion that makes one ends: the bitcast-rooted copy fusion and the
    copying output of the tuple-rooted one are reported, with the copies
    inside them; the in-place updates are not."""
    assert _made_arrays(_POOLS_HLO, 32) == [
        "%transpose.1", "%negate.1", "%fusion.2", "%fusion.3"]
    assert _made_arrays(_POOLS_HLO, 8) == []


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


def test_paged_serving_step_qwen_updates_pools_in_place(one_chip):
    """The paged engine's whole decode step, at the chat cell's arena: 28
    layers at Qwen3-1.7B's widths in bf16, 32 slots x 1024 positions in
    pages of 16, a bucket of 8 rows. The layer loop carries the stacked
    pools and each layer's kernel reads its own in place: no instruction
    makes an array of one layer's pool or of the stack, no scratch buffer
    holds one, and the pools leave the step in the donated arena's
    buffers."""
    cfg = dataclasses.replace(QWEN, kv_layout="paged", kv_block_size=16,
                              decode_attn_backend="pallas")
    slots, cap, page, rows, view = 32, 1024, 16, 8, 64
    pages = slots * cap // page
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
    params = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0)))
    arena = tuple(
        {name: on_chip(st[name]) for name in ("k", "v", "length")}
        for st in jax.eval_shape(lambda: M.init_decode_paged(
            cfg, slots, slots * cap, page, pages)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    compiled = _paged_step(cfg, rows, view).lower(
        params, arena, i32(rows), i32(rows), i32(rows),
        i32(rows, view)).compile()
    text = compiled.as_text()
    assert re.search(r"%paged_decode_attn(\.\d+)? = .*custom-call", text)

    layer_pool = (pages + 1) * page * cfg.num_kv_heads * cfg.head_dim
    assert arena[0]["k"].shape == (cfg.num_layers, pages + 1, page,
                                   cfg.num_kv_heads, cfg.head_dim)
    assert _made_arrays(text, layer_pool) == []
    assert _made_arrays(text, cfg.num_layers * layer_pool) == []
    mem = compiled.memory_analysis()
    pool_bytes = cfg.num_layers * layer_pool * 2
    assert mem.temp_size_in_bytes < layer_pool * 2
    assert mem.alias_size_in_bytes >= 2 * pool_bytes


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
