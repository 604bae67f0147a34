"""Paged KV arena (ISSUE 10): block-table attention from the kernel to the
serving engine must be token-for-token identical to the contiguous layouts.

Four layers of checks:

  * ``serving/paging.BlockAllocator`` unit invariants — exhaustion,
    reuse-after-evict, reservation rollback, double-free detection.
  * Layer level: ``models/layers.attention_decode`` over a paged cache
    (pool + fragmented block table) matches the contiguous ring cache.
  * Kernel level: the block-table Pallas kernel (interpret mode) matches
    the pure-jnp paged reference on fragmented tables.
  * Engine level: the paged engine == contiguous batched == sequential
    ``generate`` oracle for arbitrary request mixes, block sizes and
    fragmented free lists — including across a merge-round hot swap, with
    eviction poisoning on, and through pool exhaustion + over-capacity
    admission (the capacity win contiguous slots cannot express).

Plus the checkpoint-arrival machinery: manifest round-trip through
``CheckpointWatcher`` and the checkpoint-to-adoption latency stamp.
"""
import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.kernels.decode_attn.decode_attn import page_fetches
from repro.kernels.decode_attn.ops import paged_decode_attention
from repro.kernels.decode_attn.ref import (
    gather_paged_kv,
    paged_decode_attention_ref,
)
from repro.launch.serve import generate
from repro.models import blocks
from repro.models import layers as L
from repro.models import model as M
from repro.serving.engine import ServeEngine
from repro.serving.paging import BlockAllocator
from repro.serving.swap import (
    CheckpointWatcher,
    MergeCheckpoint,
    SwapReport,
    write_checkpoint_manifest,
)
from repro.serving.fl_model import serve_config
from repro.serving.traffic import Request

CAP = 16
ARCHS = ("qwen3-1.7b", "xlstm-125m")
BLOCK_SIZES = (1, 4, 16)


@functools.lru_cache(maxsize=4)
def _cfg_params(arch: str):
    cfg = serve_config(arch)
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------


def test_allocator_exhaustion_and_reuse():
    a = BlockAllocator(4)
    assert a.free_blocks() == 4 and a.available() == 4
    assert a.reserve(4)
    got = [a.alloc() for _ in range(4)]
    assert sorted(got) == [0, 1, 2, 3]
    assert a.free_blocks() == 0 and a.available() == 0
    assert not a.reserve(1)  # exhausted
    with pytest.raises(RuntimeError):
        a.alloc()  # nothing free and nothing reserved
    a.free(got[:2])  # evict two blocks
    assert a.free_blocks() == 2 and a.available() == 2
    assert a.reserve(2)
    reused = [a.alloc(), a.alloc()]
    assert set(reused) == set(got[:2])  # reuse-after-evict
    a.free(reused + got[2:])
    assert a.free_blocks() == 4 and a.reserved == 0


def test_allocator_reservation_rollback():
    a = BlockAllocator(8)
    assert a.reserve(5)
    assert a.available() == 3
    assert not a.reserve(4)  # over the unreserved remainder
    a.release(5)  # admission failed downstream: full rollback
    assert a.available() == 8 and a.reserved == 0
    assert a.reserve(8)


def test_allocator_double_free_raises():
    a = BlockAllocator(2)
    a.reserve(1)
    b = a.alloc()
    a.free([b])
    with pytest.raises(ValueError):
        a.free([b])
    with pytest.raises(ValueError):
        a.free([99])


def test_allocator_alloc_requires_reservation():
    a = BlockAllocator(2)
    with pytest.raises(RuntimeError):
        a.alloc()


# ---------------------------------------------------------------------------
# layer level: paged attention_decode == contiguous ring cache
# ---------------------------------------------------------------------------


def _paged_layer_case(window: int, bs: int, seed: int):
    cfg = serve_config("qwen3-1.7b")
    if window:
        cfg = dataclasses.replace(cfg, window_size=window)
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    p = L.attention_init(key, cfg, jnp.float32)
    B, max_len = 4, 12
    # non-windowed rows stay < max_len (the engine evicts at capacity
    # before a full row ever decodes); windowed rows may wrap the ring
    deepest = max_len if window else max_len - 1
    lengths = np.asarray([1, max_len // 2, deepest - 2, deepest], np.int32)

    ccache = L.attention_init_cache(cfg, B, max_len, jnp.float32)
    C = ccache["k"].shape[1]
    ccache["k"] = jnp.asarray(
        rng.normal(size=ccache["k"].shape).astype(np.float32))
    ccache["v"] = jnp.asarray(
        rng.normal(size=ccache["v"].shape).astype(np.float32))
    ccache["length"] = jnp.asarray(lengths)

    # paged mirror: same logical slots, pages dealt from a SHUFFLED id
    # space so the table is maximally fragmented
    T = -(-C // bs)
    pcache = L.attention_init_cache_paged(cfg, B, max_len, jnp.float32,
                                          bs, B * T)
    ids = rng.permutation(B * T).reshape(B, T).astype(np.int32)
    k_pool = np.zeros(pcache["k"].shape, np.float32)
    v_pool = np.zeros(pcache["v"].shape, np.float32)
    ck = np.asarray(ccache["k"])
    cv = np.asarray(ccache["v"])
    for b in range(B):
        for s in range(C):
            k_pool[ids[b, s // bs], s % bs] = ck[b, s]
            v_pool[ids[b, s // bs], s % bs] = cv[b, s]
    pcache["k"] = jnp.asarray(k_pool)[None]  # a run of one layer
    pcache["v"] = jnp.asarray(v_pool)[None]
    pcache["block_tables"] = jnp.asarray(ids)
    pcache["length"] = jnp.asarray(lengths)
    pcache["layer"] = jnp.int32(0)

    x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)).astype(np.float32))
    pos = jnp.asarray(lengths)

    yc, nc = L.attention_decode(p, cfg, x, ccache, pos)
    yp, np_ = L.attention_decode(p, cfg, x, pcache, pos)
    # W = T * bs may exceed C by page rounding: the extra columns are
    # exactly masked, but reduction widths differ -> tight allclose
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yc),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(np_["length"]),
                                  np.asarray(nc["length"]))
    # the written-through pool holds the same logical cache
    gk, _gv = gather_paged_kv(np_["k"][0], np_["v"][0],
                              np_["block_tables"])
    np.testing.assert_allclose(np.asarray(gk)[:, :C], np.asarray(nc["k"]),
                               rtol=0, atol=0)


@pytest.mark.parametrize("bs", BLOCK_SIZES)
def test_paged_attention_decode_full(bs):
    _paged_layer_case(window=0, bs=bs, seed=bs)


@pytest.mark.parametrize("bs", BLOCK_SIZES)
def test_paged_attention_decode_windowed(bs):
    # window < max_len: the ring-buffer path over the paged pool
    _paged_layer_case(window=8, bs=bs, seed=100 + bs)


# ---------------------------------------------------------------------------
# kernel level: interpret-mode Pallas vs the jnp paged reference
# ---------------------------------------------------------------------------


def _round_robin_case():
    """Pages dealt round-robin, with unallocated tails on two rows."""
    B, T, bs = 4, 4, 4
    bt = np.arange(B * T).reshape(T, B).T.astype(np.int32).copy()
    bt[0, 3] = -1  # row 0: only 3 pages live
    bt[2, 2:] = -1  # row 2: only 2 pages live
    return bt, [bs * 3, bs * T, bs * 2 - 1, 1]


# (G, Kv, D, bs, T, lengths, dtype, layer); the kernel takes ``ppb`` whole
# pages a grid step (ops.pages_per_block), and each case names where its
# lengths fall against that block: 0 (a dead row), 1, a block edge, the
# full table. Pages of 128-wide heads are copied by the kernel itself, the
# others through the grid pipeline (decode_attn.page_is_tiled): both are
# here. ``layer`` None is one layer's pools (P, bs, Kv, D); else the pools
# are three layers' stacked (3, P, bs, Kv, D), as the layer loop carries
# them, and the kernel reads ``layer`` in place, first, middle and last.
# Every layer holds different data, so reading the wrong one fails.
PAGED_KERNEL_CASES = [
    pytest.param(4, 2, 64, 4, 4, "round-robin", jnp.float32, None,
                 id="4-2-64-4-4-round-robin-float32"),
    pytest.param(1, 16, 128, 16, 7, [0, 1, 2 * 2 * 16, 7 * 16],
                 jnp.float32, None,                                  # ppb 2
                 id="1-16-128-16-7-lengths1-float32"),
    pytest.param(2, 8, 128, 16, 20, [8 * 16, 20 * 16, 17, 0],
                 jnp.bfloat16, None,                                 # ppb 8
                 id="2-8-128-16-20-lengths2-bfloat16"),
    pytest.param(7, 4, 80, 8, 30, [25 * 8, 30 * 8, 1, 97],
                 jnp.float32, None,                                  # ppb 25
                 id="7-4-80-8-30-lengths3-float32"),
    pytest.param(12, 1, 64, 4, 6, [6 * 4, 1, 0, 13],
                 jnp.float32, None,                                  # ppb = T
                 id="12-1-64-4-6-lengths4-float32"),
    pytest.param(1, 16, 128, 16, 10, [4 * 16, 1, 10 * 16],
                 jnp.bfloat16, None,                                 # ppb 4
                 id="1-16-128-16-10-lengths5-bfloat16"),
    pytest.param(12, 4, 64, 8, 9, [9 * 8, 0, 8],
                 jnp.bfloat16, None,                                 # ppb = T
                 id="12-4-64-8-9-lengths6-bfloat16"),
    pytest.param(2, 16, 64, 16, 12, [8 * 16, 12 * 16, 0, 33],
                 jnp.bfloat16, None,                                 # ppb 8
                 id="2-16-64-16-12-lengths7-bfloat16"),
    pytest.param(2, 8, 128, 16, 20, [8 * 16, 20 * 16, 17, 0],
                 jnp.bfloat16, 0,                          # the kernel's copies
                 id="stacked3-layer0-2-8-128-16-20-lengths2-bfloat16"),
    pytest.param(4, 2, 64, 4, 4, "round-robin", jnp.float32, 1,  # the pipeline
                 id="stacked3-layer1-4-2-64-4-4-round-robin-float32"),
    pytest.param(1, 16, 128, 16, 10, [4 * 16, 1, 10 * 16],
                 jnp.bfloat16, 2,                          # the kernel's copies
                 id="stacked3-layer2-1-16-128-16-10-lengths5-bfloat16"),
    pytest.param(12, 4, 64, 8, 9, [9 * 8, 0, 8], jnp.bfloat16, 2,  # the pipeline
                 id="stacked3-layer2-12-4-64-8-9-lengths6-bfloat16"),
]


@pytest.mark.parametrize("G,Kv,D,bs,T,lengths,dtype,layer",
                         PAGED_KERNEL_CASES)
def test_paged_kernel_matches_reference_fragmented(G, Kv, D, bs, T, lengths,
                                                   dtype, layer):
    rng = np.random.default_rng(7)
    if lengths == "round-robin":
        bt, lengths = _round_robin_case()
    else:
        # fragmented: a shuffled page-id space, -1 past each row's live pages
        bt = rng.permutation(len(lengths) * T).reshape(-1, T).astype(np.int32)
        for b, n in enumerate(lengths):
            bt[b, -(-n // bs):] = -1
    B = len(lengths)
    P = B * T + 1
    normal = lambda *s: jnp.asarray(rng.normal(size=s), dtype)
    q = normal(B, G * Kv, D)
    stack = () if layer is None else (3,)
    k_pool, v_pool = normal(*stack, P, bs, Kv, D), normal(*stack, P, bs, Kv, D)
    lengths = jnp.asarray(lengths, jnp.int32)
    bt = jnp.asarray(bt)

    kw = {} if layer is None else {"layer": jnp.int32(layer)}
    got = np.asarray(paged_decode_attention(q, k_pool, v_pool, bt, lengths,
                                            backend="interpret", **kw),
                     np.float32)
    if layer is not None:  # the reference reads the one layer's pools
        k_pool, v_pool = k_pool[layer], v_pool[layer]
    if dtype == jnp.float32:
        want = paged_decode_attention_ref(q, k_pool, v_pool, bt, lengths)
        rtol = 2e-5
    else:
        # Pools are read exactly into f32 on both sides. The kernel attends
        # with the query pre-scaled by 1/sqrt(D) and rounded to bf16, so the
        # reference gets that same query in f32; what differs then is the
        # f32 summation order (the f32 tolerance) and the kernel's final
        # rounding of its f32 output to bf16, at most half a bf16 ulp:
        # 2^-8 of the value.
        seen = (q * (1.0 / np.sqrt(D))).astype(dtype).astype(jnp.float32)
        want = paged_decode_attention_ref(seen * np.float32(np.sqrt(D)),
                                          k_pool, v_pool, bt, lengths)
        rtol = 2e-5 + 2.0 ** -8
    want = np.asarray(want, np.float32)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], rtol=rtol, atol=2e-5)
    # a dead row finalizes to zeros, never NaN
    assert (got[~live] == 0).all()


def test_carried_pool_write_touches_only_its_layer():
    """The layer loop carries every layer's pools stacked, and layer ``l``
    writes its token through one scatter a pool at ``[l, page, offset]``:
    every other layer's pages, and every other slot of its own, stay
    bit-identical, and the layer attends exactly as over its own pool
    alone, a run of one layer. A dead row's all -1 table sends its write
    to the trash page."""
    cfg = serve_config("qwen3-1.7b")
    rng = np.random.default_rng(11)
    p = L.attention_init(jax.random.PRNGKey(11), cfg, jnp.float32)
    n_layers, B, bs, T = 3, 4, 4, 3
    trash = B * T
    pool_shape = (n_layers, trash + 1, bs, cfg.num_kv_heads, cfg.head_dim)
    k_pool = rng.normal(size=pool_shape).astype(np.float32)
    v_pool = rng.normal(size=pool_shape).astype(np.float32)
    ids = rng.permutation(B * T).reshape(B, T).astype(np.int32)
    ids[3] = -1                                   # a dead lane
    lengths = np.asarray([0, 5, T * bs - 1, 6], np.int32)
    x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)).astype(np.float32))
    pos = jnp.asarray(lengths)

    for layer in range(n_layers):
        cache = {"k": jnp.asarray(k_pool), "v": jnp.asarray(v_pool),
                 "block_tables": jnp.asarray(ids),
                 "length": jnp.asarray(lengths), "layer": jnp.int32(layer)}
        y, new = L.attention_decode(p, cfg, x, cache, pos)
        alone = {"k": jnp.asarray(k_pool[layer:layer + 1]),
                 "v": jnp.asarray(v_pool[layer:layer + 1]),
                 "block_tables": jnp.asarray(ids),
                 "length": jnp.asarray(lengths), "layer": jnp.int32(0)}
        y_alone, new_alone = L.attention_decode(p, cfg, x, alone, pos)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_alone))
        np.testing.assert_array_equal(np.asarray(new["length"]), lengths + 1)

        written = np.zeros(pool_shape[:3], bool)
        for b, n in enumerate(lengths):
            page = ids[b, n // bs]
            written[layer, page if page >= 0 else trash, n % bs] = True
        for name, pool in (("k", k_pool), ("v", v_pool)):
            got = np.asarray(new[name])
            np.testing.assert_array_equal(got[~written], pool[~written])
            np.testing.assert_array_equal(
                got[layer], np.asarray(new_alone[name])[0])
            assert not np.array_equal(got[written], pool[written])


def test_page_fetches_stay_in_the_pool():
    """Every page the kernel's grid fetches is a pool page. A dead row of
    the engine's padded batch keeps the stale position of the row that
    left it and an all -1 table; the chip's DMA bounds check halts the
    core on a page index outside the pool, where interpret mode clamps."""
    bs, ppb = 4, 3
    bt = np.array([[5, 2, 7, 1, 0, 0, 0],
                   [-1] * 7,                  # dead row, stale length
                   [3, 6, -1, -1, -1, -1, -1]], np.int32)
    lengths = jnp.asarray([4 * bs, 6 * bs + 1, 2 * bs - 1], jnp.int32)
    pages = np.asarray(page_fetches(jnp.asarray(bt), lengths, bs, ppb))
    assert pages.shape == (3, 9)
    assert (pages >= 0).all()
    # a live slot fetches its table entry; a slot past the row's length,
    # or past the table's end, repeats what it fetched last
    np.testing.assert_array_equal(pages[0], [5, 2, 7, 1, 2, 7, 1, 2, 7])
    np.testing.assert_array_equal(pages[2, :3], [3, 6, 0])


# ---------------------------------------------------------------------------
# engine level: paged == contiguous batched == generate oracle
# ---------------------------------------------------------------------------


def _drive(cfg, params, reqs, stagger, kv_layout, block_size=4,
           shuffle_seed=None, debug_poison=False):
    """Admit ``reqs`` into a 4-slot engine as slots free up and collect
    every request's token stream. ``shuffle_seed`` pre-fragments the paged
    allocator's free list so block tables are never contiguous."""
    kw = {}
    if kv_layout == "paged":
        kw = {"kv_layout": "paged", "block_size": block_size,
              "debug_poison_evictions": debug_poison}
    eng = ServeEngine(params, cfg, num_slots=4, capacity=CAP, **kw)
    if kv_layout == "paged" and shuffle_seed is not None:
        np.random.default_rng(shuffle_seed).shuffle(eng.allocator._free)
    queue = list(reqs)
    out = {}

    def admit_all():
        while queue and eng.free_slots():
            a = eng.try_admit(queue[0])
            if a is None:
                break  # paged pool exhausted: wait for an eviction
            queue.pop(0)
            if a.done:
                out[a.request.rid] = a.tokens

    admit_all()
    for _ in range(stagger):
        for fin in eng.step():
            out[fin.request.rid] = fin.tokens
    while queue or eng.num_active:
        admit_all()
        for fin in eng.step():
            out[fin.request.rid] = fin.tokens
    if kv_layout == "paged":
        # every page back on the free list, every promise returned
        assert eng.allocator.free_blocks() == eng.pool_blocks
        assert eng.allocator.reserved == 0
    return out


@settings(max_examples=6, deadline=None)
@given(
    arch_i=st.integers(0, len(ARCHS) - 1),
    bs_i=st.integers(0, len(BLOCK_SIZES) - 1),
    seed=st.integers(0, 2**16),
    n_req=st.integers(1, 6),
    stagger=st.integers(0, 3),
)
def test_paged_equals_batched_equals_oracle(arch_i, bs_i, seed, n_req,
                                            stagger):
    """The property: for arbitrary request mixes, block sizes and
    fragmented free lists, the paged engine, the contiguous batched
    engine and the sequential oracle emit identical tokens per request."""
    cfg, params = _cfg_params(ARCHS[arch_i])
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_req):
        L_p = int(rng.integers(1, 9))
        max_new = int(rng.integers(1, min(7, CAP - L_p + 1)))
        prompt = rng.integers(0, cfg.vocab_size, L_p).astype(np.int32)
        reqs.append(Request(rid=i, client_id=0, prompt=prompt,
                            max_new_tokens=max_new))

    batched = _drive(cfg, params, reqs, stagger, "contiguous")
    paged = _drive(cfg, params, reqs, stagger, "paged",
                   block_size=BLOCK_SIZES[bs_i], shuffle_seed=seed)
    assert batched == paged
    for r in reqs:
        toks, _ = generate(params, cfg, {"tokens": r.prompt[None]},
                           max_new_tokens=r.max_new_tokens, capacity=CAP)
        got = paged[r.rid]
        assert got == list(np.asarray(toks[0][:len(got)])), (
            f"rid {r.rid} diverges from the sequential oracle"
        )


def test_paged_windowed_arch_parity():
    cfg, _ = _cfg_params("qwen3-1.7b")
    cfg = dataclasses.replace(cfg, window_size=8)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, client_id=0,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(2, 9))
                                        ).astype(np.int32),
                    max_new_tokens=int(rng.integers(2, 7)))
            for i in range(5)]
    batched = _drive(cfg, params, reqs, 1, "contiguous")
    paged = _drive(cfg, params, reqs, 1, "paged", block_size=4,
                   shuffle_seed=5)
    assert batched == paged


def test_paged_hybrid_arch_parity():
    """A hybrid stack: attention runs of two layers between recurrent
    runs, each attention run carrying its own stacked pools through its
    layer loop."""
    cfg, _ = _cfg_params("recurrentgemma-2b")
    cfg = dataclasses.replace(cfg, num_layers=6,
                              block_pattern=("rglru", "attn", "attn"))
    assert blocks.runs(cfg) == [("rglru", 1), ("attn", 2)] * 2
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(9)
    reqs = [Request(rid=i, client_id=0,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(2, 9))
                                        ).astype(np.int32),
                    max_new_tokens=int(rng.integers(2, 7)))
            for i in range(4)]
    batched = _drive(cfg, params, reqs, 1, "contiguous")
    paged = _drive(cfg, params, reqs, 1, "paged", block_size=4,
                   shuffle_seed=9)
    assert batched == paged


def test_paged_poison_evictions_invisible():
    """Debug poison fills every evicted page with POISON_VALUE; if any
    step read a poisoned (or stale-but-masked) slot with nonzero weight,
    the token streams would diverge from the unpoisoned run."""
    cfg, params = _cfg_params("qwen3-1.7b")
    rng = np.random.default_rng(11)
    reqs = [Request(rid=i, client_id=0,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(2, 9))
                                        ).astype(np.int32),
                    max_new_tokens=int(rng.integers(1, 7)))
            for i in range(8)]
    plain = _drive(cfg, params, reqs, 2, "paged", block_size=4,
                   shuffle_seed=1)
    poisoned = _drive(cfg, params, reqs, 2, "paged", block_size=4,
                      shuffle_seed=1, debug_poison=True)
    assert plain == poisoned


def test_paged_parity_across_hot_swap():
    """Mixed depths + a weight hot-swap mid-flight: paged and contiguous
    agree token-for-token through the swap, and a post-swap admission
    matches the sequential oracle on the new weights."""
    cfg, params = _cfg_params("qwen3-1.7b")
    p_new = M.init_params(jax.random.PRNGKey(9), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (3, 7, 5)]

    def run(layout):
        kw = ({"kv_layout": "paged", "block_size": 4}
              if layout == "paged" else {})
        eng = ServeEngine(params, cfg, num_slots=4, capacity=CAP, **kw)
        a = eng.try_admit(Request(rid=0, client_id=0, prompt=prompts[0],
                                  max_new_tokens=9))
        eng.step()
        eng.step()
        b = eng.try_admit(Request(rid=1, client_id=0, prompt=prompts[1],
                                  max_new_tokens=5))
        eng.step()
        eng.swap_params(p_new)  # mixed occupancy, mixed depths, swap
        c = eng.try_admit(Request(rid=2, client_id=0, prompt=prompts[2],
                                  max_new_tokens=4))
        eng.run_to_completion()
        assert len(a.tokens) == 9 and len(b.tokens) == 5
        return [a.tokens, b.tokens, c.tokens]

    assert run("contiguous") == run("paged")
    toks, _ = generate(p_new, cfg, {"tokens": prompts[2][None]},
                       max_new_tokens=4, capacity=CAP)
    assert run("paged")[2] == list(np.asarray(toks[0]))


# ---------------------------------------------------------------------------
# capacity semantics: over-capacity admission and pool exhaustion
# ---------------------------------------------------------------------------


def test_paged_admits_what_contiguous_rejects():
    """prompt + max_new > capacity but <= num_slots * capacity: contiguous
    hard-rejects, the paged pool serves it — token-for-token with the
    sequential oracle at the pool-wide capacity."""
    cfg, params = _cfg_params("qwen3-1.7b")
    rng = np.random.default_rng(2)
    big = Request(rid=99, client_id=0,
                  prompt=rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                  max_new_tokens=CAP + 4)  # 26 > 16, <= 64

    con = ServeEngine(params, cfg, num_slots=4, capacity=CAP)
    a = con.try_admit(big)
    assert a is not None and a.rejected and con.rejects == 1

    pag = ServeEngine(params, cfg, num_slots=4, capacity=CAP,
                      kv_layout="paged", block_size=4)
    a = pag.try_admit(big)
    assert a is not None and not a.rejected
    pag.run_to_completion()
    assert len(a.tokens) == CAP + 4
    assert pag.over_capacity_admits == 1
    toks, _ = generate(params, cfg, {"tokens": big.prompt[None]},
                       max_new_tokens=CAP + 4, capacity=pag.max_row_len)
    assert a.tokens == list(np.asarray(toks[0]))

    # beyond even the whole pool: uniform hard reject
    sup = Request(rid=100, client_id=0, prompt=big.prompt,
                  max_new_tokens=4 * CAP + 1)
    r = pag.try_admit(sup)
    assert r is not None and r.rejected


def test_paged_pool_exhaustion_recovers():
    """Admission that the pool cannot cover returns None (request waits),
    the reservation rolls back, and the same request admits cleanly after
    evictions return pages."""
    cfg, params = _cfg_params("qwen3-1.7b")
    rng = np.random.default_rng(4)
    eng = ServeEngine(params, cfg, num_slots=4, capacity=8,
                      kv_layout="paged", block_size=4)  # pool: 8 pages

    def req(rid):
        return Request(rid=rid, client_id=0,
                       prompt=rng.integers(0, cfg.vocab_size,
                                           4).astype(np.int32),
                       max_new_tokens=8)  # 12 slots -> 3 pages

    a0, a1 = eng.try_admit(req(0)), eng.try_admit(req(1))
    assert a0 is not None and a1 is not None
    reserved_before = eng.allocator.reserved
    assert eng.try_admit(req(2)) is None  # 3 > 8 - 6 free pages
    assert eng.allocator.reserved == reserved_before  # rollback
    eng.run_to_completion()  # evictions return every page
    a2 = eng.try_admit(req(2))
    assert a2 is not None and not a2.rejected
    eng.run_to_completion()
    assert len(a2.tokens) == 8
    assert eng.allocator.free_blocks() == eng.pool_blocks


def test_paged_requires_batched_mode():
    cfg, params = _cfg_params("qwen3-1.7b")
    with pytest.raises(ValueError):
        ServeEngine(params, cfg, num_slots=2, capacity=8,
                    kv_layout="paged", block_size=4, fused_mode="vmap")
    with pytest.raises(ValueError):
        ServeEngine(params, cfg, num_slots=2, capacity=8,
                    kv_layout="bogus")


# ---------------------------------------------------------------------------
# checkpoint-arrival swap machinery
# ---------------------------------------------------------------------------


def test_checkpoint_manifest_watcher_roundtrip(tmp_path):
    d = str(tmp_path)
    w = CheckpointWatcher(d, after_round=0, min_poll_s=0.0)
    assert w.poll() is None  # nothing published
    ck0 = MergeCheckpoint(round=0, rep_paths={1: "a.npz"},
                          global_path="g0.npz", groups=((1, 2),))
    ck2 = MergeCheckpoint(round=2, rep_paths={3: "b.npz", 5: "c.npz"},
                          global_path="g2.npz", groups=((3, 4), (5, 6)))
    write_checkpoint_manifest(d, ck0)
    write_checkpoint_manifest(d, ck2)
    assert not any(n.endswith(".tmp") for n in os.listdir(d))  # atomic
    got, mtime = w.poll()  # round 0 filtered by after_round
    assert got == ck2 and mtime > 0
    assert w.poll() is None  # already yielded: no re-adoption


def test_swap_report_adoption_latency():
    r = SwapReport(round=3, ckpt_written_at=100.0, adopted_at=100.25)
    assert abs(r.ckpt_to_adoption_ms - 250.0) < 1e-6
    assert SwapReport(round=3).ckpt_to_adoption_ms == 0.0
