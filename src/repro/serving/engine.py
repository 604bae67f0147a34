"""Fixed-slot continuous-batching serving engine over a paged KV arena.

One :class:`ServeEngine` is one serving *replica*: a weight pytree plus a
preallocated decode-state arena of ``num_slots`` request rows. Requests
are admitted into free rows as they arrive (prefill + ``states_from_prefill``
written into the row), every occupied row advances one token per fused
decode step, and rows are evicted on EOS / max-tokens, so short and long
requests share the same compiled programs and a new arrival never waits
for the previous batch to drain. ``launch.serve.generate`` (one lockstep
batch, run to completion over a contiguous cache) is the sequential
parity oracle this engine is tested against token-for-token.

The arena (DESIGN.md §10). Attention runs hold a global pool of
``block_size``-position KV pages per layer, plus one trailing trash page;
each row reads its cache through a host-authoritative block table
(``serving/paging.BlockAllocator`` owns the free list). Recurrent runs keep
one state row per slot. Every leaf carries the slot (or pool) axis at
position 1, under the per-run layer axis. One ragged batched
``decode_step`` advances every row; per-row cache lengths
(``models/layers.attention_decode``) keep rows at different depths exact
inside the single call. Active rows are kept *prefix-compacted* in
``[0, num_active)`` (eviction moves the last active row into the hole), so
the step only runs over an occupancy bucket of ``next_pow2(num_active)``
rows, and, for full-attention configs, over a depth bucket of
``next_pow2(max_pos + 1)`` positions in whole pages. Rows inside the bucket
beyond ``num_active`` carry length 0; the step re-pins their lengths to 0
after the token-write increment, so their (discarded) output never grows
the work.

Admission is free-page accounting: a request needs ceil((L + max_new) / bs)
pages reserved up front (window-capped), drawn in full at once, so a
row's block table never changes during its decode and the device copy of
the table is reused across every step between admissions. The pool holds
``num_slots * capacity`` positions (plus the trash page), and one row may
draw all of them: a request longer than one slot's ``capacity`` is
admitted while the pool has pages (``over_capacity_admits`` counts those),
one the pool cannot cover yet returns None and stays queued, and only
``L + max_new > num_slots * capacity`` is *rejected*: ``try_admit``
returns the ActiveRequest with ``rejected=True`` / ``done=True`` and no
row is touched, so an open-loop trace survives a poison request and the
router can count rejects. Eviction returns a row's pages to the free list.
``debug_poison_evictions=True`` fills freed pages with a finite sentinel
(``POISON_VALUE``) so any read-after-free shifts decoded tokens and fails
the parity tests; the sentinel is deliberately NOT NaN: the additive -1e30
decode mask must keep exactly-masked poison at zero weight, and NaN would
propagate through masked lanes of correct code.

Compiled-program discipline: programs are cached per config at module
level (shared across replicas); jax's jit cache then keys on shapes.
Admission compiles once per distinct prompt length
(``traffic.LEN_BUCKETS``); the step compiles once per (occupancy bucket,
depth bucket), both power-of-two rounded, a handful in practice. Decoding
is greedy (argmax), the oracle's default.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.kernels.decode_attn.ops import pages_per_block
from repro.launch.serve import states_from_prefill
from repro.models import blocks as B
from repro.models import model as M
from repro.serving.paging import BlockAllocator
from repro.serving.traffic import Request

# eviction poison sentinel: large enough that a stale read (a block-table /
# allocator bug) visibly shifts attention outputs and decoded tokens, small
# enough (<< 1e23 = ulp of the -1e30 mask) that exactly-masked poison still
# softmaxes to exactly zero weight
POISON_VALUE = 1e4


@dataclass
class ActiveRequest:
    """A request occupying a slot (or finished/rejected): generated tokens
    + timing. ``rejected=True`` means the request never ran (over
    capacity) — ``done`` is immediately True and ``tokens`` stays empty."""
    request: Request
    tokens: List[int] = field(default_factory=list)
    admitted_at: float = 0.0
    finished_at: float = 0.0
    rejected: bool = False

    @property
    def done(self) -> bool:
        return self.rejected or (
            len(self.tokens) >= self.request.max_new_tokens
            or (
                self.request.eos_id is not None
                and len(self.tokens) > 0
                and self.tokens[-1] == self.request.eos_id
            )
        )


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _mask_lengths(cfg, view, active):
    """Re-pin attention lengths of inactive bucket lanes to 0 (the step
    just incremented them by the token write)."""
    out = []
    for (mtype, _n), st in zip(B.runs(cfg), view):
        if mtype == "attn":
            st = dict(st)
            st["length"] = st["length"] * active[None, :]
        out.append(st)
    return tuple(out)


# ---------------------------------------------------------------------------
# arena programs
# ---------------------------------------------------------------------------
#
# Arena layout: attention runs hold {k, v: (runL, P+1, bs, Kv, D) page
# pools, length: (runL, num_slots)}; recurrent runs keep one state row per
# slot, (runL, num_slots, ...). Block tables live on the HOST (the engine's
# ``_bt``) and are passed into each program — the device never owns them,
# so allocator moves are plain numpy writes, not compiled programs. The
# decode step's layer loop carries a run's stacked pools whole: each layer
# scatters its token into its own pool in place and the kernel reads that
# pool by its layer index, so the pools leave the step in the donated
# arena's buffers and the step copies no pool, of a layer or the stack.


@functools.lru_cache(maxsize=32)
def _paged_admit(cfg: ModelConfig):
    """(params, arena, row, tokens (1, L), bt_row (T,)) -> (first_tok,
    arena): prefill + ring-cache conversion, then scatter the row's cache
    pages through its block table into the page pools (unallocated -1
    entries land on the trash page). jit compiles once per prompt length."""

    def admit(params, arena, row, tokens, bt_row):
        logits_last, raw = M.prefill(params, cfg, {"tokens": tokens})
        L = tokens.shape[1]
        T = bt_row.shape[0]
        bs = _pool_bs(arena, cfg)
        # only the prompt's pages hold data (the row's full reservation is
        # allocated, but pages past the prompt are written by decode before
        # they are ever attended) — convert and scatter just the live
        # pages, not the full row-capacity table. Ring placement is
        # unchanged: the live ring C' = min(window, n_live * bs) puts
        # every resident slot where the full T * bs table would (wrap only
        # happens once L > window, and then both rings equal the window).
        live = min(L, cfg.window_size) if cfg.window_size > 0 else L
        n_live = min(-(-live // bs), T)
        out = []
        for (mtype, _n), full, st in zip(B.runs(cfg), arena,
                                         states_from_prefill(
                                             cfg, raw, L, n_live * bs)):
            if mtype == "attn":
                trash = full["k"].shape[1] - 1
                blk = jnp.where(bt_row[:n_live] >= 0, bt_row[:n_live], trash)
                C = st["k"].shape[2]
                runL = st["k"].shape[0]

                def pages(a, s):
                    s = s[:, 0].astype(a.dtype)      # (runL, C, Kv, D)
                    if C < n_live * bs:  # page rounding: pad dead tail slots
                        pad = jnp.zeros((runL, n_live * bs - C) + s.shape[2:],
                                        a.dtype)
                        s = jnp.concatenate([s, pad], axis=1)
                    return s.reshape((runL, n_live, bs) + s.shape[2:])

                out.append({
                    "k": full["k"].at[:, blk].set(pages(full["k"], st["k"])),
                    "v": full["v"].at[:, blk].set(pages(full["v"], st["v"])),
                    "length": jax.lax.dynamic_update_index_in_dim(
                        full["length"], st["length"][:, 0], row, axis=1
                    ),
                })
            else:
                out.append(jax.tree_util.tree_map(
                    lambda a, s: jax.lax.dynamic_update_index_in_dim(
                        a, s[:, 0].astype(a.dtype), row, axis=1
                    ),
                    full, st,
                ))
        return (jnp.argmax(logits_last[0], -1).astype(jnp.int32),
                tuple(out))

    return jax.jit(admit, donate_argnums=(1,))


def _pool_bs(arena, cfg) -> int:
    """Page size from the first attention run's pool shape."""
    for (mtype, _n), st in zip(B.runs(cfg), arena):
        if mtype == "attn":
            return st["k"].shape[2]
    return 1  # no attention caches: page size is irrelevant


@functools.lru_cache(maxsize=64)
def _paged_step(cfg: ModelConfig, n_rows: int, t_view: int):
    """(params, arena, tok, pos, active, bt (n_rows, t_view)) ->
    (next_tok (n_rows,), arena).

    ONE ragged batched ``decode_step`` over the occupancy bucket; the
    host block table is broadcast to the per-layer cache dicts and dropped
    from the returned arena, and the pools come back as the layer loop
    carried them, updated in place. ``t_view`` is the depth bucket in
    PAGES — rows deeper than ``t_view * bs`` never occur inside the
    bucket, so slicing table columns is exact. Its phases carry named scopes (view,
    the model's decode step, write_back, sample)."""

    def step(params, arena, tok, pos, active, bt):
        view = []
        with jax.named_scope("view"):
            for (mtype, _n), st in zip(B.runs(cfg), arena):
                if mtype == "attn":
                    runL = st["length"].shape[0]
                    view.append({
                        "k": st["k"], "v": st["v"],
                        "block_tables": jnp.broadcast_to(
                            bt[None], (runL, n_rows, t_view)
                        ),
                        "length": st["length"][:, :n_rows],
                    })
                else:
                    view.append(
                        jax.tree_util.tree_map(lambda a: a[:, :n_rows], st)
                    )
        logits, new_view = M.decode_step(params, cfg, tuple(view), tok, pos)
        out = []
        with jax.named_scope("write_back"):
            new_view = _mask_lengths(cfg, new_view, active)
            for (mtype, _n), full, v in zip(B.runs(cfg), arena, new_view):
                if mtype == "attn":
                    out.append({
                        "k": v["k"], "v": v["v"],  # the carried pools
                        "length": full["length"].at[:, :n_rows].set(
                            v["length"]),
                    })
                else:
                    out.append(jax.tree_util.tree_map(
                        lambda a, b: a.at[:, :n_rows].set(b), full, v
                    ))
        with jax.named_scope("sample"):
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return nxt, tuple(out)

    return jax.jit(step, donate_argnums=(1,))


@functools.lru_cache(maxsize=32)
def _paged_evict(cfg: ModelConfig):
    """(arena, src, dst) -> arena: copy row ``src`` over row ``dst`` and
    zero row ``src``'s attention lengths (donated; src == dst just zeroes
    the row). The prefix-compaction primitive, one compiled program with
    device-scalar row indices. Pages are freed host-side by the allocator,
    so on device only the attention *lengths* move; recurrent-state rows
    move whole."""

    def ev(arena, src, dst):
        out = []
        for (mtype, _n), st in zip(B.runs(cfg), arena):
            if mtype == "attn":
                ln = st["length"]
                r = jax.lax.dynamic_index_in_dim(ln, src, axis=1,
                                                 keepdims=False)
                ln = jax.lax.dynamic_update_index_in_dim(ln, r, dst, axis=1)
                keep = (jnp.arange(ln.shape[1]) != src).astype(ln.dtype)
                out.append(dict(st, length=ln * keep[None, :]))
            else:
                def move(a):
                    r = jax.lax.dynamic_index_in_dim(a, src, axis=1,
                                                     keepdims=False)
                    return jax.lax.dynamic_update_index_in_dim(a, r, dst,
                                                               axis=1)

                out.append(jax.tree_util.tree_map(move, st))
        return tuple(out)

    return jax.jit(ev, donate_argnums=(0,))


@functools.lru_cache(maxsize=32)
def _poison_blocks(cfg: ModelConfig):
    """(arena, mask (P+1,) bool) -> arena with masked pool pages filled
    with POISON_VALUE in every attention run (debug_poison_evictions)."""

    def poison(arena, mask):
        out = []
        for (mtype, _n), st in zip(B.runs(cfg), arena):
            if mtype == "attn":
                m = mask[None, :, None, None, None]
                out.append(dict(
                    st,
                    k=jnp.where(m, jnp.asarray(POISON_VALUE, st["k"].dtype),
                                st["k"]),
                    v=jnp.where(m, jnp.asarray(POISON_VALUE, st["v"].dtype),
                                st["v"]),
                ))
            else:
                out.append(st)
        return tuple(out)

    return jax.jit(poison, donate_argnums=(0,))


def _adopt(old, new):
    """Donated weight adoption for hot swaps: the old replica weights are
    donated so XLA reuses/free-lists their HBM for the incoming tree."""
    return jax.tree_util.tree_map(lambda o, n: n.astype(o.dtype), old, new)


_adopt_jit = jax.jit(_adopt, donate_argnums=(0,))


class ServeEngine:
    """Continuous-batching replica over one model (see module docstring).

    Host-side bookkeeping is tiny: per-slot ActiveRequest or None, the
    per-slot last token and next absolute position (the fused step's only
    per-tick inputs), and the block tables. All model state lives in the
    donated device arena. ``kv_layout`` accepts only ``"paged"``, the one
    layout.
    """

    def __init__(
        self,
        params,
        cfg: ModelConfig,
        num_slots: int = 8,
        capacity: int = 64,
        kv_layout: str = "paged",
        block_size: Optional[int] = None,
        debug_poison_evictions: bool = False,
    ):
        assert cfg.supports_decode, f"{cfg.name} is encoder-only"
        if kv_layout != "paged":
            raise ValueError(
                f"kv_layout must be 'paged' (the only layout), got "
                f"{kv_layout!r}"
            )
        self.cfg = cfg
        self.num_slots = int(num_slots)
        self.capacity = int(capacity)
        self.block_size = int(block_size if block_size is not None
                              else cfg.kv_block_size)
        self.debug_poison = bool(debug_poison_evictions)
        self.params = jax.tree_util.tree_map(jnp.asarray, params)
        self.over_capacity_admits = 0  # admits longer than one slot
        # the pool holds num_slots * capacity positions; one row may draw
        # all of them
        self.max_row_len = self.num_slots * self.capacity
        self._row_cap = (
            min(cfg.window_size, self.max_row_len)
            if cfg.window_size > 0 else self.max_row_len
        )
        self._table_len = -(-self._row_cap // self.block_size)
        self.pool_blocks = -(-self.max_row_len // self.block_size)
        self.allocator = BlockAllocator(self.pool_blocks)
        self._has_attn = any(m == "attn" for m, _ in B.runs(cfg))
        self._bt = np.full((self.num_slots, self._table_len), -1, np.int32)
        self._row_blocks: List[List[int]] = [
            [] for _ in range(self.num_slots)
        ]
        # device-side table cache: tables mutate on admit/evict only (rows
        # draw their full reservation at admission), so every pure-decode
        # step reuses the previous upload instead of re-slicing +
        # re-transferring every tick
        self._bt_version = 0
        self._bt_dev: Dict[Tuple[int, int], Tuple[int, jnp.ndarray]] = {}
        # strip block tables from the device arena: the host table is
        # authoritative and enters each program as an argument
        arena = []
        for (mtype, _n), st in zip(
            B.runs(cfg),
            M.init_decode_paged(cfg, self.num_slots, self.max_row_len,
                                self.block_size, self.pool_blocks),
        ):
            if mtype == "attn":
                arena.append({"k": st["k"], "v": st["v"],
                              "length": st["length"]})
            else:
                arena.append(st)
        self.arena = tuple(arena)
        # (kv heads, head dim, itemsize) of a pool page, for the step's
        # counters of what the paged decode kernel reads
        self._kv_page = next(
            ((st["k"].shape[3], st["k"].shape[4], st["k"].dtype.itemsize)
             for (mtype, _n), st in zip(B.runs(cfg), arena)
             if mtype == "attn"), None)
        self.slots: List[Optional[ActiveRequest]] = [None] * self.num_slots
        self._tok = np.zeros(self.num_slots, np.int32)
        self._pos = np.zeros(self.num_slots, np.int32)
        self.steps = 0          # fused decode steps executed
        self.swaps = 0          # weight hot-swaps performed
        self.rejects = 0        # over-capacity requests turned away

    # ------------------------------------------------------------------
    @property
    def num_active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    # ------------------------------------------------------------------
    def try_admit(self, req: Request, now: float = 0.0
                  ) -> Optional[ActiveRequest]:
        """Admit ``req`` into a free row: prefill its prompt and write the
        converted decode state into the arena. Returns the ActiveRequest
        (already *finished* if max_new_tokens == 1, since the first token
        comes from prefill; ``rejected=True`` if the request can never
        fit), or None when no row is free or the page pool cannot cover
        the request's worst-case reservation. The program span
        ``serve.admit`` carries the request's id, its prompt length and
        whether it took a slot (``admitted``)."""
        with TraceAnnotation("serve.admit", rid=req.rid,
                             prompt=len(req.prompt)) as span:
            a = self._admit(req, now)
            span.set_metadata(admitted=int(a is not None and not a.rejected))
            return a

    def _admit(self, req: Request, now: float) -> Optional[ActiveRequest]:
        """Free-page accounting: reserve the worst case
        ceil((L + max_new) / bs) pages up front (window-capped) and draw
        them all immediately. Because admission reserves the worst case,
        lazy per-step draws would buy no extra capacity (``available()``
        already subtracts reservations); eager draws make the block table
        immutable for the row's whole decode, so the device table upload
        is cached across every step between admissions. Slots past the
        prompt hold stale pool data until decode writes them; the ring
        mask zeroes them exactly (same contract as the trash page). The
        reservation is rolled back if no row is free: a refused reserve or
        a full house both return None and the request waits in the router
        queue."""
        L = len(req.prompt)
        if L + req.max_new_tokens > self.max_row_len:
            # cannot fit even with the whole pool: hard reject
            self.rejects += 1
            return ActiveRequest(request=req, admitted_at=now,
                                 finished_at=now, rejected=True)
        need = 0
        if self._has_attn:
            need = -(-min(L + req.max_new_tokens, self._row_cap)
                     // self.block_size)
        if not self.allocator.reserve(need):
            return None
        free = self.free_slots()
        if not free:
            self.allocator.release(need)  # rollback
            return None
        # actives stay prefix-compacted: the first free slot IS row
        # num_active
        slot = free[0]
        blocks = [self.allocator.alloc() for _ in range(need)]
        self._bt[slot, :] = -1
        self._bt[slot, :need] = blocks
        self._bt_version += 1
        tokens = jnp.asarray(np.asarray(req.prompt, np.int32)[None])
        first, self.arena = _paged_admit(self.cfg)(
            self.params, self.arena, jnp.int32(slot), tokens,
            jnp.asarray(self._bt[slot]),
        )
        if L + req.max_new_tokens > self.capacity:
            self.over_capacity_admits += 1
        active = ActiveRequest(request=req, tokens=[int(first)],
                               admitted_at=now)
        if active.done:
            # never occupies the row: return the pages
            active.finished_at = now
            self.allocator.free(blocks)
            self._bt[slot, :] = -1
            self._bt_version += 1
            if self.debug_poison and blocks:
                self.arena = _poison_blocks(self.cfg)(
                    self.arena, jnp.asarray(self._block_mask(blocks))
                )
            self.arena = _paged_evict(self.cfg)(
                self.arena, jnp.int32(slot), jnp.int32(slot)
            )
            return active
        self.slots[slot] = active
        self._row_blocks[slot] = blocks
        self._tok[slot] = int(first)
        self._pos[slot] = L
        return active

    def _block_mask(self, blocks: List[int]) -> np.ndarray:
        mask = np.zeros(self.pool_blocks + 1, bool)  # trash never poisoned
        mask[np.asarray(blocks, np.int64)] = True
        return mask

    # ------------------------------------------------------------------
    def step(self, now: float = 0.0) -> List[ActiveRequest]:
        """One fused decode step over all active rows; returns requests
        that finished this step (their rows and pages are freed). No-op
        when idle. The program span ``serve.step`` carries the active
        rows, the row bucket and depth view (in pages) the step ran at,
        whether the block table went to the device (``bt_upload``), and
        the live KV pages its rows attend and the page blocks the paged
        decode kernel visits (``kv_pages``, ``kv_blocks``). Evictions nest
        in it as ``serve.evict``."""
        na = self.num_active
        if na == 0:
            return []
        with TraceAnnotation("serve.step", rows=na) as span:
            finished, shape = self._step(na, now)
            span.set_metadata(**shape)
        return finished

    def _step(self, na: int, now: float) -> Tuple[List[ActiveRequest], dict]:
        # every page a row will ever write was drawn at admission, so the
        # block table only mutates on admit/evict and the device upload
        # below is a cache hit on every pure-decode step.
        # bucket floor of 2: XLA's batch-1 path is measurably slower than
        # one masked dead lane on CPU, and the floor halves the program count
        n_rows = min(max(_next_pow2(na), 2), self.num_slots)
        if self.cfg.window_size > 0:
            t_view = self._table_len  # ring cache: never depth-sliced
        else:
            max_pos = int(self._pos[:na].max())
            s_view = min(
                max(_next_pow2(max_pos + 1), min(16, self._row_cap)),
                self._row_cap,
            )
            t_view = -(-s_view // self.block_size)
        active = np.zeros(n_rows, np.int32)
        active[:na] = 1
        key = (n_rows, t_view)
        ent = self._bt_dev.get(key)
        upload = ent is None or ent[0] != self._bt_version
        if upload:
            bt_dev = jnp.asarray(self._bt[:n_rows, :t_view])
            self._bt_dev[key] = (self._bt_version, bt_dev)
        else:
            bt_dev = ent[1]
        shape = dict(bucket=n_rows, view=t_view, bt_upload=int(upload),
                     **self._kv_counters(na, n_rows, t_view))
        nxt, self.arena = _paged_step(self.cfg, n_rows, t_view)(
            self.params, self.arena,
            jnp.asarray(self._tok[:n_rows]), jnp.asarray(self._pos[:n_rows]),
            jnp.asarray(active), bt_dev,
        )
        nxt = np.asarray(nxt)
        self.steps += 1
        for i in range(na):
            a = self.slots[i]
            a.tokens.append(int(nxt[i]))
            self._tok[i] = int(nxt[i])
            self._pos[i] += 1
        # swap-remove evictions, highest row first, to keep the prefix
        # compact: the last active row fills each hole on device and host
        done_rows = [i for i in range(na) if self.slots[i].done]
        if not done_rows:
            return [], shape
        finished: List[ActiveRequest] = []
        with TraceAnnotation("serve.evict", rows=len(done_rows)):
            cur = na
            for i in sorted(done_rows, reverse=True):
                a = self.slots[i]
                a.finished_at = now
                finished.append(a)
                freed = self._row_blocks[i]
                self.allocator.free(freed)
                if self.debug_poison and freed:
                    self.arena = _poison_blocks(self.cfg)(
                        self.arena, jnp.asarray(self._block_mask(freed))
                    )
                last = cur - 1
                self.arena = _paged_evict(self.cfg)(
                    self.arena, jnp.int32(last), jnp.int32(i)
                )
                self._bt[i] = self._bt[last]
                self._bt[last] = -1
                self._bt_version += 1
                self._row_blocks[i] = self._row_blocks[last]
                self._row_blocks[last] = []
                self.slots[i] = self.slots[last]
                self.slots[last] = None
                self._tok[i] = self._tok[last]
                self._pos[i] = self._pos[last]
                cur -= 1
        return finished, shape

    def _kv_counters(self, na: int, n_rows: int, t_view: int) -> dict:
        """What a step's attention reads in each layer: the live pages of
        its rows (``kv_pages``), and the page blocks the paged decode
        kernel's grid visits (``kv_blocks``), live or not."""
        if self._kv_page is None:
            return {}
        bs = self.block_size
        # a windowed row attends at most its ring
        live = np.minimum(self._pos[:na] + 1, self._row_cap)
        ppb = pages_per_block(bs, *self._kv_page, t_view)
        return dict(kv_pages=int((-(-live // bs)).sum()),
                    kv_blocks=n_rows * -(-t_view // ppb))

    def run_to_completion(self, now: float = 0.0) -> List[ActiveRequest]:
        """Drain all active slots (no new admissions)."""
        out: List[ActiveRequest] = []
        while self.num_active:
            out.extend(self.step(now))
        return out

    # ------------------------------------------------------------------
    def swap_params(self, new_params) -> float:
        """Hot-swap replica weights between decode steps; returns the stall
        in seconds (host->device transfer + donated adoption, no
        recompile: shapes, dtypes and jit caches are unchanged).

        Staleness semantics (DESIGN.md §10): in-flight rows keep their
        KV/recurrent caches, so their remaining tokens are decoded with
        NEW weights over caches computed under OLD weights, a bounded
        staleness window that ends when the row is evicted. Requests
        admitted after the swap see the new weights end to end (the
        hot-swap parity contract tested in tests/test_serving_engine.py).
        The arena is untouched."""
        import time

        t0 = time.perf_counter()
        self.params = _adopt_jit(self.params, new_params)
        jax.block_until_ready(self.params)
        self.swaps += 1
        return time.perf_counter() - t0
