"""Serving cells: requests go through the program's ``ReplicaSet`` (one
paged ``ServeEngine`` replica) by ``submit`` and ``tick``, as a server loop
would drive it.

Set-up makes the weights on the device from the seed, builds the engine,
and warms every program the mix can reach: one admission per prompt
bucket, and one decode step per (rows, cache-depth) bucket the mix's
concurrency and lengths allow, through the engine's public calls. The
window then offers the mix, an open loop that submits each request at its
due time. After the window no new request is sent, and those in flight
are finished (a minute at most) so that every latency counts; a traced
run stops and reads its trace only then. The reference then rebuilds the
logits of a sample of the finished requests and measures how far each
served token lies below its best.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import numpy as np

from bench import common, generator

DRAIN_S = 60.0


def next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def program_config(m: dict):
    """The program's config for this model, checked against the
    configuration file, in its paged serving layout."""
    from repro.configs import get_config

    cfg = get_config(m["program_config"])
    pairs = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
             "num_heads": "num_attention_heads",
             "num_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
             "vocab_size": "vocab_size", "head_dim": "head_dim",
             "rope_theta": "rope_theta", "dtype": "torch_dtype"}
    for ours, theirs in pairs.items():
        if getattr(cfg, ours) != type(getattr(cfg, ours))(m[theirs]):
            cfg = dataclasses.replace(cfg, **{ours: type(getattr(cfg, ours))(m[theirs])})
    if not cfg.qk_norm or cfg.window_size or cfg.family != "dense":
        raise common.BenchError(f"{m['program_config']} is not a dense model "
                                "with query/key norms and full attention")
    sv = m["serving"]
    return dataclasses.replace(cfg, kv_layout="paged",
                               kv_block_size=int(sv["block_size"]))


def program_params(jax, jnp, w: dict, cfg):
    """The benchmark's weights in the program's layout. The program keeps a
    separate output head; the model ties it to the embedding, so the head
    is the embedding's transpose. Everything else shares the same
    buffers."""
    from repro.models import model as M

    lm_head = jax.jit(lambda e: e.T)(w["embed"])
    params = {
        "embed": w["embed"],
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": lm_head,
        "blocks": ({
            "norm1": {"scale": w["attn_norm"]},
            "mixer": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"],
                      "q_norm": {"scale": w["q_norm"]},
                      "k_norm": {"scale": w["k_norm"]}},
            "norm2": {"scale": w["mlp_norm"]},
            "ffn": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                    "w_down": w["w_down"]},
        },),
    }
    want = jax.eval_shape(lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got) or \
            jax.tree_util.tree_leaves(want) != jax.tree_util.tree_leaves(got):
        raise common.BenchError("the program's parameter layout changed")
    return params


def warm_plan(mix: dict, sv: dict) -> Dict[str, list]:
    """Which programs the mix can reach: prompt buckets, and decode steps by
    (row bucket, cache-depth bucket). Depth buckets follow the deepest
    row: from the shortest prompt to the longest prompt plus its longest
    answer."""
    buckets = sorted(int(b) for b in mix["prompt_buckets"])
    out_max = int(mix["output"]["max"])
    rows = sorted({min(max(next_pow2(n), 2), sv["num_slots"])
                   for n in range(1, sv["num_slots"] + 1)})
    lo, hi = next_pow2(buckets[0] + 1), next_pow2(buckets[-1] + out_max - 1)
    depths = []
    d = lo
    while d <= hi:
        depths.append(d)
        d *= 2
    return {"prompts": buckets, "rows": rows, "depths": depths}


def warm(eng, plan: dict, vocab: int):
    """Runs each program of the plan once through the engine's public
    calls. A decode bucket (rows r, depth d) is reached by admitting r
    requests, one of them with the prompt bucket that first reaches depth
    d, and stepping until every row is done."""
    from repro.serving.traffic import Request

    rng = np.random.default_rng(0)

    def req(L, new):
        return Request(rid=-1, client_id=0, max_new_tokens=new,
                       prompt=rng.integers(0, vocab, L).astype(np.int32))

    for L in plan["prompts"]:
        eng.try_admit(req(L, 1))
    short = plan["prompts"][0]
    for d in plan["depths"]:
        deep = max([L for L in plan["prompts"] if next_pow2(L + 1) <= d])
        extra = max(d // 2 - deep, 0)  # steps until the deep row is past d/2
        for r in plan["rows"]:
            rows = [req(deep, 2 + extra)] + [req(short, 2) for _ in range(r - 1)]
            for q in rows:
                if eng.try_admit(q) is None:
                    raise common.BenchError(f"warm-up: no room for {r} rows at depth {d}")
            while eng.num_active:
                eng.step()


class Book:
    """What the window saw, per request and per tick."""

    def __init__(self):
        self.due: Dict[int, float] = {}
        self.admitted: Dict[int, float] = {}
        self.times: Dict[int, List[float]] = {}
        self.done: Dict[int, object] = {}
        self.rejected = 0
        self.rows: List[int] = []          # rows stepped per tick, in the window
        self.prompts_in_window: List[int] = []
        self.decode_lengths: List[int] = []

    def observe(self, actives, done, t: float, in_window: bool):
        stepped = 0
        for a in list(actives) + [a for _, a in done]:
            rid = a.request.rid
            seen = self.times.setdefault(rid, [])
            new = len(a.tokens) - len(seen)
            if rid not in self.admitted:
                self.admitted[rid] = a.admitted_at
                if in_window:
                    self.prompts_in_window.append(len(a.request.prompt))
                new_decode = new - 1
            else:
                new_decode = new
            if new_decode > 0:
                stepped += 1
                if in_window:
                    self.decode_lengths.append(len(a.request.prompt) + len(a.tokens) - 1)
            seen.extend([t] * new)
        for _, a in done:
            self.done[a.request.rid] = a
        if in_window and stepped:
            self.rows.append(stepped)


def setup(ctx, seed: int) -> dict:
    """Weights from ``seed``, the engine, and every program the mix can
    reach run once."""
    import jax
    import jax.numpy as jnp
    from repro.serving import GLOBAL, ClusterRouter, ReplicaSet, ServeEngine

    m, mix, ref = ctx.config, ctx.mix, ctx.reference
    sv = m["serving"]
    cfg = program_config(m)
    w = ref.init_weights(m, seed % (2**31 - 1))
    params = program_params(jax, jnp, w, cfg)
    eng = ServeEngine(params, cfg, num_slots=sv["num_slots"],
                      capacity=sv["capacity"], kv_layout="paged",
                      block_size=sv["block_size"])
    warm(eng, warm_plan(mix, sv), m["vocab_size"])
    jax.block_until_ready(eng.arena)
    return {"w": w, "eng": eng, "rs": ReplicaSet({GLOBAL: eng}, ClusterRouter(1))}


def offer(ctx, st: dict, items, seconds: float, traced: bool = False) -> dict:
    """Offers the requests for ``seconds``, then finishes those in flight.
    Returns what the window saw."""
    from repro.serving.traffic import Request

    rs, eng = st["rs"], st["eng"]
    book = Book()
    reqs = [Request(rid=it.rid, client_id=0, prompt=it.prompt,
                    max_new_tokens=it.max_new_tokens, arrival=it.due)
            for it in items]
    nxt = 0
    c0 = ctx.compiles.snapshot()["compiles"]
    if traced:
        ctx.start_trace()
    t_w = time.perf_counter()

    def submit(now: float):
        nonlocal nxt
        while nxt < len(reqs) and reqs[nxt].arrival <= now:
            book.due[reqs[nxt].rid] = reqs[nxt].arrival
            rs.submit(reqs[nxt])
            nxt += 1

    while True:
        now = time.perf_counter() - t_w
        if now >= seconds:
            break
        submit(now)
        if rs.idle:
            wait = (reqs[nxt].arrival if nxt < len(reqs) else seconds) - now
            time.sleep(max(0.0, min(wait, seconds - now)))
            continue
        with ctx.span("bench.tick"):
            done = rs.tick(now)
        t = time.perf_counter() - t_w
        book.observe([a for a in eng.slots if a is not None], done, t, True)
    window_s = time.perf_counter() - t_w
    if traced:
        ctx.end_window()
    compiles_in_window = ctx.compiles.snapshot()["compiles"] - c0
    backlog = sum(len(q) for q in rs.queues.values()) + rs.num_inflight
    # finish what was sent; nothing new is sent
    while not rs.idle and time.perf_counter() - t_w < window_s + DRAIN_S:
        now = time.perf_counter() - t_w
        done = rs.tick(now)
        book.observe([a for a in eng.slots if a is not None], done,
                     time.perf_counter() - t_w, False)
    drain_s = time.perf_counter() - t_w - window_s
    book.rejected = len(rs.rejected)
    rs.rejected.clear()
    rs.finished.clear()
    trace = ctx.stop_trace() if traced else None
    return {"book": book, "reqs": reqs, "window_s": window_s, "trace": trace,
            "compiles_in_window": compiles_in_window, "backlog": backlog,
            "drain_s": drain_s}


def summary(ctx, o: dict) -> dict:
    """The record the metric readers read."""
    book, window_s = o["book"], o["window_s"]
    first = [book.times[r][0] - book.due[r] for r in book.done if book.times.get(r)]
    gaps = [b - a for r in book.done for a, b in zip(book.times[r], book.times[r][1:])]
    return {
        "ttft_s": first,
        "itl_s": gaps,
        "queue_s": [book.admitted[r] - book.due[r] for r in book.admitted],
        "tokens_in_window": sum(1 for ts in book.times.values()
                                for x in ts if x <= window_s),
        "rows_per_step": book.rows,
        "num_slots": ctx.config["serving"]["num_slots"],
        "prompts_in_window": book.prompts_in_window,
        "decode_lengths": book.decode_lengths,
        "model": ctx.config,
    }


def served_sample(ctx, o: dict, seed: int) -> dict:
    book, reqs = o["book"], o["reqs"]
    sample = pick_sample(book, seed, int(ctx.mix.get("check_tokens", 400)))
    return {rid: (np.asarray(reqs[rid].prompt), list(book.done[rid].tokens))
            for rid in sample}


def run(ctx) -> dict:
    st = setup(ctx, ctx.seed)
    setup_s = time.perf_counter() - ctx.t0
    items = generator.serving_items(ctx.mix, ctx.seed, ctx.seconds,
                                  ctx.config["vocab_size"])
    o = offer(ctx, st, items, ctx.seconds, traced=ctx.trace)
    mem_peak = ctx.memory_peak()
    book = o["book"]
    attempted = len(book.due)
    unfinished = attempted - len(book.done) - book.rejected

    # ---- the check, once the program's state is gone
    served = served_sample(ctx, o, ctx.seed)
    w = st["w"]
    del st
    gc.collect()
    gaps = token_gaps(ctx.reference, ctx.config, w, served)
    numbers = {"mean_token_gap": gaps["mean"]}
    return {
        "setup_s": setup_s,
        "window_s": o["window_s"],
        "attempted": attempted,
        "failed": book.rejected + unfinished,
        "numbers": numbers,
        "notes": {"widest_token_gap": gaps["widest"],
                  "sampled_requests": len(served),
                  "sampled_tokens": sum(len(t) for _, t in served.values()),
                  "drain_s": o["drain_s"], "backlog_at_close": o["backlog"]},
        "compiles_in_window": o["compiles_in_window"],
        "memory_peak_bytes": mem_peak,
        "trace": o["trace"],
        "record": summary(ctx, o),
    }


def pick_sample(book: Book, seed: int, tokens: int) -> List[int]:
    """Requests to check, drawn from the seed among those finished: the one
    with the longest answer, then others at random until ``tokens`` served
    tokens are covered."""
    done = sorted(book.done)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(book.done[r].tokens), -r))
    rng = generator.rng_for(seed, 0xC4EC)
    rest = [r for r in rng.permutation(done).tolist() if r != longest]
    out, n = [longest], len(book.done[longest].tokens)
    for r in rest:
        if n >= tokens:
            break
        out.append(r)
        n += len(book.done[r].tokens)
    return out


def token_gaps(ref, m: dict, w, served: dict, quant=None) -> Dict[str, float]:
    """How far each served token of the sample lies below the reference's
    best logit, given the same prompt and the tokens served before it: the
    mean over every served token, which is compared, and the widest. With
    ``quant``, the gap of the token that the control (the reference at that
    precision) would have put first instead. The widest gap is not compared:
    with random weights the best logits lie close together, so a flip in
    the program and one in the control read alike (PERF.md section 2)."""
    worst, total, n = 0.0, 0.0, 0
    for prompt, toks in served.values():
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        rows = np.arange(len(prompt) - 1, len(seq))
        lg = np.asarray(ref.logits(m, w, seq, rows))
        if quant is None:
            pick = np.asarray(toks)
        else:
            pick = np.argmax(np.asarray(ref.logits(m, w, seq, rows, quant=quant)), -1)
        gap = lg.max(-1) - lg[np.arange(len(rows)), pick]
        worst = max(worst, float(gap.max()))
        total += float(gap.sum())
        n += len(gap)
    return {"mean": total / max(n, 1), "widest": worst}
