"""The one generator that reads every traffic file under bench/traffic/.

A mix is data: lengths, rates, client counts. Sizes and gaps are drawn by
quantiles rather than at random, and a serving mix's schedule (which
request comes when, with what lengths) is the same for every seed: a
window holds a few tens of long requests, so their order alone changes
the work done in it. The seed draws the token ids.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

import numpy as np


SCHEDULE_SEED = 0  # one schedule of sizes and arrivals for every seed


@dataclass
class Item:
    """One request of a serving mix: when it is due (seconds from the start
    of the window), its prompt and how many tokens it asks for."""
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    due: float = 0.0


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def exact_counts(n: int, probs: Sequence[float]) -> np.ndarray:
    """Largest-remainder split of n into shares ``probs``."""
    p = np.asarray(probs, np.float64)
    p = p / p.sum()
    raw = n * p
    counts = np.floor(raw).astype(np.int64)
    rest = n - int(counts.sum())
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[:rest]] += 1
    return counts


def bucket_lengths(n: int, buckets: Sequence[int], probs: Sequence[float],
                   rng: np.random.Generator) -> np.ndarray:
    """n prompt lengths in the buckets' exact proportions, shuffled."""
    lens = np.repeat(np.asarray(buckets, np.int64), exact_counts(n, probs))
    rng.shuffle(lens)
    return lens


def output_lengths(n: int, spec: dict, rng: np.random.Generator) -> np.ndarray:
    """n output lengths at the quantiles (i + 0.5) / n of a lognormal
    (median, sigma), clipped to [min, max], shuffled."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown output length distribution {spec['dist']!r}")
    from statistics import NormalDist
    q = (np.arange(n) + 0.5) / n
    z = np.asarray([NormalDist().inv_cdf(float(x)) for x in q])
    vals = spec["median"] * np.exp(spec["sigma"] * z)
    vals = np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)
    rng.shuffle(vals)
    return vals


def poisson_due_times(n: int, rate: float, rng: np.random.Generator
                      ) -> np.ndarray:
    """n arrival times of an open-loop Poisson stream at ``rate``: the gaps
    are the exponential distribution's quantiles, shuffled."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng.shuffle(gaps)
    return np.cumsum(gaps) - gaps[0]


def serving_items(mix: dict, seed: int, seconds: float, vocab: int
                  ) -> List[Item]:
    """The requests of one run: every request due inside the window of an
    open loop at the mix's fixed rate, on the mix's one schedule, with
    prompts drawn from the seed."""
    if mix["kind"] != "open_loop":
        raise ValueError(f"not a serving mix: {mix['kind']!r}")
    rng = rng_for(SCHEDULE_SEED, 0x7AFF)
    n = int(round(mix["rate_per_s"] * seconds))
    due = poisson_due_times(n, mix["rate_per_s"], rng)
    lens = bucket_lengths(n, mix["prompt_buckets"], mix["prompt_probs"], rng)
    outs = output_lengths(n, mix["output"], rng)
    ids = rng_for(seed, 0x1D5)
    return [Item(rid=i,
                 prompt=ids.integers(0, vocab, int(lens[i])).astype(np.int32),
                 max_new_tokens=int(outs[i]), due=float(due[i]))
            for i in range(n)]


def fed_job(mix: dict) -> dict:
    """A federation mix is the shape of one job; every job of a run is the
    same job from the same seeded start."""
    if mix["kind"] != "fed_jobs":
        raise ValueError(f"not a federation mix: {mix['kind']!r}")
    return dict(mix)


def class_shards(labels: np.ndarray, num_clients: int, shards_per_client: int,
                 rng: np.random.Generator) -> List[np.ndarray]:
    """McMahan et al.'s non-IID split (arXiv:1602.05629): sort the samples by
    label, cut them into ``num_clients * shards_per_client`` shards of
    (nearly) equal size and deal each client that many shards at random.
    Returns each client's sample indices."""
    order = np.argsort(labels, kind="stable")
    shards = np.array_split(order, num_clients * shards_per_client)
    deal = rng.permutation(len(shards))
    return [np.sort(np.concatenate([shards[j] for j in
                                    deal[c * shards_per_client:
                                         (c + 1) * shards_per_client]]))
            for c in range(num_clients)]
