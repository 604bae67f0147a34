"""The one traffic generator: seeded, and every seed does the same work."""
import json

import numpy as np
import pytest

from bench import common, generator


def mix(name):
    return json.loads((common.BENCH / "traffic" / f"{name}.json").read_text())


def test_open_loop_is_seeded_and_seeds_share_the_work():
    m = mix("chat")
    a = generator.serving_items(m, 2**33 + 1, 30, 151936)
    b = generator.serving_items(m, 2**33 + 1, 30, 151936)
    c = generator.serving_items(m, 7, 30, 151936)
    assert [x.due for x in a] == [x.due for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert len(a) == len(c) == round(m["rate_per_s"] * 30)
    # the same schedule of sizes and arrivals, other prompts
    assert [x.due for x in a] == [x.due for x in c]
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in c]
    assert [x.max_new_tokens for x in a] == [x.max_new_tokens for x in c]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    assert all(x.due < 30 for x in a)
    assert all(0 <= int(x.prompt.max()) < 151936 for x in a)


def test_bucket_proportions_are_exact():
    m = mix("chat")
    items = generator.serving_items(m, 3, 100, 1000)
    n = len(items)
    lens = np.asarray([len(x.prompt) for x in items])
    for bucket, p in zip(m["prompt_buckets"], m["prompt_probs"]):
        assert abs(np.sum(lens == bucket) - p * n) <= 1


def test_lognormal_outputs_have_the_mix_median_and_range():
    m = mix("chat")
    outs = np.asarray([x.max_new_tokens for x in
                       generator.serving_items(m, 4, 100, 1000)])
    o = m["output"]
    assert outs.min() >= o["min"] and outs.max() <= o["max"]
    assert abs(np.median(outs) - o["median"]) <= 2


def test_poisson_gaps_have_the_rate():
    rng = np.random.default_rng(0)
    due = generator.poisson_due_times(2000, 8.0, rng)
    assert due[0] == 0 and np.all(np.diff(due) > 0)
    assert abs(np.mean(np.diff(due)) - 1 / 8.0) < 0.01


def test_only_open_loops_are_served():
    m = dict(mix("chat"), kind="closed_loop")
    with pytest.raises(ValueError, match="not a serving mix"):
        generator.serving_items(m, 1, 10, 1000)


def test_class_shards_deal_every_sample_once():
    y = np.repeat(np.arange(10), 600)
    parts = generator.class_shards(y, 100, 2, np.random.default_rng(1))
    allidx = np.concatenate(parts)
    assert len(allidx) == 6000 and len(np.unique(allidx)) == 6000
    assert {len(p) for p in parts} == {60}
    # each client sees at most two classes
    assert max(len(np.unique(y[p])) for p in parts) <= 2
    parts = generator.class_shards(np.repeat(np.arange(10), 6000), 1024, 2,
                                   np.random.default_rng(1))
    assert {len(p) for p in parts} <= {58, 59, 60}
