"""The control, at a size a test run holds: the reference one precision
below the configuration's, put in the program's place, comes out not
correct against the configuration's limits, where the program comes out
correct. On the chip the same readings are taken at each cell's own size
by bench/calibrate.py."""
import json

import jax
import pytest

from bench import calibrate as C
from bench import common
from bench import run as R

FED_MIX = {"kind": "fed_jobs", "num_clients": 6, "train_samples": 600,
           "test_samples": 100, "shards_per_client": 2, "local_epochs": 2,
           "steps_per_epoch": 5, "batch_size": 16, "rounds": 4, "merge_at": 3}
SERVE_MIX = {"kind": "open_loop", "rate_per_s": 8.0,
             "prompt_buckets": [32, 64], "prompt_probs": [0.5, 0.5],
             "output": {"dist": "lognormal", "median": 12, "sigma": 0.3,
                        "min": 8, "max": 16},
             "check_tokens": 48}


def ctx_for(cell, config=None, mix=None):
    _m, c, _e, conf, mx = R.load_cell(cell)
    conf = conf if config is None else config
    ref = common.load_module(common.BENCH / "configs" / conf["reference"])
    return R.Ctx(c, conf, mx if mix is None else mix, 1, 2.0, 0, R.T0, jax,
                 common.Compiles(jax), ref, common.ROOT / ".bench_trace" / "t")


def fails(numbers, limits):
    return [k for k, v in numbers.items() if not common.Check(k, v, limits[k]).ok]


@pytest.mark.parametrize("seed", [2**33 + 1, 5])
def test_fed_control_is_not_correct(seed):
    ctx = ctx_for("fed-cnn-k100", mix=FED_MIX)
    r = C.fed_readings(ctx, seed)
    limits = ctx.config["limits"]
    assert not fails(r["program"], limits), r["program"]
    assert fails(r["control"], limits), r["control"]
    assert fails(r["half_batch"], limits) and fails(r["unchanged"], limits)


# The served model's logits scale with the square root of its width, so a
# limit on the logit gap holds at one size only. At this test's size (d
# 256, two layers) the program's mean gap read at most 2.7e-5 and the fp8
# control's at least 1.7e-3 on the CPU (three seeds); the test's limit
# sits between them, as the cell's limit sits between its own readings on
# the chip.
TINY_MEAN_TOKEN_GAP = 3e-4


def test_serve_control_is_not_correct():
    m = json.loads((common.BENCH / "configs" / "qwen3-1.7b.json").read_text())
    m = dict(m, hidden_size=256, intermediate_size=512, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=64,
             vocab_size=1024,
             serving=dict(m["serving"], num_slots=4, capacity=128))
    ctx = ctx_for("serve-qwen3-chat", config=m, mix=SERVE_MIX)
    r = C.serve_readings(ctx, 2**33 + 3, 2.0)
    limits = {"mean": TINY_MEAN_TOKEN_GAP}

    def mean(who):
        return {"mean": r[who]["mean"]}

    assert r["sampled_tokens"] >= 48
    assert not fails(mean("program"), limits), r
    assert fails(mean("control"), limits), r
    assert fails(mean("token_altered"), limits), r
