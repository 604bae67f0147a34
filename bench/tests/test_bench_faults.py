"""A whole run on the CPU at a small size, past the look for a chip: a sound
program comes out correct, and each fault a cell can have, planted in the
timed path underneath, comes out not correct.

Federation: a round that hands back the state it got; half of each batch
left out, the mean taken over the rest. Serving: a token altered where it
is produced. (Every cell runs on one chip, so no exchange between chips
can be left out.)
"""
import json

import pytest

from bench import common
from bench import run as R

PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
FED_MIX = {"kind": "fed_jobs", "num_clients": 6, "train_samples": 600,
           "test_samples": 100, "shards_per_client": 2, "local_epochs": 1,
           "steps_per_epoch": 3, "batch_size": 8, "rounds": 4, "merge_at": 3}
SERVE_MIX = {"kind": "open_loop", "rate_per_s": 6.0,
             "prompt_buckets": [16, 32], "prompt_probs": [0.5, 0.5],
             "output": {"dist": "lognormal", "median": 6, "sigma": 0.3,
                        "min": 4, "max": 8},
             "check_tokens": 24}


def tiny_qwen():
    m = json.loads((common.BENCH / "configs" / "qwen3-1.7b.json").read_text())
    return dict(m, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                vocab_size=512, serving=dict(m["serving"], num_slots=4,
                                             capacity=64))


def fed_run():
    return R.execute("fed-cnn-k100", 2**33 + 11, 1.0, 0, chip=False,
                     mix=FED_MIX, peak=PEAK)


def serve_run():
    return R.execute("serve-qwen3-chat", 2**33 + 13, 1.5, 0, chip=False,
                     config=tiny_qwen(), mix=SERVE_MIX, peak=PEAK)


def test_fed_sound_run_is_correct():
    out = fed_run()
    assert out["correct"], out["checks"]
    assert out["attempted"] >= FED_MIX["rounds"]
    assert set(out["metrics"]) == {"fed_round_ms", "setup_s"}
    assert list(out)[-1] == "checks"


def test_fed_state_unchanged_is_caught(monkeypatch):
    import repro.core.engine as E

    real = E.make_round_fn

    def broken(loss_fn, algo):
        rnd = real(loss_fn, algo)

        def f(x_g, c_g, c_l, *rest):
            out = rnd(x_g, c_g, c_l, *rest)
            return (x_g, c_g, c_l) + tuple(out[3:])

        return f

    monkeypatch.setattr(E, "make_round_fn", broken)
    out = fed_run()
    assert not out["correct"]
    assert out["checks"]["first_update_gap"]["value"] == pytest.approx(1.0)


def test_fed_half_batch_is_caught(monkeypatch):
    import repro.models as Mo

    real = Mo.cnn_loss

    def half(params, cfg, batch):
        n = batch["y"].shape[0] // 2
        return real(params, cfg, {"x": batch["x"][:n], "y": batch["y"][:n]})

    monkeypatch.setattr(Mo, "cnn_loss", half)
    out = fed_run()
    assert not out["correct"], out["checks"]


def test_serve_sound_run_is_correct():
    out = serve_run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"serve_tokens_per_s", "serve_itl_p95_ms",
            "setup_s"} == set(out["metrics"])


def test_serve_token_altered_is_caught(monkeypatch):
    import repro.serving.engine as E

    real = E._paged_step

    def broken(cfg, n_rows, t_view):
        step = real(cfg, n_rows, t_view)

        def f(*args):
            nxt, arena = step(*args)
            return (nxt + 1) % cfg.vocab_size, arena

        return f

    monkeypatch.setattr(E, "_paged_step", broken)
    out = serve_run()
    assert not out["correct"]
    assert out["checks"]["mean_token_gap"]["value"] > 0.1
