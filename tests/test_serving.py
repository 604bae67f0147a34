"""Serving-path tests: prefill -> decode consistency against the full
forward pass, per architecture family, plus the generate loop."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import generate, states_from_prefill
from repro.models import decode_step, forward, init_params, prefill

FAMS = ["qwen3-1.7b", "recurrentgemma-2b", "xlstm-125m",
        "granite-moe-1b-a400m", "yi-34b"]


def _cfg(arch):
    return dataclasses.replace(get_config(arch).reduced(), dtype="float32")


@pytest.mark.parametrize("arch", FAMS)
def test_decode_matches_forward(arch, rng):
    """Logits for token S via prefill(S)+decode == full forward over S+1."""
    cfg = _cfg(arch)
    params = init_params(rng, cfg)
    B, S = 2, 48
    toks = jax.random.randint(rng, (B, S + 1), 0, cfg.vocab_size)
    logits_full, _ = forward(params, cfg, {"tokens": toks}, remat=False)
    _, raw = prefill(params, cfg, {"tokens": toks[:, :S]})
    states = states_from_prefill(cfg, raw, S, capacity=S + 8)
    logits_dec, _ = decode_step(
        params, cfg, states, toks[:, S], jnp.full((B,), S, jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(logits_dec), np.asarray(logits_full[:, -1]), atol=2e-4
    )


@pytest.mark.parametrize("S", [1, 2])
def test_decode_matches_forward_prompt_shorter_than_conv(S, rng):
    """A prompt shorter than the RG-LRU's causal conv window: prefill hands
    decode the window with the conv's zero padding in front."""
    cfg = _cfg("recurrentgemma-2b")
    params = init_params(rng, cfg)
    toks = jax.random.randint(rng, (2, S + 1), 0, cfg.vocab_size)
    logits_full, _ = forward(params, cfg, {"tokens": toks}, remat=False)
    _, raw = prefill(params, cfg, {"tokens": toks[:, :S]})
    states = states_from_prefill(cfg, raw, S, capacity=S + 8)
    logits_dec, _ = decode_step(
        params, cfg, states, toks[:, S], jnp.full((2,), S, jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(logits_dec), np.asarray(logits_full[:, -1]), atol=2e-4
    )


def test_multi_token_decode_consistency(rng):
    """Greedy generate agrees with repeated full forwards (teacher forcing
    its own outputs) for a dense model."""
    cfg = _cfg("qwen3-1.7b")
    params = init_params(rng, cfg)
    B, S, N = 1, 24, 6
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    out, _ = generate(params, cfg, {"tokens": toks}, max_new_tokens=N)
    # reference: grow the sequence with full forwards
    seq = toks
    ref = []
    for _ in range(N):
        logits, _ = forward(params, cfg, {"tokens": seq}, remat=False)
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        ref.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(jnp.stack(ref, 1)))


def test_sliding_window_ring_cache(rng):
    """Windowed decode: cache shorter than the sequence still matches the
    windowed full forward."""
    cfg = dataclasses.replace(_cfg("qwen3-1.7b"), window_size=16)
    params = init_params(rng, cfg)
    B, S = 1, 40
    toks = jax.random.randint(rng, (B, S + 1), 0, cfg.vocab_size)
    logits_full, _ = forward(params, cfg, {"tokens": toks}, remat=False)
    _, raw = prefill(params, cfg, {"tokens": toks[:, :S]})
    states = states_from_prefill(cfg, raw, S, capacity=S + 8)
    logits_dec, _ = decode_step(
        params, cfg, states, toks[:, S], jnp.full((B,), S, jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(logits_dec), np.asarray(logits_full[:, -1]), atol=2e-4
    )


def test_encoder_only_generate_rejected(rng):
    cfg = _cfg("hubert-xlarge")
    params = init_params(rng, cfg)
    with pytest.raises(AssertionError):
        generate(params, cfg, {"tokens": jnp.zeros((1, 4), jnp.int32)})


def test_generate_jit_eager_parity(rng):
    """The cached jitted decode path is token-identical to the eager loop
    (the retracing fix cannot change what generate emits)."""
    cfg = _cfg("xlstm-125m")
    params = init_params(rng, cfg)
    toks = jax.random.randint(rng, (2, 12), 0, cfg.vocab_size)
    out_j, _ = generate(params, cfg, {"tokens": toks}, max_new_tokens=8,
                        jit_decode=True)
    out_e, _ = generate(params, cfg, {"tokens": toks}, max_new_tokens=8,
                        jit_decode=False)
    np.testing.assert_array_equal(np.asarray(out_j), np.asarray(out_e))


def test_generate_does_not_retrace_per_token(rng):
    """One decode compile per (config, shapes) — not one per token or per
    generate call."""
    from repro.launch.serve import decode_step_fn
    cfg = _cfg("xlstm-125m")
    params = init_params(rng, cfg)
    toks = jax.random.randint(rng, (1, 8), 0, cfg.vocab_size)
    fn = decode_step_fn(cfg)
    before = fn._cache_size()
    generate(params, cfg, {"tokens": toks}, max_new_tokens=6)
    generate(params, cfg, {"tokens": toks}, max_new_tokens=6)
    assert fn._cache_size() <= before + 1
    assert decode_step_fn(cfg) is fn  # per-config cache is stable
