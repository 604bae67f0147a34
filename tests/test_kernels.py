"""Per-kernel shape/dtype sweeps vs. the pure-jnp ref.py oracles
(interpret=True on CPU). (Deliverable c.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attn.ops import decode_attention
from repro.kernels.decode_attn.ref import decode_attention_ref
from repro.kernels.pearson.ops import pearson_corr
from repro.kernels.pearson.ref import pearson_corr_ref


# ---------------------------------------------------------------------------
# pearson
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "K,M",
    [(2, 64), (3, 100), (7, 2048), (10, 5000), (16, 8192), (12, 12345), (33, 4096),
     (800, 700)],  # K=800: past v5e's default scoped VMEM (pearson.VMEM_LIMIT)
)
def test_pearson_matches_ref(K, M, nprng):
    X = jnp.asarray(nprng.normal(size=(K, M)).astype(np.float32))
    out = pearson_corr(X, interpret=True)
    ref = pearson_corr_ref(X)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pearson_dtypes(dtype, nprng):
    X = jnp.asarray(nprng.normal(size=(10, 4096)).astype(np.float32)).astype(dtype)
    out = pearson_corr(X, interpret=True)
    ref = pearson_corr_ref(X)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-3)


def test_pearson_constant_rows(nprng):
    X = jnp.asarray(
        np.vstack([np.ones((2, 1000)), nprng.normal(size=(3, 1000))]).astype(
            np.float32
        )
    )
    out = np.asarray(pearson_corr(X, interpret=True))
    ref = np.asarray(pearson_corr_ref(X))
    np.testing.assert_allclose(out, ref, atol=1e-5)
    assert out[0, 1] == 0.0 and out[0, 0] == 1.0


def test_pearson_perfect_correlation(nprng):
    base = nprng.normal(size=4096).astype(np.float32)
    X = jnp.asarray(np.stack([base, 2 * base + 1, -base, base + 0.5]))
    out = np.asarray(pearson_corr(X, interpret=True))
    np.testing.assert_allclose(out[0, 1], 1.0, atol=1e-4)
    np.testing.assert_allclose(out[0, 2], -1.0, atol=1e-4)
    np.testing.assert_allclose(out[0, 3], 1.0, atol=1e-4)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,Hq,Kv,D,S,window",
    [
        (2, 8, 2, 64, 1024, 0),
        (1, 56, 8, 128, 2048, 0),    # yi/llava GQA geometry
        (2, 4, 4, 80, 700, 0),       # hubert head_dim, ragged S
        (2, 16, 8, 128, 1024, 256),  # sliding window
        (1, 10, 1, 256, 1536, 0),    # recurrentgemma MQA geometry
        (2, 48, 4, 128, 640, 0),     # starcoder2 geometry
    ],
)
def test_decode_attn_matches_ref(B, Hq, Kv, D, S, window, nprng):
    q = jnp.asarray(nprng.normal(size=(B, Hq, D)).astype(np.float32))
    k = jnp.asarray(nprng.normal(size=(B, S, Kv, D)).astype(np.float32))
    v = jnp.asarray(nprng.normal(size=(B, S, Kv, D)).astype(np.float32))
    lengths = jnp.asarray(nprng.integers(S // 2, S + 1, B), jnp.int32)
    out = decode_attention(q, k, v, lengths, window=window, interpret=True)
    ref = decode_attention_ref(q, k, v, lengths, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_decode_attn_bf16(nprng):
    B, Hq, Kv, D, S = 2, 8, 4, 128, 1024
    mk = lambda s: jnp.asarray(nprng.normal(size=s).astype(np.float32)).astype(
        jnp.bfloat16
    )
    q, k, v = mk((B, Hq, D)), mk((B, S, Kv, D)), mk((B, S, Kv, D))
    lengths = jnp.full((B,), S, jnp.int32)
    out = decode_attention(q, k, v, lengths, interpret=True).astype(jnp.float32)
    ref = decode_attention_ref(q, k, v, lengths).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-2)


def test_decode_attn_backend_selection(nprng):
    """Backend auto-selection mirrors FLConfig.pearson_backend: "auto"
    resolves to the jnp reference on CPU, conflicting explicit flags raise,
    unknown values raise — never a silent fallback."""
    from repro.kernels.decode_attn.ops import resolve_decode_backend

    B, Hq, Kv, D, S = 2, 8, 2, 64, 256
    q = jnp.asarray(nprng.normal(size=(B, Hq, D)).astype(np.float32))
    k = jnp.asarray(nprng.normal(size=(B, S, Kv, D)).astype(np.float32))
    v = jnp.asarray(nprng.normal(size=(B, S, Kv, D)).astype(np.float32))
    lengths = jnp.asarray([5, S], jnp.int32)

    # on CPU, "auto" must be the pure-jnp reference, bit for bit
    assert jax.default_backend() == "cpu"
    assert resolve_decode_backend("auto") == "reference"
    out_auto = decode_attention(q, k, v, lengths, backend="auto")
    out_ref = decode_attention_ref(q, k, v, lengths)
    np.testing.assert_array_equal(np.asarray(out_auto), np.asarray(out_ref))

    # deprecated interpret kwarg keeps working and maps onto backends
    out_i = decode_attention(q, k, v, lengths, interpret=True)
    out_b = decode_attention(q, k, v, lengths, backend="interpret")
    np.testing.assert_array_equal(np.asarray(out_i), np.asarray(out_b))

    # conflicting explicit flags raise
    with pytest.raises(ValueError, match="conflicting"):
        decode_attention(q, k, v, lengths, backend="reference",
                         interpret=True)
    with pytest.raises(ValueError, match="conflicting"):
        decode_attention(q, k, v, lengths, backend="interpret",
                         interpret=False)
    # non-conflicting combinations resolve
    assert resolve_decode_backend("interpret", interpret=True) == "interpret"
    assert resolve_decode_backend("auto", interpret=False) == "pallas"
    with pytest.raises(ValueError, match="one of"):
        decode_attention(q, k, v, lengths, backend="mosaic")


def test_decode_attn_length_zero_row(nprng):
    """A length-0 row (dead serving lane) finalizes to zeros, never NaN,
    and does not disturb live rows."""
    B, Hq, Kv, D, S = 2, 4, 2, 64, 256
    q = jnp.asarray(nprng.normal(size=(B, Hq, D)).astype(np.float32))
    k = jnp.asarray(nprng.normal(size=(B, S, Kv, D)).astype(np.float32))
    v = jnp.asarray(nprng.normal(size=(B, S, Kv, D)).astype(np.float32))
    lengths = jnp.asarray([0, 77], jnp.int32)
    out = np.asarray(decode_attention(q, k, v, lengths, interpret=True))
    assert np.all(np.isfinite(out))
    np.testing.assert_array_equal(out[0], np.zeros_like(out[0]))
    ref = decode_attention_ref(q[1:], k[1:], v[1:], lengths[1:])
    np.testing.assert_allclose(out[1], np.asarray(ref)[0], atol=2e-5)


def test_decode_attn_short_length(nprng):
    """length = 1: attends to exactly one slot."""
    B, Hq, Kv, D, S = 1, 4, 2, 64, 512
    q = jnp.asarray(nprng.normal(size=(B, Hq, D)).astype(np.float32))
    k = jnp.asarray(nprng.normal(size=(B, S, Kv, D)).astype(np.float32))
    v = jnp.asarray(nprng.normal(size=(B, S, Kv, D)).astype(np.float32))
    lengths = jnp.ones((B,), jnp.int32)
    out = decode_attention(q, k, v, lengths, interpret=True)
    ref = decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # with one valid slot, output = v[:, 0] per kv group
    expect = np.repeat(np.asarray(v[:, 0]), Hq // Kv, axis=1).reshape(B, Hq, D)
    np.testing.assert_allclose(np.asarray(out), expect, atol=2e-5)


# ---------------------------------------------------------------------------
# flash prefill attention
# ---------------------------------------------------------------------------

from repro.kernels.flash_prefill.ops import flash_prefill_attention
from repro.kernels.flash_prefill.ref import flash_prefill_ref


@pytest.mark.parametrize(
    "B,S,Hq,Kv,D,causal,window",
    [
        (1, 256, 8, 2, 64, True, 0),
        (2, 384, 4, 4, 80, True, 0),      # ragged S, MHA, odd head dim
        (1, 512, 14, 2, 128, True, 0),    # G=7 GQA folding (yi geometry)
        (1, 256, 8, 8, 128, False, 0),    # bidirectional (encoder)
        (1, 512, 8, 2, 64, True, 128),    # sliding window
        (1, 300, 10, 1, 256, True, 0),    # MQA, ragged (recurrentgemma)
    ],
)
def test_flash_prefill_matches_ref(B, S, Hq, Kv, D, causal, window, nprng):
    q = jnp.asarray(nprng.normal(size=(B, S, Hq, D)).astype(np.float32))
    k = jnp.asarray(nprng.normal(size=(B, S, Kv, D)).astype(np.float32))
    v = jnp.asarray(nprng.normal(size=(B, S, Kv, D)).astype(np.float32))
    out = flash_prefill_attention(q, k, v, causal=causal, window=window,
                                  interpret=True)
    ref = flash_prefill_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_flash_prefill_bf16(nprng):
    B, S, Hq, Kv, D = 1, 256, 8, 4, 128
    mk = lambda s: jnp.asarray(nprng.normal(size=s).astype(np.float32)).astype(jnp.bfloat16)
    q, k, v = mk((B, S, Hq, D)), mk((B, S, Kv, D)), mk((B, S, Kv, D))
    out = flash_prefill_attention(q, k, v, interpret=True).astype(jnp.float32)
    ref = flash_prefill_ref(q, k, v).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-2)
