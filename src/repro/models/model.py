"""Model-level API for every assigned architecture family.

  init_params(rng, cfg)                     -> param pytree
  forward(params, cfg, batch)               -> logits
  loss_fn(params, cfg, batch)               -> (scalar f32, metrics)
  prefill(params, cfg, batch)               -> (logits_last, decode_states)
  init_decode(cfg, batch, max_len, dtype)   -> decode states
  decode_step(params, cfg, states, tokens, pos) -> (logits, states)

Batches:
  dense/moe/ssm/hybrid : {"tokens": (B, S) int32}
  vlm                  : {"tokens": (B, S_text)}, {"patch_embeds": (B, P, D_VIT)}
  audio                : {"frames": (B, S, D_FEAT)}, {"labels": (B, S) int32}

The modality frontends are stubs per the assignment: ``patch_embeds`` /
``frames`` are precomputed embeddings of the right shape; the projector
(d_vit -> d_model / d_feat -> d_model) IS part of the model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import blocks as B
from repro.models import layers as L

D_VIT = 1152   # SigLIP-style vision tower output width (stub frontend)
D_FEAT = 512   # wav2vec2/hubert conv feature extractor width (stub frontend)


def param_dtype(cfg):
    return jnp.dtype(cfg.dtype)


def init_params(rng, cfg):
    dtype = param_dtype(cfg)
    k_emb, k_blocks, k_head, k_proj = jax.random.split(rng, 4)
    params = {
        "blocks": B.init_blocks(k_blocks, cfg, dtype),
        "final_norm": L.rmsnorm_init(cfg.d_model, dtype),
        "lm_head": L.dense_init(k_head, cfg.d_model, cfg.vocab_size, dtype, scale=0.02),
    }
    if cfg.family == "audio":
        params["in_proj"] = L.dense_init(k_proj, D_FEAT, cfg.d_model, dtype)
    else:
        params["embed"] = L.embed_init(k_emb, cfg.vocab_size, cfg.d_model, dtype)
    if cfg.family == "vlm":
        params["projector"] = L.dense_init(k_proj, D_VIT, cfg.d_model, dtype)
    return params


def _embed_inputs(params, cfg, batch):
    """-> (x (B,S,D), positions (S,))"""
    if cfg.family == "audio":
        x = batch["frames"].astype(param_dtype(cfg)) @ params["in_proj"]
    elif cfg.family == "vlm":
        tok = jnp.take(params["embed"], batch["tokens"], axis=0)
        patches = batch["patch_embeds"].astype(param_dtype(cfg)) @ params["projector"]
        x = jnp.concatenate([patches, tok], axis=1)
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
    positions = jnp.arange(x.shape[1])
    return x, positions


def forward(params, cfg, batch, return_state: bool = False, remat: bool = True):
    x, positions = _embed_inputs(params, cfg, batch)
    x, states, aux = B.blocks_fwd(
        params["blocks"], cfg, x, positions, return_state=return_state, remat=remat
    )
    x = L.rmsnorm(params["final_norm"], x)
    logits = x @ params["lm_head"]
    if return_state:
        return logits, states, aux
    return logits, aux


def _xent(logits, labels, mask=None):
    """Cross-entropy in f32; logits (..., V), labels (...) int."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def loss_fn(params, cfg, batch, remat: bool = True):
    logits, aux = forward(params, cfg, batch, remat=remat)
    if cfg.family == "audio":
        loss = _xent(logits, batch["labels"])
    elif cfg.family == "vlm":
        P = batch["patch_embeds"].shape[1]
        text_logits = logits[:, P - 1 : -1]          # predict text tokens
        loss = _xent(text_logits, batch["tokens"])
    else:
        loss = _xent(logits[:, :-1], batch["tokens"][:, 1:])
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux": aux}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def init_decode(cfg, batch: int, max_len: int):
    return B.init_decode_states(cfg, batch, max_len, param_dtype(cfg))


def init_decode_paged(cfg, batch: int, max_row_len: int, block_size: int,
                      num_blocks: int):
    """Paged serving arena: attention caches become global page pools with
    per-row block tables (see layers.attention_init_cache_paged)."""
    return B.init_decode_states_paged(cfg, batch, max_row_len,
                                      param_dtype(cfg), block_size, num_blocks)


def prefill(params, cfg, batch):
    """Full forward that also returns per-layer decode states."""
    logits, states, _aux = forward(params, cfg, batch, return_state=True, remat=False)
    return logits[:, -1], states


def decode_step(params, cfg, states, tokens, pos):
    """tokens (B,) int32, pos (B,) int32 absolute position of the new token.
    Its phases carry named scopes: embed, layers, head."""
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens[:, None], axis=0)
    with jax.named_scope("layers"):
        x, new_states = B.blocks_decode(params["blocks"], cfg, x, states, pos)
    with jax.named_scope("head"):
        x = L.rmsnorm(params["final_norm"], x)
        logits = (x @ params["lm_head"])[:, 0]
    return logits, new_states
