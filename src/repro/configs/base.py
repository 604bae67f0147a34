"""Config system: one dataclass that covers every assigned architecture family.

Each architecture file in this package registers a ``ModelConfig`` under its
public id (``--arch <id>``). ``reduced()`` returns the smoke-test variant
(≤2 layers, d_model ≤ 512, ≤4 experts) of the same family.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

from repro.utils.registry import Registry

# ---------------------------------------------------------------------------
# Input shapes assigned to this paper (public pool).
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


@dataclass(frozen=True)
class ModelConfig:
    """Architecture description.

    ``family`` selects the block structure:
      dense   — pre-norm decoder, GQA attention + SwiGLU MLP
      moe     — dense attention + top-k expert MLP
      ssm     — xLSTM (mix of mLSTM / sLSTM blocks, no separate FFN)
      hybrid  — RG-LRU recurrent blocks : local-attention blocks (2:1)
      vlm     — dense decoder consuming text tokens + stub patch embeddings
      audio   — encoder-only (bidirectional) transformer on stub frame embeds
    """

    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int
    citation: str = ""

    # attention options
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    # "blocked"  — q-block scan, full (q_blk, T) score rows (the recorded
    #              §Roofline baseline)
    # "online"   — flash-style: python loop over q blocks, inner kv-block
    #              scan with online-softmax (m, l, acc) carry, triangular
    #              causal scheduling (skips fully-masked kv blocks), bf16
    #              probs for the PV matmul. §Perf H1 — 21x lower memory
    #              term, numerically equivalent; the default.
    attn_impl: str = "online"
    # sliding-window attention. 0 = full attention. Used natively by the
    # hybrid family ("local attn") and as the sub-quadratic variant that
    # unlocks long_500k for dense/vlm archs.
    window_size: int = 0
    # decode-attention backend for the one-token serving path
    # (models/layers.attention_decode). Mirrors FLConfig.pearson_backend:
    #   "auto"      — compiled Pallas flash-decode on TPU/GPU, the masked
    #                 jnp path on CPU (the parity-oracle numerics)
    #   "pallas"    — force the compiled Pallas kernel
    #   "interpret" — force the Pallas kernel in interpret mode (tests)
    #   "jnp"       — force the masked jnp path
    # Unknown values raise at the first decode step, never silently fall
    # back.
    decode_attn_backend: str = "auto"
    # admission-time prefill attention backend (the full-sequence pass run
    # once per admitted request). Mirrors decode_attn_backend:
    #   "auto"      — kernels/flash_prefill on TPU/GPU, the jnp blocked/
    #                 online path on CPU
    #   "pallas"    — force the compiled flash-prefill kernel
    #   "interpret" — force the kernel in interpret mode (tests)
    #   "jnp"       — force the jnp path
    # Only the cache-returning prefill pass uses the kernel (the training
    # forward stays on the differentiable jnp implementations). Unknown
    # values raise, never a silent fallback.
    prefill_backend: str = "auto"
    # serving KV-cache layout: "paged" (a global pool of
    # kv_block_size-position blocks + a per-row block table) is the only
    # one. The serving engine reads nothing here; the field stays because
    # bench/drivers/serve.py sets it.
    kv_layout: str = "paged"
    # paged-arena page size: cache positions per KV block
    kv_block_size: int = 16

    # MoE
    num_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25
    # §Perf H2: with_sharding_constraint hints inside the MoE dispatch
    # (expert buffers over 'model', token tensors over 'data') — GSPMD
    # otherwise replicates the scatter/gather operands. Needs an ambient
    # mesh, so off by default (CPU tests run without one).
    moe_hints: bool = False
    # "gspmd" — scatter/gather dispatch, auto-partitioned (baseline)
    # "ep"    — explicit expert-parallel shard_map dispatch (§Perf H2-it4;
    #           falls back to gspmd when no mesh is ambient)
    moe_impl: str = "gspmd"
    # Adam moment dtype (§Perf H2-it7: "bfloat16" halves the optimizer
    # state — the dominant term of the resident train state at 400B scale)
    opt_moments: str = "float32"

    # hybrid (recurrentgemma): pattern unit, e.g. ("rglru","rglru","attn")
    block_pattern: Tuple[str, ...] = ()
    rglru_width: Optional[int] = None  # recurrence width (= d_model here)

    # ssm (xlstm): indices of sLSTM blocks; all others are mLSTM
    slstm_at: Tuple[int, ...] = ()

    # modality frontend stub: number of prefix embedding tokens supplied by
    # the (stubbed) vision tower; audio uses the whole sequence as frames.
    num_patch_tokens: int = 0

    dtype: str = "bfloat16"

    # ----- derived ---------------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def is_encoder(self) -> bool:
        return self.family == "audio"

    @property
    def supports_decode(self) -> bool:
        return not self.is_encoder

    def supports_shape(self, shape_name: str) -> bool:
        """Which assigned input shapes this arch runs (skips per DESIGN.md)."""
        shape = INPUT_SHAPES[shape_name]
        if shape.kind == "decode" and not self.supports_decode:
            return False  # encoder-only: no autoregressive decode
        return True

    def decode_variant(self, shape_name: str) -> "ModelConfig":
        """For long_500k on full-attention archs, switch to the
        sliding-window sub-quadratic variant (window 4096)."""
        if (
            shape_name == "long_500k"
            and self.family in ("dense", "moe", "vlm")
            and self.window_size == 0
        ):
            return dataclasses.replace(self, window_size=4_096)
        return self

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: same family/block structure, tiny dims."""
        n_layers = min(self.num_layers, 2)
        d_model = min(self.d_model, 256)
        heads = min(self.num_heads, 4)
        head_dim = min(self.head_dim, 64)
        kv = min(self.num_kv_heads, heads)
        repl = dict(
            num_layers=n_layers,
            d_model=d_model,
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=head_dim,
            d_ff=0 if self.d_ff == 0 else min(self.d_ff, 512),
            vocab_size=min(self.vocab_size, 512),
            window_size=min(self.window_size, 64) if self.window_size else 0,
        )
        if self.num_experts:
            repl["num_experts"] = min(self.num_experts, 4)
            repl["experts_per_token"] = min(
                self.experts_per_token, repl["num_experts"]
            )
        if self.block_pattern:
            repl["block_pattern"] = self.block_pattern
        if self.slstm_at:
            repl["slstm_at"] = tuple(i for i in self.slstm_at if i < n_layers) or (0,)
        if self.num_patch_tokens:
            repl["num_patch_tokens"] = 8
        if self.rglru_width is not None:
            repl["rglru_width"] = d_model
        return dataclasses.replace(self, name=self.name + "-smoke", **repl)


CONFIGS: Registry[ModelConfig] = Registry("arch config")


def get_config(name: str) -> ModelConfig:
    return CONFIGS.get(name)()


def list_archs():
    return CONFIGS.names()
