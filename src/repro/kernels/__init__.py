"""Pallas TPU kernels for the perf-critical compute layers:

  pearson/      -- streaming K x K Pearson correlation over flattened client
                   parameter vectors (the paper technique's at-scale hot spot)
  decode_attn/  -- flash-decode GQA attention (serving hot loop)
  flash_prefill/ -- flash-attention prefill (serving admission)

Each package ships <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper), ref.py (pure-jnp oracle). TPU is the lowering target; on the CPU
the kernels run in interpret mode, which platform.py derives from the
platform.
"""
from repro.kernels.pearson.ops import pearson_corr
from repro.kernels.decode_attn.ops import decode_attention
from repro.kernels.flash_prefill.ops import flash_prefill_attention
