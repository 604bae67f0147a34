"""Operations and bytes that each kernel and each whole step needs, from
shapes alone. These are what the algorithm requires, not what a program
happens to compute: padding, recomputation and logits nobody reads are not
counted. Rooflines and MFU divide by these."""
from __future__ import annotations

from typing import Dict, Iterable, Sequence, Tuple


# ---------------------------------------------------------------------------
# the federation's CNN
# ---------------------------------------------------------------------------


def cnn_layer_flops(c: dict) -> Dict[str, float]:
    """Forward multiply-adds x 2 per sample, per layer, for the paper's CNN:
    SAME 3x3 convolutions each followed by a 2x2 max-pool, then two dense
    layers."""
    k, size, c_in = c["kernel_size"], c["image_size"], c["channels"]
    out = {}
    for i, c_out in enumerate(c["conv_features"]):
        out[f"conv{i}"] = 2.0 * size * size * c_out * k * k * c_in
        c_in, size = c_out, size // 2
    flat = size * size * c_in
    out["fc1"] = 2.0 * flat * c["hidden"]
    out["fc2"] = 2.0 * c["hidden"] * c["num_classes"]
    return out


def cnn_train_flops(c: dict) -> float:
    """Forward plus backward per training sample. The backward pass costs two
    forwards per layer (input and weight gradients), except that the first
    layer needs no gradient for its input, the data."""
    layers = cnn_layer_flops(c)
    fwd = sum(layers.values())
    return 3.0 * fwd - layers["conv0"]


def cnn_forward_flops(c: dict) -> float:
    return sum(cnn_layer_flops(c).values())


def cnn_param_count(c: dict) -> int:
    k, size, c_in = c["kernel_size"], c["image_size"], c["channels"]
    n = 0
    for c_out in c["conv_features"]:
        n += k * k * c_in * c_out + c_out
        c_in, size = c_out, size // 2
    flat = size * size * c_in
    n += flat * c["hidden"] + c["hidden"]
    n += c["hidden"] * c["num_classes"] + c["num_classes"]
    return n


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def pearson(K: int, M: int, itemsize: int = 4) -> Tuple[float, float]:
    """Gram accumulation of K client rows over M coordinates: X X^T and the
    row sums. Reads X once and writes the (K, K) gram and (K,) sums."""
    flops = 2.0 * K * K * M + 1.0 * K * M
    nbytes = float(itemsize) * K * M + 4.0 * (K * K + K)
    return flops, nbytes


def paged_decode(lengths: Iterable[int], heads: int, kv_heads: int,
                 head_dim: int, itemsize: int = 2) -> Tuple[float, float]:
    """One layer of one-token attention over each row's cache: QK^T and PV
    over ``length`` cached positions per row; reads each row's keys and
    values once, and its query and output."""
    flops = nbytes = 0.0
    for n in lengths:
        flops += 4.0 * heads * head_dim * n
        nbytes += itemsize * (2.0 * n * kv_heads * head_dim
                              + 2.0 * heads * head_dim)
    return flops, nbytes


def flash_prefill(L: int, heads: int, kv_heads: int, head_dim: int,
                  itemsize: int = 2) -> Tuple[float, float]:
    """One layer of causal attention over a prompt of L tokens: each query
    attends to L(L+1)/2 keys over all queries; reads q, k, v and writes o
    once."""
    flops = 4.0 * heads * head_dim * L * (L + 1) / 2.0
    nbytes = itemsize * L * head_dim * (2.0 * heads + 2.0 * kv_heads)
    return flops, nbytes


# ---------------------------------------------------------------------------
# a dense decoder (qwen3) served
# ---------------------------------------------------------------------------


def dense_matmul_params(m: dict) -> Tuple[float, float]:
    """(per-token matmul weights in all layers, output head weights)."""
    d, dh = m["hidden_size"], m["head_dim"]
    q = m["num_attention_heads"] * dh
    kv = m["num_key_value_heads"] * dh
    ff = m["intermediate_size"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return float(m["num_hidden_layers"] * per_layer), float(d * m["vocab_size"])


def serve_flops(m: dict, prompts: Sequence[int],
                decode_lengths: Sequence[int]) -> float:
    """Model FLOPs of serving: each prompt through every layer and the head
    at its last position (the first token), and each decoded token through
    every layer and the head, attending over ``decode_lengths`` positions
    (the cache plus itself)."""
    layers, head = dense_matmul_params(m)
    H, Kv, D = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    n = m["num_hidden_layers"]
    total = 0.0
    for L in prompts:
        total += 2.0 * layers * L + 2.0 * head
        total += n * flash_prefill(L, H, Kv, D)[0]
    total += (2.0 * (layers + head)) * len(decode_lengths)
    total += n * paged_decode(decode_lengths, H, Kv, D)[0]
    return total
