"""Operations and bytes that the algorithm needs, computed from shapes.

The counts are of the work, not of one implementation of it: a fan-in of C
clients over n ternary elements reads C·n/4 code bytes and writes 4n bytes
of f32 sum, whatever kernel does it. A roofline share built on these counts
therefore reads the same number for any later kernel that does the same
work. Work an implementation adds (the MXU packing product of the fused
encode, a padded transpose) is not counted.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and HBM bytes/s of one chip of ``device_kind``; an
    unknown device is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE}")
    return table[device_kind]


def roof_seconds(flops: float, nbytes: float, pk: dict) -> float:
    """Least time the chip could take: the larger of compute and memory time."""
    return max(flops / pk["flops_bf16"], nbytes / pk["hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# ResNet18* (arXiv:2003.03564 §V.A): 3x3 convs, all at `width` channels.
# ---------------------------------------------------------------------------


def resnet18s_params(width: int = 64, classes: int = 10, in_ch: int = 3,
                     blocks: int = 8) -> int:
    stem = 9 * in_ch * width + 2 * width
    block = 2 * 9 * width * width + 4 * width
    head = width * classes + classes
    return stem + blocks * block + head


def resnet18s_forward_flops(width: int = 64, classes: int = 10, hw: int = 32,
                            in_ch: int = 3, blocks: int = 8) -> float:
    """Multiply-adds ×2 of the convolutions and the head for one sample;
    stages halve the side at blocks 2, 4 and 6 (two blocks per stage)."""
    flops = 2.0 * hw * hw * 9 * in_ch * width
    side = hw
    for b in range(blocks):
        if b in (2, 4, 6):
            side = (side + 1) // 2
        flops += 2 * (2.0 * side * side * 9 * width * width)
    return flops + 2.0 * width * classes


def resnet18s_train_flops(**kw) -> float:
    """Forward + backward: the backward pass costs twice the forward."""
    return 3.0 * resnet18s_forward_flops(**kw)


# ---------------------------------------------------------------------------
# Decoder-only transformer (dense, gated MLP, tied or untied head).
# ---------------------------------------------------------------------------


def lm_matmul_shapes(cfg: dict) -> list[tuple[int, int]]:
    """(K, N) of each quantizable weight of one layer."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    return [(d, cfg["num_attention_heads"] * hd), (d, kv), (d, kv),
            (cfg["num_attention_heads"] * hd, d), (d, f), (d, f), (f, d)]


def lm_quantizable_params(cfg: dict) -> int:
    return cfg["num_hidden_layers"] * sum(k * n for k, n in lm_matmul_shapes(cfg))


def lm_embedding_params(cfg: dict) -> int:
    n = cfg["vocab_size"] * cfg["hidden_size"]
    return n if cfg["tie_word_embeddings"] else 2 * n


def lm_step_flops(cfg: dict, tokens: int, ctx: float) -> float:
    """Forward FLOPs of ``tokens`` new tokens, each attending over ``ctx``
    cached positions on average: weight matmuls, the head, and QK/PV."""
    d = cfg["hidden_size"]
    weights = lm_quantizable_params(cfg) + cfg["vocab_size"] * d
    attn = cfg["num_hidden_layers"] * 2 * 2 * ctx * d
    return tokens * (2.0 * weights + attn)


def lm_decode_step_bytes(cfg: dict, batch: int, ctx: float) -> float:
    """HBM bytes of one decode step: 2-bit codes of every quantizable weight,
    the f32 head, and the K and V of ``ctx`` positions for each sequence."""
    d = cfg["hidden_size"]
    codes = lm_quantizable_params(cfg) / 4
    head = 4.0 * cfg["vocab_size"] * d
    kv = cfg["num_hidden_layers"] * batch * ctx * 2 * d * 4
    return codes + head + kv


def lm_prefill_bytes(cfg: dict, batch: int, prompt: int) -> float:
    """Weights once, plus the K and V written for every prompt position."""
    d = cfg["hidden_size"]
    return (lm_quantizable_params(cfg) / 4 + 4.0 * cfg["vocab_size"] * d
            + cfg["num_hidden_layers"] * batch * prompt * 2 * d * 4)


def packed_matmul_cost(m: int, k: int, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of x(M,K) f32 @ ternary W(K,N) at 2 bits a weight."""
    return 2.0 * m * k * n, k * n / 4 + 4.0 * m * k + 4.0 * m * n


# ---------------------------------------------------------------------------
# Server fold (fan-in, re-quantize).
# ---------------------------------------------------------------------------


def fanin_cost(clients: int, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of Σ_c coeff_c · codes_c over n elements: C·n/4 code
    bytes in, 4n bytes of f32 sum out, one multiply-add per code."""
    return 2.0 * clients * n, clients * n / 4 + 4.0 * n


def requantize_cost(n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of scale, threshold and pack: 4n in, n/4 out, and a
    handful of elementwise operations per element."""
    return 4.0 * n, 4.0 * n + n / 4


def fold_cost(clients: int, n_ternary: int, n_raw: int) -> tuple[float, float]:
    """Whole fold: every upload's codes and raw leaves in, the f32 global
    out, then the re-quantize of the ternary part."""
    f1, b1 = fanin_cost(clients, n_ternary)
    f2, b2 = requantize_cost(n_ternary)
    raw_f, raw_b = 2.0 * clients * n_raw, 4.0 * clients * n_raw + 4.0 * n_raw
    return f1 + f2 + raw_f, b1 + b2 + raw_b

