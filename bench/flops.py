"""Operations and bytes the algorithms need, computed from their shapes.

Each function counts what the computation requires, not what a kernel
happens to read: a padded block or a recomputed activation is not counted.
"""
from __future__ import annotations

from typing import Tuple


# ---------------------------------------------------------- decoder LM step
def lm_matmul_params(c: dict) -> int:
    """Weights one token multiplies with (the tied head counted once)."""
    D, F, V, L = (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
                  c["num_hidden_layers"])
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    hd = D // H
    per_layer = D * (H + 2 * K) * hd + H * hd * D + 3 * D * F
    return L * per_layer + D * V


def lm_params(c: dict) -> int:
    """All weights of the model (tied embedding once, norm scales too)."""
    D, L = c["hidden_size"], c["num_hidden_layers"]
    return lm_matmul_params(c) + L * 2 * D + D


def attention_cost(c: dict, lanes: int, length: int, kv_itemsize: int) -> Tuple[float, float]:
    """One decode attention call of one layer: `lanes` queries against the
    first `length` cached positions (what the kernel must read, not the
    blocks it does read)."""
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // H
    flops = 4.0 * lanes * H * hd * length
    nbytes = lanes * length * 2.0 * K * hd * kv_itemsize + 2.0 * lanes * H * hd * 2
    return flops, nbytes


def decode_step_flops(c: dict, lanes: int, length: int) -> float:
    """One fleet step: every lane's token through all layers and the head."""
    attn = attention_cost(c, lanes, length, 4)[0] * c["num_hidden_layers"]
    return 2.0 * lm_matmul_params(c) * lanes + attn


def decode_step_bytes(c: dict, lanes: int, length: int, weight_itemsize: int,
                      kv_itemsize: int) -> float:
    """What one fleet step must read: every weight once, and each lane's
    keys and values up to its length, at the dtypes the program holds."""
    attn = attention_cost(c, lanes, length, kv_itemsize)[1] * c["num_hidden_layers"]
    return lm_params(c) * weight_itemsize + attn
