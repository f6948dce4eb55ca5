"""Operations and bytes of the granite-4.0-h decode step and of its
`ssm_decode` kernel, computed from their shapes.

Each function counts what the computation requires, not what a kernel
happens to read: a padded block or a recomputed activation is not counted.
Weights are held in bf16 except A_log, dt_bias and D (float32, as the
program holds them); the recurrent state, the convolution window and the
keys and values are float32.
"""
from __future__ import annotations

from typing import Tuple

from reference.granite_hybrid import dims, kinds

F32 = 4


def _attention_matmul_params(d: dict) -> int:
    return d["D"] * (d["H"] + 2 * d["K"]) * d["hd"] + d["H"] * d["hd"] * d["D"]


def _mamba_matmul_params(d: dict) -> int:
    """in_proj, the depthwise convolution's taps, out_proj."""
    return (d["D"] * (2 * d["Di"] + 2 * d["N"] + d["Hm"]) + d["W"] * d["C"]
            + d["Di"] * d["D"])


def matmul_params(c: dict) -> int:
    """Weights one token multiplies with (the tied head counted once)."""
    d = dims(c)
    mambas, attns = kinds(c)
    mlp = 3 * d["D"] * d["F"]
    return (len(mambas) * (_mamba_matmul_params(d) + mlp)
            + len(attns) * (_attention_matmul_params(d) + mlp) + d["D"] * d["V"])


def weight_bytes(c: dict) -> int:
    """Every weight once at the dtype the program holds it: the matrices,
    the conv taps and bias and the norm scales in bf16, A_log, dt_bias and D
    in float32."""
    d = dims(c)
    mambas, attns = kinds(c)
    norms = (len(mambas) * (2 * d["D"] + d["Di"]) + len(attns) * 2 * d["D"] + d["D"])
    bf16 = matmul_params(c) + len(mambas) * d["C"] + norms
    return 2 * bf16 + F32 * len(mambas) * 3 * d["Hm"]


def ssm_decode_cost(c: dict, lanes: int) -> Tuple[float, float]:
    """One `ssm_decode` call (one layer, every lane): h <- dA h + B dtx (3
    operations an element of the state) and y = C h (2 more); the state
    read and written, dA, dtx and y (lanes x H·P each), B and C (lanes x N
    each), all float32."""
    d = dims(c)
    state = lanes * d["Hm"] * d["P"] * d["N"]
    flops = 5.0 * state
    nbytes = F32 * (2 * state + 3 * lanes * d["Di"] + 2 * lanes * d["N"])
    return flops, nbytes


def attention_cost(c: dict, lanes: int, length: int) -> Tuple[float, float]:
    """One decode attention call of one layer: `lanes` queries against the
    first `length` float32 cached positions, bf16 queries in and outputs
    back."""
    d = dims(c)
    flops = 4.0 * lanes * d["H"] * d["hd"] * length
    nbytes = lanes * length * 2.0 * d["K"] * d["hd"] * F32 + 2.0 * lanes * d["H"] * d["hd"] * 2
    return flops, nbytes


def decode_step_flops(c: dict, lanes: int, length: int) -> float:
    """One fleet step: every lane's token through all layers and the head."""
    mambas, attns = kinds(c)
    return (2.0 * matmul_params(c) * lanes + len(mambas) * ssm_decode_cost(c, lanes)[0]
            + len(attns) * attention_cost(c, lanes, length)[0])


def decode_step_bytes(c: dict, lanes: int, length: int) -> float:
    """What one fleet step must move: every weight once, each Mamba2
    layer's state read and written and its convolution window (d_conv - 1
    positions read, one written), and each lane's keys and values up to its
    length."""
    d = dims(c)
    mambas, attns = kinds(c)
    state = 2.0 * F32 * lanes * d["Hm"] * d["P"] * d["N"]
    conv = F32 * lanes * d["C"] * d["W"]
    kv = lanes * length * 2.0 * d["K"] * d["hd"] * F32
    return weight_bytes(c) + len(mambas) * (state + conv) + len(attns) * kv
