"""hybrid_flops.py against counts made by hand, at a small shape (two
Mamba2 layers and one attention layer), and the hybrid cell's three readers
on a window and trace made by hand."""
from types import SimpleNamespace

import pytest

import hybrid_flops
import run
from conftest import BENCH

SMALL = {
    "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "shared_intermediate_size": 512, "vocab_size": 1000, "num_hidden_layers": 3,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "mamba_expand": 2, "mamba_d_head": 64, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4,
}


def test_small_hybrid_counts():
    # Mamba2 layer: d_inner 512, 8 heads of 64, state 16, conv channels
    # 512 + 32 = 544; in_proj 256 x (2 x 512 + 2 x 16 + 8) = 256 x 1064,
    # 4 conv taps x 544, out_proj 512 x 256; MLP 3 x 256 x 512
    mamba = 256 * 1064 + 4 * 544 + 512 * 256 + 3 * 256 * 512
    # attention layer: q, k, v 256 x (4 + 2 + 2) x 64; o 256 x 256; MLP
    attn = 256 * 8 * 64 + 256 * 256 + 3 * 256 * 512
    matmul = 2 * mamba + attn + 256 * 1000  # tied head once
    assert hybrid_flops.matmul_params(SMALL) == matmul == 2_443_520
    # bf16: matrices, conv bias (544 a layer), norms (mamba: 2 x 256 + 512,
    # attention 2 x 256, final 256); f32: A_log, dt_bias, D (3 x 8 a layer)
    norms = 2 * (2 * 256 + 512) + 2 * 256 + 256
    assert hybrid_flops.weight_bytes(SMALL) == 2 * (matmul + 2 * 544 + norms) + 4 * 2 * 24
    # one ssm_decode call, 4 lanes: 5 operations an element of the state
    # (4 x 8 x 64 x 16), the state read and written, dA, dtx and y (4 x 512
    # each), B and C (4 x 16 each), all f32
    state = 4 * 8 * 64 * 16
    f, b = hybrid_flops.ssm_decode_cost(SMALL, 4)
    assert f == 5 * state == 163_840
    assert b == 4 * (2 * state + 3 * 4 * 512 + 2 * 4 * 16) == 287_232
    # a fleet step of 4 lanes at length 100: 2 operations a weight a token,
    # the state updates of 2 layers, attention over 100 positions in 1
    step = 2 * matmul * 4 + 2 * 5 * state + 4 * 4 * 4 * 64 * 100
    assert hybrid_flops.decode_step_flops(SMALL, 4, 100) == step
    moved = (hybrid_flops.weight_bytes(SMALL)
             + 2 * (2 * 4 * state + 4 * 4 * 544 * 4)  # state, conv window
             + 4 * 100 * 2 * 2 * 64 * 4)              # f32 k and v
    assert hybrid_flops.decode_step_bytes(SMALL, 4, 100) == moved


def _reader(name):
    return run.load_module(BENCH / "metrics" / f"{name}.py")


def test_readers_on_a_window_made_by_hand():
    op = SimpleNamespace(dur=2_000_000)  # 2 ms a call
    trace = SimpleNamespace(
        kernel_ops=lambda names: [op] * 6 if names == ("ssm_decode",) else [],
        module_seconds=lambda word: 0.5 if word == "jit_step" else 0.0)
    window = {"steps": 3, "positions": [10, 11, 12], "lanes": 4, "seconds": 0.25}
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = SimpleNamespace(window=window, trace=trace, peak=peak, config=SMALL)
    flops = sum(hybrid_flops.decode_step_flops(SMALL, 4, p + 1) for p in (10, 11, 12))
    assert _reader("hybrid_decode_mfu").read(ctx) == pytest.approx(100 * flops / 0.25 / 1e12)
    moved = sum(hybrid_flops.decode_step_bytes(SMALL, 4, p + 1) for p in (10, 11, 12))
    assert _reader("hybrid_decode_hbm_share").read(ctx) == pytest.approx(100 * moved / 0.5 / 1e9)
    nbytes = hybrid_flops.ssm_decode_cost(SMALL, 4)[1]
    assert _reader("ssm_decode_roofline").read(ctx) == pytest.approx(
        100 * (nbytes / 1e9) / 2e-3)
    # a trace without the kernel or the step program reads nothing
    empty = SimpleNamespace(kernel_ops=lambda names: [], module_seconds=lambda word: 0.0)
    ctx.trace = empty
    assert _reader("ssm_decode_roofline").read(ctx) is None
    assert _reader("hybrid_decode_hbm_share").read(ctx) is None
