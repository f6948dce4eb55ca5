"""flops.py against counts made by hand."""
import json

from conftest import BENCH

import run

flops = run.load_module(BENCH / "flops.py")
GRANITE = json.loads((BENCH / "configs" / "granite-3-2b.json").read_text())


def test_granite_decode_step():
    # per layer: q, k, v 2048 x (32 + 8 + 8) x 64; o 2048 x 2048; 3 x 2048 x 8192
    per_layer = 2048 * 48 * 64 + 2048 * 2048 + 3 * 2048 * 8192
    assert per_layer == 60_817_408
    matmul = 40 * per_layer + 2048 * 49155  # tied head once
    assert flops.lm_matmul_params(GRANITE) == matmul == 2_533_365_760
    # 4 lanes: 2 operations per weight per token, and attention over 2048
    # positions (q.k and p.v: 4 x heads x head size per position per layer)
    attn = 4 * 4 * 32 * 64 * 2048 * 40
    assert flops.decode_step_flops(GRANITE, 4, 2048) == 2 * matmul * 4 + attn
    # every weight once in bf16 (norm scales too: 40 x 2 x 2048 + 2048),
    # then f32 keys and values of 2048 positions x 8 heads x 64 per lane
    weights = 2 * (matmul + 40 * 2 * 2048 + 2048)
    assert weights == 5_067_063_296
    kv = 40 * (4 * 2048 * 2 * 8 * 64 * 4 + 2 * 4 * 32 * 64 * 2)
    assert flops.decode_step_bytes(GRANITE, 4, 2048, 2, 4) == weights + kv


def test_one_decode_attention_call():
    # 4 lanes over 1920 positions: f32 k and v of 8 kv heads x 64, plus the
    # bf16 queries in and outputs back (4 x 32 x 64 each)
    f, b = flops.attention_cost(GRANITE, 4, 1920, 4)
    assert f == 4 * 4 * 32 * 64 * 1920 == 62_914_560
    assert b == 4 * 1920 * 2 * 8 * 64 * 4 + 2 * 4 * 32 * 64 * 2 == 31_490_048
