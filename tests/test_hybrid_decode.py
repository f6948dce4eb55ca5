"""granite-4.0-h's Mamba2 / NoPE-attention pattern against its plain
reference (bench/reference/granite_hybrid.py: float32, highest precision,
the recurrence run position by position, none of the program imported), on
seeded random weights at a small size that keeps both kinds of layer
(mamba, attention, mamba). The program's weights are the benchmark's
(bench/drivers/decode_hybrid.py: the reference's draws with granite's four
multipliers folded in), here in float32.

Every tolerance below is on ||program - reference|| / ||reference|| of the
logits: 1e-4 for float32 throughout. The program reaches the same numbers
by another road (the training path sums the recurrence in chunks, the
multipliers are folded into weights once in float32, decode accumulates the
state one position at a time in another order), so agreement is to float32
rounding over a few layers, not to the bit; a missing term (the conv bias,
the D skip, the gate, the state carried over) moves the logits by 1e-2 or
more at this size.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))

from drivers import decode_hybrid as driver  # noqa: E402
from reference import granite_hybrid as hybrid  # noqa: E402

from repro.models import build_model  # noqa: E402
from repro.serve import CohortDecoder  # noqa: E402
from repro.serve.kv_cache import PagedKVCache  # noqa: E402

TOL = 1e-4
SEED = 2 ** 31 + 5  # a seed wider than 32 signed bits, as the benchmark's are

SMALL = {
    "name": "granite-4.0-h-small-test", "hidden_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "shared_intermediate_size": 256, "vocab_size": 512, "num_hidden_layers": 3,
    "layer_types": ["mamba", "attention", "mamba"],
    "mamba_expand": 2, "mamba_d_head": 64, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_conv_bias": True,
    "position_embedding_type": "nope", "tie_word_embeddings": True,
    "embedding_multiplier": 12.0, "attention_multiplier": 1 / 32,
    "residual_multiplier": 0.22, "logits_scaling": 8.0, "rms_norm_eps": 1e-5,
    "initializer_range": 0.02, "embedding_std": 0.02, "context_state_std": 0.25,
}


def rel_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module")
def program():
    cfg = driver.program_config(SMALL, jnp.float32)
    bank = driver.program_bank(SMALL, jax.random.key(SEED), jnp.float32)
    return build_model(cfg), bank


def _tokens(lanes, n, salt=0):
    return np.random.default_rng([SEED, salt]).integers(
        0, SMALL["vocab_size"], (lanes, n)).astype(np.int32)


def _teacher_forced(dec, tokens):
    """Logits (lanes, n, V) of decode fed `tokens`, one position per call."""
    dec.sync()  # a first sync restarts the lanes on seed tokens
    out = []
    for t in range(tokens.shape[1]):
        dec.tokens = tokens[None, :, t]
        out.append(dec.decode(1)[1][0])
    return np.stack(out, axis=1)


def test_training_forward_matches_reference(program):
    model, bank = program
    tokens = _tokens(3, 16)
    params = jax.tree.map(lambda a: a[0], bank)
    got, _ = model.forward(params, {"tokens": jnp.asarray(tokens)})
    want = hybrid.forward(SMALL, SEED, tokens, 0)
    assert rel_err(got, want) < TOL


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_decode_from_empty_state_matches_full_forward(program, backend):
    """Prefill equals decode: from a zeroed state, decoding a prompt token by
    token gives the reference's full forward over the prompt."""
    model, bank = program
    lanes, n = 4, 10
    tokens = _tokens(lanes, n, 1)
    dec = CohortDecoder(model, lambda: bank, lambda: [0], lanes=lanes,
                        page_size=16, backend=backend)
    got = _teacher_forced(dec, tokens)
    want = hybrid.forward(SMALL, SEED, tokens, 0)
    assert rel_err(got, want) < TOL
    assert dec.cache.state_resets == 1 and dec.step_compiles == 1


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_decode_from_seeded_state_matches_reference(program, backend):
    """From a seeded context (the attention layer's keys and values) and a
    request's seeded recurrent state, written as the benchmark writes them,
    teacher-forced decode gives the reference's logits."""
    model, bank = program
    lanes, start, n, request = 4, 20, 9, 3
    tokens = _tokens(lanes, n, 2)
    key = jax.random.key(SEED)
    dec = CohortDecoder(model, lambda: bank, lambda: [0], lanes=lanes,
                        page_size=16, backend=backend)
    dec.sync()
    dec.cache.k, dec.cache.v = driver.program_kv(SMALL, key, lanes, 32, start)
    dec.cache.ssm, dec.cache.conv = driver.write_state(
        SMALL, dec.cache.ssm, dec.cache.conv, key, request, start)
    dec.cache.index = np.full(1, start, np.int32)
    got = _teacher_forced(dec, tokens)
    want = hybrid.forward(SMALL, SEED, tokens, start, request)
    assert rel_err(got, want) < TOL
    # the recurrent state is carried: from a zeroed state the logits part
    zeroed = hybrid.forward(SMALL, SEED, tokens, start)
    assert rel_err(got, zeroed) > 100 * TOL
    assert dec.cache.relayouts == 1 and dec.step_compiles == 1


def test_sync_keeps_retained_state_and_zeroes_children():
    cache = PagedKVCache(n_layers=1, lanes=2, n_kv_heads=1, head_dim=8,
                         page_size=4, ssm_shape=(2, 2, 4, 8),
                         conv_shape=(2, 3, 2, 12))
    cache.sync([3])
    assert cache.state_resets == 1 and cache.state_nbytes == 4 * (2 * 2 * 4 * 8 + 2 * 3 * 2 * 12)
    key = jax.random.key(0)
    cache.ssm = jax.random.normal(key, cache.ssm.shape)
    cache.conv = jax.random.normal(jax.random.fold_in(key, 1), cache.conv.shape)
    ssm3, conv3 = np.asarray(cache.ssm[0]), np.asarray(cache.conv[0])
    cache.sync([3, 7])  # a partition: 3 retained, 7 a new child
    assert cache.state_resets == 2 and cache.rows == 2
    np.testing.assert_array_equal(cache.ssm[0], ssm3)
    np.testing.assert_array_equal(cache.conv[0], conv3)
    assert not np.any(cache.ssm[1]) and not np.any(cache.conv[1])
    cache.sync([7])  # 3 retired, 7 keeps its (zero) state
    assert cache.state_resets == 2 and cache.slots == [7]
    assert not np.any(cache.ssm[0])
    cache.ensure(40)  # pages grow, the state does not
    assert cache.seq >= 40 and cache.ssm.shape == (1, 2, 2, 4, 8)
