"""Pallas decode-attention kernel vs the pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("shape", [
    (2, 8, 2, 16, 64, 40),    # B, H, Hkv, hd, S, len
    (1, 4, 4, 32, 128, 128),  # MHA, full cache
    (3, 16, 2, 64, 300, 200), # padding path
    (2, 8, 8, 128, 1024, 1),  # single valid token
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_oracle(shape, dtype):
    B, H, Hkv, hd, S, L = shape
    key = jax.random.key(B * 100 + S)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, hd), dtype)
    got = ops.decode_attention(q, k, v, jnp.asarray(L), block_s=128)
    want = ref.decode_attention(q, k, v, jnp.asarray(L))
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_per_sequence_lengths():
    B, H, Hkv, hd, S = 4, 8, 4, 32, 256
    key = jax.random.key(0)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, hd))
    lens = jnp.asarray([1, 64, 200, 256])
    got = ops.decode_attention(q, k, v, lens, block_s=128)
    for b in range(B):
        want = ref.decode_attention(q[b:b+1], k[b:b+1], v[b:b+1], lens[b])
        np.testing.assert_allclose(np.asarray(got[b:b+1]), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stacked_cache_layer_read_in_place(layer, dtype):
    """A layer of the paged decode's stacked (Lk, B, S, Hkv·hd) cache, read
    through the layer operand, equals the oracle on that layer's slice."""
    Lk, B, H, Hkv, hd, S = 3, 2, 8, 2, 64, 256
    key = jax.random.key(layer)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (Lk, B, S, Hkv * hd), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (Lk, B, S, Hkv * hd), dtype)
    lens = jnp.asarray([77, 256])
    got = ops.decode_attention(q, k, v, lens, jnp.asarray(layer), block_s=128)
    split = (B, S, Hkv, hd)
    want = ref.decode_attention(q, k[layer].reshape(split), v[layer].reshape(split), lens)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )
