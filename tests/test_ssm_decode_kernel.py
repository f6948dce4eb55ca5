"""Pallas Mamba2 state-update kernel (kernels/ssm_decode.py) vs the pure-jnp
oracle, reading and writing one layer of a stacked state in place."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("shape", [
    (3, 4, 64, 16),   # lanes, heads, head size, state size
    (2, 8, 64, 128),  # granite-4.0-h's head and state sizes
])
def test_ssm_decode_updates_one_layer_in_place(layer, shape):
    """Layer `layer` of the stacked (Lm, B, N, H·P) state equals the oracle's
    update, y equals the oracle's, and every other layer is untouched.
    Tolerance 1e-5 (relative, float32): the kernel and the oracle sum C·h
    over N in different orders."""
    B, H, P, N = shape
    Lm = 3
    key = jax.random.key(layer * 10 + N)
    k = lambda i: jax.random.fold_in(key, i)  # noqa: E731
    x = jax.random.normal(k(0), (B, H, P))
    dt = jax.random.normal(k(1), (B, H))
    A_log = jnp.log(jax.random.uniform(k(2), (H,), minval=1.0, maxval=16.0))
    dt_bias = jax.random.normal(k(3), (H,)) - 3.0
    D = jax.random.normal(k(4), (H,))
    b = jax.random.normal(k(5), (B, N))
    c = jax.random.normal(k(6), (B, N))
    state = jax.random.normal(k(7), (Lm, B, N, H * P))
    y, new = ops.ssm_decode(x, dt, A_log, dt_bias, D, b, c, state, jnp.asarray(layer))
    y_want, new_want = ref.ssm_decode(x, dt, A_log, dt_bias, D, b, c, state, layer)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_want), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new), np.asarray(new_want), rtol=1e-5, atol=1e-5)
    others = [l for l in range(Lm) if l != layer]
    np.testing.assert_array_equal(np.asarray(new)[others], np.asarray(state)[others])
    assert not np.allclose(np.asarray(new)[layer], np.asarray(state)[layer])
