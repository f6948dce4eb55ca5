"""Public jit'd wrappers around the Pallas kernels.

Handles padding to tile multiples and the dtype policy. The kernels are
compiled for the TPU. On the CPU backend (the test suite) they run in
Pallas interpret mode, which checks results but not the TPU's tiling or
VMEM limits (tests/test_tpu_compile.py compiles them for a described
TPU). Any other backend is refused rather than interpreted.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import cosine_sim as _cs
from repro.kernels import decode_attention as _da
from repro.kernels import segment_aggregate as _sa
from repro.kernels import ssm_decode as _ssm


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels target the TPU; refusing to interpret them on {backend!r}"
        )
    return backend == "cpu"


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("block_p", "block_d"))
def cosine_similarity(
    x: jnp.ndarray, c: jnp.ndarray, block_p: int = 128, block_d: int = 512
) -> jnp.ndarray:
    """x: (P, D), c: (K, D) -> (P, K) cosine sims. Pads to tile multiples.

    Leading batch axis: x (C, P, D) with c (C, K, D) -> (C, P, K); the
    kernel is vmapped over the cohort axis (Pallas turns the batch axis
    into an extra grid dimension, so it stays one dispatch).
    """
    if x.ndim == 3:
        return jax.vmap(
            lambda xi, ci: cosine_similarity(xi, ci, block_p, block_d)
        )(x, c)
    P, D = x.shape
    K = c.shape[0]
    bp = min(block_p, max(8, P))
    bd = min(block_d, max(128, D))
    xp = _pad_to(_pad_to(x, 0, bp), 1, bd)
    cp = _pad_to(c, 1, bd)
    # padded centroid rows have zero norm -> sims 0 after eps guard; padded
    # x rows likewise. K stays un-tiled (small); pad to lane multiple of 8.
    cp = _pad_to(cp, 0, 8)
    out = _cs.cosine_similarity(xp, cp, block_p=bp, block_d=bd, interpret=_interpret())
    return out[:P, :K]


@partial(jax.jit, static_argnames=("num_segments", "block_p", "block_d"))
def segment_aggregate(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    weights: Optional[jnp.ndarray] = None,
    block_p: int = 256,
    block_d: int = 512,
) -> jnp.ndarray:
    """data: (P, D); ids: (P,) -> (K, D) weighted segment sums.

    Leading batch axis: data (C, P, D) with ids (C, P) (and optional
    weights (C, P)) -> (C, K, D), one dispatch via vmap.
    """
    if data.ndim == 3:
        if weights is None:
            return jax.vmap(
                lambda d, i: segment_aggregate(
                    d, i, num_segments, None, block_p, block_d
                )
            )(data, segment_ids)
        return jax.vmap(
            lambda d, i, w: segment_aggregate(
                d, i, num_segments, w, block_p, block_d
            )
        )(data, segment_ids, weights)
    P, D = data.shape
    bp = min(block_p, max(8, P))
    bd = min(block_d, max(128, D))
    dp = _pad_to(_pad_to(data, 0, bp), 1, bd)
    Ppad = dp.shape[0]
    ids = jnp.full((Ppad, 1), -1, jnp.int32).at[:P, 0].set(segment_ids.astype(jnp.int32))
    w = jnp.zeros((Ppad, 1), jnp.float32)
    w = w.at[:P, 0].set(jnp.ones((P,)) if weights is None else weights.astype(jnp.float32))
    ks = max(8, num_segments)
    out = _sa.segment_aggregate(
        dp, ids, ks, w, block_p=bp, block_d=bd, interpret=_interpret()
    )
    return out[:num_segments, :D]


@partial(jax.jit, static_argnames=("block_s",))
def decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    length: jnp.ndarray,
    layer: Optional[jnp.ndarray] = None,
    block_s: int = 512,
) -> jnp.ndarray:
    """GQA decode attention over a long KV cache (flash-decode).

    q: (B, H, hd); length: scalar or (B,). k, v: one layer's (B, S, Hkv, hd)
    cache, padded here to a block multiple (padded slots are masked by
    `length`); or with `layer`, the paged decode's stacked
    (Lk, B, S, Hkv·hd) cache, read in place and never padded (its S is a
    pow2 number of pages, so a block divides it).
    """
    B = q.shape[0]
    lb = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    if layer is None:
        bs = min(block_s, max(128, k.shape[1]))
        k, v = _pad_to(k, 1, bs), _pad_to(v, 1, bs)
    else:
        bs = min(block_s, k.shape[2])
    return _da.decode_attention(q, k, v, lb, layer, block_s=bs,
                                interpret=_interpret())


def _ssm_step_inputs(x, dt, A_log, dt_bias):
    """Per-channel decay exp(dt·A) and input dt·x of Mamba2's one-token
    update, (B, H·P) each: dt = softplus(dt + dt_bias) and A = -exp(A_log)
    are per head, broadcast over the head's P channels."""
    B, H, P = x.shape
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)  # (B, H)
    da = jnp.exp(dt * -jnp.exp(A_log))
    return (jnp.repeat(da, P, axis=-1),
            jnp.repeat(dt, P, axis=-1) * x.reshape(B, H * P).astype(jnp.float32))


@jax.jit
def ssm_decode(x, dt, A_log, dt_bias, D, b, c, state, layer):
    """Mamba2's one-token selective state update of layer `layer` of the
    stacked state (Lm, B, N, H·P) float32, in place (the Pallas kernel).

    x: (B, H, P); dt: (B, H), before dt_bias and softplus; A_log, dt_bias,
    D: (H,); b, c: (B, N). Returns (y (B, H, P) float32, with the D·x skip,
    and the state).
    """
    B, H, P = x.shape
    da, dtx = _ssm_step_inputs(x, dt, A_log, dt_bias)
    y, state = _ssm.ssm_decode(da, dtx, b.astype(jnp.float32),
                               c.astype(jnp.float32), state, layer,
                               interpret=_interpret())
    return y.reshape(B, H, P) + D[:, None] * x.astype(jnp.float32), state
