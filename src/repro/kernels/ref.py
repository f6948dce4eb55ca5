"""Pure-jnp oracles for every Pallas kernel (the correctness contract)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def cosine_similarity(x: jnp.ndarray, c: jnp.ndarray, eps: float = 1e-8) -> jnp.ndarray:
    """x: (P, D), c: (K, D) -> (P, K) cosine similarities."""
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    dots = x @ c.T
    xn = jnp.linalg.norm(x, axis=1, keepdims=True)
    cn = jnp.linalg.norm(c, axis=1, keepdims=True)
    return dots / jnp.maximum(xn * cn.T, eps)


def segment_aggregate(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    weights: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """data: (P, D); segment_ids: (P,) int; -> (K, D) weighted segment sums."""
    d = data.astype(jnp.float32)
    if weights is not None:
        d = d * weights.astype(jnp.float32)[:, None]
    return jax.ops.segment_sum(d, segment_ids, num_segments=num_segments)


def decode_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, length: jnp.ndarray
) -> jnp.ndarray:
    """GQA decode attention oracle.

    q: (B, H, d); k, v: (B, S, Hkv, d); length: () or (B,) valid KV count.
    Returns (B, H, d).
    """
    B, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, hd).astype(jnp.float32)
    scores = jnp.einsum("bngk,bsnk->bngs", qg, k.astype(jnp.float32)) / jnp.sqrt(
        jnp.float32(hd)
    )
    t = jnp.arange(S)
    valid = t[None, :] < jnp.broadcast_to(jnp.asarray(length), (B,))[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngs,bsnk->bngk", probs, v.astype(jnp.float32))
    return out.reshape(B, H, hd).astype(q.dtype)


def ssm_decode(x, dt, A_log, dt_bias, D, b, c, state, layer):
    """Mamba2 one-token selective state update oracle.

    x: (B, H, P); dt: (B, H) before dt_bias and softplus; A_log, dt_bias,
    D: (H,); b, c: (B, N); state: stacked (L, B, N, H·P) float32, of which
    layer `layer` is updated (a copy, as the oracle may).
    Returns (y (B, H, P) float32, state).
    """
    B, H, P = x.shape
    N = b.shape[-1]
    xf = x.astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)  # (B, H)
    a = jnp.exp(dt * -jnp.exp(A_log))
    h = state[layer].reshape(B, N, H, P)
    h = a[:, None, :, None] * h + (
        b.astype(jnp.float32)[:, :, None, None] * (dt[:, :, None] * xf)[:, None]
    )
    y = jnp.einsum("bn,bnhp->bhp", c.astype(jnp.float32), h)
    y = y + D[None, :, None] * xf
    return y, state.at[layer].set(h.reshape(B, N, H * P))
