"""Tiled pairwise cosine-similarity Pallas kernel.

sims[p, k] = <x_p, c_k> / (||x_p|| * ||c_k||)

Grid: (P/bp, D/bd) with the D axis innermost ("arbitrary" semantics) so dot
products and squared norms accumulate in VMEM scratch across D tiles; the
final D tile fuses the rsqrt normalization. The MXU runs the (bp, bd) @
(bd, K) inner-product tile; K (number of clusters) is small and padded to a
lane multiple of 128 by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, c_ref, o_ref, acc, x2, c2, *, nd: int, eps: float):
    d = pl.program_id(1)

    @pl.when(d == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        x2[...] = jnp.zeros_like(x2)
        c2[...] = jnp.zeros_like(c2)

    x = x_ref[...].astype(jnp.float32)  # (bp, bd)
    c = c_ref[...].astype(jnp.float32)  # (K, bd)
    acc[...] += jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    x2[...] += jnp.sum(x * x, axis=1, keepdims=True)  # (bp, 1)
    c2[...] += jnp.sum(c * c, axis=1)[None, :]  # (1, K)

    @pl.when(d == nd - 1)
    def _done():
        denom = jnp.sqrt(x2[...] * c2[...])  # (bp, K) via broadcast
        o_ref[...] = (acc[...] / jnp.maximum(denom, eps)).astype(o_ref.dtype)


def cosine_similarity(
    x: jnp.ndarray,
    c: jnp.ndarray,
    *,
    block_p: int = 128,
    block_d: int = 512,
    eps: float = 1e-8,
    interpret: bool = False,
) -> jnp.ndarray:
    """x: (P, D), c: (K, D), P % block_p == 0, D % block_d == 0 -> (P, K)."""
    P, D = x.shape
    K = c.shape[0]
    bp = min(block_p, P)
    bd = min(block_d, D)
    assert P % bp == 0 and D % bd == 0, (x.shape, bp, bd)
    nd = D // bd

    return pl.pallas_call(
        functools.partial(_kernel, nd=nd, eps=eps),
        grid=(P // bp, nd),
        in_specs=[
            pl.BlockSpec((bp, bd), lambda p, d: (p, d)),
            pl.BlockSpec((K, bd), lambda p, d: (0, d)),
        ],
        out_specs=pl.BlockSpec((bp, K), lambda p, d: (p, 0)),
        out_shape=jax.ShapeDtypeStruct((P, K), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bp, K), jnp.float32),
            pltpu.VMEM((bp, 1), jnp.float32),
            pltpu.VMEM((1, K), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, c)
