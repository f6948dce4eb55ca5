"""Segmented (cohort-wise) weighted aggregation Pallas kernel.

out[k, :] = sum_{i : seg[i] == k} w_i * data[i, :]

This is Auxo's aggregation primitive: cluster-centroid refresh and
per-cohort gradient aggregation are both segment-sums keyed by cluster /
cohort assignment. The scatter is recast as a one-hot matmul so it runs on
the MXU: out_tile += onehot(seg_tile).T @ data_tile.

Grid: (D/bd, P/bp) with P innermost, accumulating into the (K, bd) output
tile held in VMEM scratch across P tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(data_ref, seg_ref, w_ref, o_ref, acc, *, np_: int, k: int):
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = data_ref[...].astype(jnp.float32)  # (bp, bd)
    seg = seg_ref[...]  # (bp, 1) int32
    w = w_ref[...].astype(jnp.float32)  # (bp, 1)
    kids = jax.lax.broadcasted_iota(jnp.int32, (seg.shape[0], k), 1)
    onehot = jnp.where(seg == kids, w, 0.0)  # (bp, K) weighted one-hot
    acc[...] += jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(p == np_ - 1)
    def _done():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def segment_aggregate(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    weights: jnp.ndarray,
    *,
    block_p: int = 256,
    block_d: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """data: (P, D); segment_ids: (P, 1) int32; weights: (P, 1) -> (K, D)."""
    P, D = data.shape
    bp = min(block_p, P)
    bd = min(block_d, D)
    assert P % bp == 0 and D % bd == 0, (data.shape, bp, bd)
    np_ = P // bp

    return pl.pallas_call(
        functools.partial(_kernel, np_=np_, k=num_segments),
        grid=(D // bd, np_),
        in_specs=[
            pl.BlockSpec((bp, bd), lambda d, p: (p, d)),
            pl.BlockSpec((bp, 1), lambda d, p: (p, 0)),
            pl.BlockSpec((bp, 1), lambda d, p: (p, 0)),
        ],
        out_specs=pl.BlockSpec((num_segments, bd), lambda d, p: (0, d)),
        out_shape=jax.ShapeDtypeStruct((num_segments, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((num_segments, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(data, segment_ids, weights)
