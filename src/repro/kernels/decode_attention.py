"""Blocked GQA decode-attention Pallas kernel (beyond-paper serving path).

One query token per sequence attends over a long KV cache: the KV sequence
is processed in VMEM blocks with a streaming (flash-style) softmax — running
max `m`, normalizer `l`, and accumulator `acc` live in VMEM scratch across
KV blocks. This is the compute hot-spot of decode_32k / long_500k serving.

Grid: (B, S/bs) with the KV axis innermost ("arbitrary" semantics).

The cache may be the stack of every layer's cache, (Lk, B, S, W), as the
paged decode step carries it: the layer rides in SMEM as a scalar-prefetch
operand and the k/v index maps pick that layer's blocks where they lie, so
the call copies nothing out of the stack. One layer's (B, S, Hkv, hd)
cache is the Lk = 1, layer = 0 case.

Layout: the cache's (Hkv, hd) head axes are flattened into one lane axis of
width W = Hkv·hd, so a k/v block is a plain (bs, W) tile — every block dim
is aligned to the TPU's (8, 128) tiling, and both matmuls are 2-D. GQA is
expressed through a block-diagonal query: row h of the (H, W) query holds
q[h] in the columns of its kv head h // group and zeros elsewhere, so
`q_bd @ k.T` gives every head's scores against its own kv head (the zero
columns add exact zeros). `p @ v` yields (H, W); column block h // group of
row h is head h's output, which the wrapper selects. The per-sequence
lengths are an SMEM operand blocked by sequence, so a vmap over the
caller's rows stays one grid and never loops over rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(layer_ref, len_ref, q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
            ns: int, hd: int):
    del layer_ref  # read by the k/v index maps only
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, -1e30)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0].astype(jnp.float32)  # (H, W) block-diagonal
    k = k_ref[...].astype(jnp.float32)  # (bs, W)
    v = v_ref[...].astype(jnp.float32)  # (bs, W)
    bs = k.shape[0]
    H = q.shape[0]

    # scores[h, t] = <q[h], k[t, h // group]> / sqrt(hd)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) / math.sqrt(hd)  # (H, bs)

    # validity: global kv index < cache length
    idx = s * bs + jax.lax.broadcasted_iota(jnp.int32, (H, bs), 1)
    scores = jnp.where(idx < len_ref[0, 0], scores, -1e30)

    # streaming softmax update
    m_prev = m_s[...]  # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)  # (H, bs)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc[...] = acc[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )  # (H, W)
    m_s[...] = m_new

    @pl.when(s == ns - 1)
    def _done():
        o_ref[0] = (acc[...] / jnp.maximum(l_s[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    length: jnp.ndarray,
    layer=None,
    *,
    block_s: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """q: (B, H, hd); length: (B,) valid KV count. k, v: one layer's
    (B, S, Hkv, hd) cache, or with `layer` the stacked (Lk, B, S, Hkv·hd)
    cache of which layer `layer` is read in place.

    Returns (B, H, hd). S % block_s == 0 (ops.py pads a layer's cache).
    """
    B, H, hd = q.shape
    if layer is None:
        S, Hkv = k.shape[1], k.shape[2]
        k, v = k.reshape(1, B, S, Hkv * hd), v.reshape(1, B, S, Hkv * hd)
        layer = 0
    S, W = k.shape[2], k.shape[3]
    Hkv = W // hd
    group = H // Hkv
    bs = min(block_s, S)
    assert S % bs == 0, (S, bs)
    ns = S // bs
    kv_of = jnp.arange(H) // group  # kv head of each query head
    q_bd = (
        q[:, :, None, :]
        * jax.nn.one_hot(kv_of, Hkv, dtype=q.dtype)[None, :, :, None]
    ).reshape(B, H, W)
    kv_spec = pl.BlockSpec(
        (pl.squeezed, pl.squeezed, bs, W), lambda b, s, l: (l[0], b, s, 0)
    )

    out = pl.pallas_call(
        functools.partial(_kernel, ns=ns, hd=hd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, ns),
            in_specs=[
                pl.BlockSpec((pl.squeezed, 1, 1), lambda b, s, l: (b, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, H, W), lambda b, s, l: (b, 0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=pl.BlockSpec((1, H, W), lambda b, s, l: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, W), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, W), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="decode_attention",
    )(
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
        length.astype(jnp.int32).reshape(B, 1, 1),
        q_bd, k, v,
    )
    # head h's output is column block h // group of its row
    return jnp.take_along_axis(
        out.reshape(B, H, Hkv, hd), kv_of[None, :, None, None], axis=2
    )[:, :, 0]
