"""Mamba2's one-token selective state update, in place (Pallas TPU kernel).

Each call updates ONE layer of the stacked recurrent state of a Mamba2
stack, held as (Lm, B, N, W) float32 with W = H·P (heads × head size):

    h[n, c] <- dA[c] · h[n, c] + B[n] · dtx[c]
    y[c]     = sum_n C[n] · h[n, c]

where, for channel c of head h(c), dA = exp(dt·A) and dtx = dt·x
(`kernels.ops.ssm_decode` forms both from dt, dt_bias, A_log and x, and adds
the D·x skip). The layer rides in SMEM as a scalar-prefetch operand and the
state's index map picks that layer's blocks where they lie; the state is
aliased to the second output, so the call writes it in place and copies
nothing out of the stack. mamba_ssm's `selective_state_update` is the
published counterpart; its state is (B, H, P, N). Here the N axis leads, so
a (N, W) block is tile-aligned (N = 128 rows of the (8, 128) tiling), dA,
dtx and y are rows, and B and C become (N, 1) columns in the kernel.

Grid: (B, W / block_w), one lane's block of channels per step on the TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _column(row):
    """A (1, N) row as an (N, 1) column (a tile-aligned transpose)."""
    return jnp.broadcast_to(row, (8, row.shape[-1])).T[:, :1]


def _kernel(layer_ref, da_ref, dtx_ref, b_ref, c_ref, h_ref, y_ref, ho_ref):
    del layer_ref  # read by the state's index map only
    for i in range(h_ref.shape[0]):  # the block's lanes
        h = da_ref[i] * h_ref[i] + _column(b_ref[i]) * dtx_ref[i]  # (N, bw)
        ho_ref[i] = h
        y_ref[i] = jnp.sum(_column(c_ref[i]) * h, axis=0, keepdims=True)


def ssm_decode(da, dtx, b, c, state, layer, *, block_w: int = 4096,
               interpret: bool = False):
    """da, dtx: (B, W) float32; b, c: (B, N) float32; state: the stacked
    (Lm, B, N, W) float32 state, of which layer `layer` is read and written
    in place. Returns (y (B, W) float32, state).

    On the TPU a grid step takes one lane's (N, block_w) block. Interpreted
    (off the TPU, where no VMEM limit holds) a step takes every lane: the
    interpreter writes each step's block into the whole state, so fewer
    steps run much faster.
    """
    _, B, N, W = state.shape
    bw = min(block_w, W)
    bb = B if interpret else 1
    assert W % bw == 0, (W, bw)
    row = pl.BlockSpec((bb, 1, bw), lambda i, j, l: (i, 0, j))
    vec = pl.BlockSpec((bb, 1, N), lambda i, j, l: (i, 0, 0))
    hspec = pl.BlockSpec((pl.squeezed, bb, N, bw), lambda i, j, l: (l[0], i, 0, j))
    y, state = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // bb, W // bw),
            in_specs=[row, row, vec, vec, hspec],
            out_specs=[row, hspec],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={5: 1},  # operand 5 (the state) is output 1
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
        name="ssm_decode",
    )(
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
        da.reshape(B, 1, W), dtx.reshape(B, 1, W),
        b.reshape(B, 1, N), c.reshape(B, 1, N), state,
    )
    return y.reshape(B, W), state
