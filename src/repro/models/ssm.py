"""State-space / recurrent blocks: Mamba2 (chunked SSD) and xLSTM.

The chunked SSD algorithm re-expresses the selective-scan as block matmuls
(intra-chunk "attention-like" term + inter-chunk state passing), which maps
onto the TPU MXU — the hardware adaptation of the CUDA selective-scan kernel.
mLSTM (xLSTM's matrix-memory cell) is expressed through the *same* chunked
machinery: h_t = f_t h_{t-1} + i_t v_t k_t^T is an SSD recurrence with decay
log f and per-step input gain i. sLSTM is inherently sequential (recurrent
weight mixing) and uses lax.scan over time; its decode step is O(1).

Covers zamba2-7b (Mamba2 + shared attention) and xlstm-1.3b (mLSTM+sLSTM).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (
    ModelConfig,
    dense_init,
    mlp,
    mlp_init,
    rmsnorm,
    rmsnorm_init,
)


# ---------------------------------------------------------------------------
# Chunked SSD core (shared by Mamba2 and mLSTM)
# ---------------------------------------------------------------------------
def _segsum(a):
    """Stable segment-sum: out[..., i, j] = sum_{j < l <= i} a[..., l].

    a: (..., Q). Returns (..., Q, Q), -inf above the diagonal.
    """
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x, a, B, C, chunk: int):
    """Chunked selective state-space duality scan.

    Recurrence (per head): h_t = exp(a_t) h_{t-1} + B_t x_t^T,
                           y_t = C_t^T h_t.
    x: (b, l, h, p)   per-step inputs (already scaled by dt / input gate)
    a: (b, l, h)      per-step log-decay (<= 0 for stability)
    B: (b, l, h, n)   input maps
    C: (b, l, h, n)   output maps
    Returns y: (b, l, h, p), final_state: (b, h, n, p).
    """
    b, l, h, p = x.shape
    n = B.shape[-1]
    Q = min(chunk, l)
    assert l % Q == 0, (l, Q)
    nc = l // Q

    xr = x.reshape(b, nc, Q, h, p).transpose(0, 1, 3, 2, 4)  # (b,c,h,Q,p)
    ar = a.reshape(b, nc, Q, h).transpose(0, 1, 3, 2)  # (b,c,h,Q)
    Br = B.reshape(b, nc, Q, h, n).transpose(0, 1, 3, 2, 4)  # (b,c,h,Q,n)
    Cr = C.reshape(b, nc, Q, h, n).transpose(0, 1, 3, 2, 4)

    ar = ar.astype(jnp.float32)
    a_cum = jnp.cumsum(ar, axis=-1)  # (b,c,h,Q)
    a_total = a_cum[..., -1]  # (b,c,h)

    # 1. intra-chunk (diagonal blocks): attention-like matmul on the MXU.
    L = jnp.exp(_segsum(ar))  # (b,c,h,Q,Q)
    scores = jnp.einsum("bchqn,bchkn->bchqk", Cr, Br).astype(jnp.float32)
    y_diag = jnp.einsum("bchqk,bchkp->bchqp", (scores * L).astype(x.dtype), xr)

    # 2. chunk-final states: decay-to-end weighted input outer products.
    decay_end = jnp.exp(a_total[..., None] - a_cum)  # (b,c,h,Q)
    states = jnp.einsum(
        "bchqn,bchq,bchqp->bchnp", Br, decay_end.astype(x.dtype), xr
    )  # (b,c,h,n,p)

    # 3. inter-chunk recurrence over chunk states (tiny sequential scan).
    def step(carry, inp):
        st, atot = inp
        new = carry * jnp.exp(atot)[..., None, None].astype(carry.dtype) + st
        return new, carry  # emit state *entering* the chunk

    init = jnp.zeros((b, h, n, p), x.dtype)
    final, prev_states = jax.lax.scan(
        step, init, (states.transpose(1, 0, 2, 3, 4), a_total.transpose(1, 0, 2))
    )
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # (b,c,h,n,p)

    # 4. inter-chunk contribution: y += (C ⊙ decay_in) @ prev_state.
    decay_in = jnp.exp(a_cum)  # (b,c,h,Q)
    y_off = jnp.einsum(
        "bchqn,bchq,bchnp->bchqp", Cr, decay_in.astype(x.dtype), prev_states
    )

    y = (y_diag + y_off).transpose(0, 1, 3, 2, 4).reshape(b, l, h, p)
    return y, final


def ssd_step(state, x, a, B, C):
    """Single-token recurrent step (decode path).

    state: (b,h,n,p); x: (b,h,p); a: (b,h); B,C: (b,h,n).
    """
    state = state * jnp.exp(a.astype(jnp.float32))[..., None, None].astype(state.dtype)
    state = state + jnp.einsum("bhn,bhp->bhnp", B, x)
    y = jnp.einsum("bhn,bhnp->bhp", C, state)
    return y, state


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------
def mamba2_dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    d_inner = cfg.ssm_expand * cfg.d_model
    H = cfg.ssm_heads or max(1, d_inner // 64)
    P = d_inner // H
    N = cfg.ssm_state
    return d_inner, H, P, N


def gated_eps(cfg: ModelConfig) -> float:
    """The gated RMSNorm's eps (its input is not the residual stream)."""
    return cfg.ssm_norm_eps or cfg.norm_eps


def mamba2_init(key, cfg: ModelConfig):
    d_inner, H, P, N = mamba2_dims(cfg)
    conv_ch = d_inner + 2 * N
    k_in, k_conv, k_dt, k_out = jax.random.split(key, 4)
    p = {
        "norm": rmsnorm_init(cfg.d_model, cfg.dtype),
        # order: [z (gate), x, B, C, dt]
        "w_in": dense_init(k_in, (cfg.d_model, 2 * d_inner + 2 * N + H), cfg.dtype),
        "conv_w": dense_init(k_conv, (cfg.ssm_conv, conv_ch), cfg.dtype, scale=0.5),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H).astype(jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "gated_norm": rmsnorm_init(d_inner, cfg.dtype),
        "w_out": dense_init(k_out, (d_inner, cfg.d_model), cfg.dtype),
    }
    if cfg.ssm_conv_bias:
        p["conv_b"] = jnp.zeros((conv_ch,), cfg.dtype)
    return p


def _causal_conv(seq, w, carry=None, bias=None):
    """Depthwise causal conv. seq: (b,l,ch); w: (kw,ch); carry: (b,kw-1,ch);
    bias: (ch,) or None."""
    kw = w.shape[0]
    if carry is None:
        carry = jnp.zeros((seq.shape[0], kw - 1, seq.shape[2]), seq.dtype)
    padded = jnp.concatenate([carry, seq], axis=1)
    out = sum(padded[:, i : i + seq.shape[1]] * w[i] for i in range(kw))
    if bias is not None:
        out = out + bias
    new_carry = padded[:, -(kw - 1) :] if kw > 1 else carry
    return jax.nn.silu(out), new_carry


def mamba2_apply(params, cfg: ModelConfig, x):
    """x: (B, L, D) -> (B, L, D). Training path (chunked SSD)."""
    d_inner, H, P, N = mamba2_dims(cfg)
    h = rmsnorm(params["norm"], x, cfg.norm_eps)
    zxbcdt = jnp.einsum("bld,de->ble", h, params["w_in"])
    z, xin, Bc, Cc, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner, 2 * d_inner + N, 2 * d_inner + 2 * N], axis=-1
    )
    conv_in = jnp.concatenate([xin, Bc, Cc], axis=-1)
    conv_out, _ = _causal_conv(conv_in, params["conv_w"], bias=params.get("conv_b"))
    xin, Bc, Cc = jnp.split(conv_out, [d_inner, d_inner + N], axis=-1)

    b, l, _ = x.shape
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (b,l,H)
    A = -jnp.exp(params["A_log"])  # (H,) negative
    a = dt * A  # (b,l,H) log decay

    xh = xin.reshape(b, l, H, P)
    Bh = jnp.broadcast_to(Bc[:, :, None, :], (b, l, H, N))
    Ch = jnp.broadcast_to(Cc[:, :, None, :], (b, l, H, N))
    y, _ = ssd_chunked(xh * dt[..., None].astype(x.dtype), a, Bh, Ch, cfg.ssm_chunk)
    y = y + xh * params["D"][None, None, :, None].astype(x.dtype)
    y = y.reshape(b, l, d_inner)
    y = rmsnorm(params["gated_norm"], y * jax.nn.silu(z), gated_eps(cfg))
    return x + jnp.einsum("ble,ed->bld", y, params["w_out"])


def mamba2_cache_init(cfg: ModelConfig, batch: int, dtype=None):
    d_inner, H, P, N = mamba2_dims(cfg)
    dtype = dtype or cfg.dtype
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, d_inner + 2 * N), dtype),
        "ssm": jnp.zeros((batch, H, N, P), dtype),
    }


def mamba2_decode(params, cfg: ModelConfig, x, cache):
    """x: (B, 1, D); O(1) recurrent update."""
    d_inner, H, P, N = mamba2_dims(cfg)
    h = rmsnorm(params["norm"], x, cfg.norm_eps)
    zxbcdt = jnp.einsum("bld,de->ble", h, params["w_in"])
    z, xin, Bc, Cc, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner, 2 * d_inner + N, 2 * d_inner + 2 * N], axis=-1
    )
    conv_in = jnp.concatenate([xin, Bc, Cc], axis=-1)
    conv_out, new_conv = _causal_conv(
        conv_in, params["conv_w"], cache["conv"], params.get("conv_b")
    )
    xin, Bc, Cc = jnp.split(conv_out[:, 0], [d_inner, d_inner + N], axis=-1)

    b = x.shape[0]
    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])  # (b,H)
    A = -jnp.exp(params["A_log"])
    a = dt * A
    xh = xin.reshape(b, H, P) * dt[..., None].astype(x.dtype)
    Bh = jnp.broadcast_to(Bc[:, None, :], (b, H, N)).astype(x.dtype)
    Ch = jnp.broadcast_to(Cc[:, None, :], (b, H, N)).astype(x.dtype)
    y, new_ssm = ssd_step(cache["ssm"], xh, a, Bh, Ch)
    y = y + xin.reshape(b, H, P) * params["D"][None, :, None].astype(x.dtype)
    y = y.reshape(b, 1, d_inner)
    y = rmsnorm(params["gated_norm"], y * jax.nn.silu(z), gated_eps(cfg))
    out = x + jnp.einsum("ble,ed->bld", y, params["w_out"])
    return out, {"conv": new_conv, "ssm": new_ssm}


# Mamba2 mixer + MLP (granite-4.0-h's layer of the mamba kind)
def mamba2_mlp_init(key, cfg: ModelConfig):
    k_m, k_f = jax.random.split(key)
    return {
        "mixer": mamba2_init(k_m, cfg),
        "mlp_norm": rmsnorm_init(cfg.d_model, cfg.dtype),
        "mlp": mlp_init(k_f, cfg),
    }


def _mlp_after(params, cfg: ModelConfig, x):
    return x + mlp(params["mlp"], rmsnorm(params["mlp_norm"], x, cfg.norm_eps))


def mamba2_mlp_apply(params, cfg: ModelConfig, x):
    return _mlp_after(params, cfg, mamba2_apply(params["mixer"], cfg, x))


def mamba2_mlp_decode(params, cfg: ModelConfig, x, cache):
    x, cache = mamba2_decode(params["mixer"], cfg, x, cache)
    return _mlp_after(params, cfg, x), cache


# ---------------------------------------------------------------------------
# mLSTM block (xLSTM matrix memory) — via the SSD machinery
# ---------------------------------------------------------------------------
def mlstm_init(key, cfg: ModelConfig):
    nh = cfg.n_heads
    hd = cfg.d_model // nh
    k_q, k_k, k_v, k_g, k_o, k_u, k_d2 = jax.random.split(key, 7)
    d_up = cfg.ssm_expand * cfg.d_model
    hd_up = d_up // nh
    return {
        "norm": rmsnorm_init(cfg.d_model, cfg.dtype),
        "w_up": dense_init(k_u, (cfg.d_model, 2 * d_up), cfg.dtype),
        # per-head block-diagonal projections (xLSTM paper: q/k/v mix only
        # within a head) — 1/nh the parameters of dense projections
        "wq": dense_init(k_q, (nh, hd_up, hd_up), cfg.dtype),
        "wk": dense_init(k_k, (nh, hd_up, hd_up), cfg.dtype),
        "wv": dense_init(k_v, (nh, hd_up, hd_up), cfg.dtype),
        "w_gates": dense_init(k_g, (d_up, nh, 2), jnp.float32),  # (i, f) pre-acts
        "out_norm": rmsnorm_init(d_up, cfg.dtype),
        "w_down": dense_init(k_d2, (d_up, cfg.d_model), cfg.dtype),
    }


def _mlstm_qkvg(params, cfg: ModelConfig, h):
    nh = cfg.n_heads
    up = jnp.einsum("bld,de->ble", h, params["w_up"])
    u, gate = jnp.split(up, 2, axis=-1)
    b, l = u.shape[:2]
    uh = u.reshape(b, l, nh, -1)  # (b, l, nh, hd_up)
    q = jnp.einsum("blhe,hek->blhk", uh, params["wq"])
    k = jnp.einsum("blhe,hek->blhk", uh, params["wk"]) / math.sqrt(q.shape[-1])
    v = jnp.einsum("blhe,hek->blhk", uh, params["wv"])
    pre = jnp.einsum("ble,ehg->blhg", u.astype(jnp.float32), params["w_gates"])
    # stabilized gates: sigmoid input gate (soft-capped variant of the paper's
    # exponential gate; see module docstring), log-sigmoid forget decay.
    ig = jax.nn.sigmoid(pre[..., 0])  # (b,l,nh)
    a = jax.nn.log_sigmoid(pre[..., 1])  # (b,l,nh) log decay <= 0
    return q, k, v, ig, a, gate


def mlstm_apply(params, cfg: ModelConfig, x):
    h = rmsnorm(params["norm"], x, cfg.norm_eps)
    q, k, v, ig, a, gate = _mlstm_qkvg(params, cfg, h)
    xin = v * ig[..., None].astype(v.dtype)
    num, _ = ssd_chunked(xin, a, k, q, cfg.ssm_chunk)  # (b,l,h,p)
    ones = jnp.ones_like(xin[..., :1])
    den, _ = ssd_chunked(ones * ig[..., None].astype(v.dtype), a, k, q, cfg.ssm_chunk)
    y = num / jnp.maximum(jnp.abs(den), 1.0)
    b, l = x.shape[:2]
    y = y.reshape(b, l, -1)
    y = rmsnorm(params["out_norm"], y, cfg.norm_eps) * jax.nn.silu(gate)
    return x + jnp.einsum("ble,ed->bld", y, params["w_down"])


def mlstm_cache_init(cfg: ModelConfig, batch: int, dtype=None):
    nh = cfg.n_heads
    hd = (cfg.d_model // nh) * cfg.ssm_expand
    dtype = dtype or cfg.dtype
    return {
        "C": jnp.zeros((batch, nh, hd, hd), dtype),  # (b,h,n=k,p=v)
        "n": jnp.zeros((batch, nh, hd, 1), dtype),
    }


def mlstm_decode(params, cfg: ModelConfig, x, cache):
    h = rmsnorm(params["norm"], x, cfg.norm_eps)
    q, k, v, ig, a, gate = _mlstm_qkvg(params, cfg, h)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]
    ig, a = ig[:, 0], a[:, 0]
    xin = v * ig[..., None].astype(v.dtype)
    num, newC = ssd_step(cache["C"], xin, a, k, q)
    den, newn = ssd_step(cache["n"], (ig[..., None] * jnp.ones_like(xin[..., :1])).astype(v.dtype), a, k, q)
    y = num / jnp.maximum(jnp.abs(den), 1.0)
    y = y.reshape(x.shape[0], 1, -1)
    y = rmsnorm(params["out_norm"], y, cfg.norm_eps) * jax.nn.silu(gate)
    return x + jnp.einsum("ble,ed->bld", y, params["w_down"]), {"C": newC, "n": newn}


# ---------------------------------------------------------------------------
# sLSTM block (scalar memory, recurrent mixing -> lax.scan over time)
# ---------------------------------------------------------------------------
def slstm_init(key, cfg: ModelConfig):
    nh = cfg.n_heads
    hd = cfg.d_model // nh
    k_w, k_r, k_f, k_o = jax.random.split(key, 4)
    d_ff = int(cfg.d_model * 4 / 3 / 2) * 2  # GLU ffn at 4/3 projection factor
    return {
        "norm": rmsnorm_init(cfg.d_model, cfg.dtype),
        # input projections for (i, f, z, o)
        "w": dense_init(k_w, (cfg.d_model, nh, 4, hd), cfg.dtype),
        # head-wise recurrent mixing for (i, f, z, o)
        "r": dense_init(k_r, (nh, 4, hd, hd), cfg.dtype, scale=0.4),
        "out_norm": rmsnorm_init(cfg.d_model, cfg.dtype),
        "ffn_norm": rmsnorm_init(cfg.d_model, cfg.dtype),
        "ffn_up": dense_init(k_f, (cfg.d_model, 2 * d_ff), cfg.dtype),
        "ffn_down": dense_init(k_o, (d_ff, cfg.d_model), cfg.dtype),
    }


def slstm_cell(params_r, wx, state):
    """One sLSTM time step. wx: (b,nh,4,hd) input pre-acts; state dict."""
    c, n, m, hprev = state["c"], state["n"], state["m"], state["h"]
    rx = jnp.einsum("bhk,hgkj->bhgj", hprev, params_r)  # (b,nh,4,hd)
    pre = wx.astype(jnp.float32) + rx.astype(jnp.float32)
    i_pre, f_pre, z_pre, o_pre = pre[:, :, 0], pre[:, :, 1], pre[:, :, 2], pre[:, :, 3]
    # stabilizer state m (log-space max trick from the xLSTM paper)
    log_f = jax.nn.log_sigmoid(f_pre)
    m_new = jnp.maximum(log_f + m, i_pre)
    i = jnp.exp(i_pre - m_new)
    f = jnp.exp(log_f + m - m_new)
    z = jnp.tanh(z_pre)
    o = jax.nn.sigmoid(o_pre)
    c_new = f * c + i * z
    n_new = f * n + i
    h_new = o * c_new / jnp.maximum(jnp.abs(n_new), 1.0)
    return {"c": c_new, "n": n_new, "m": m_new, "h": h_new}


def slstm_state_init(cfg: ModelConfig, batch: int):
    nh = cfg.n_heads
    hd = cfg.d_model // nh
    z = jnp.zeros((batch, nh, hd), jnp.float32)
    return {"c": z, "n": z, "m": z - 30.0, "h": z}


def slstm_apply(params, cfg: ModelConfig, x):
    b, l, d = x.shape
    nh = cfg.n_heads
    h = rmsnorm(params["norm"], x, cfg.norm_eps)
    wx = jnp.einsum("bld,dhgk->blhgk", h, params["w"])  # (b,l,nh,4,hd)

    def step(state, wx_t):
        new = slstm_cell(params["r"], wx_t, state)
        return new, new["h"]

    state0 = slstm_state_init(cfg, b)
    _, hs = jax.lax.scan(step, state0, wx.transpose(1, 0, 2, 3, 4))
    y = hs.transpose(1, 0, 2, 3).reshape(b, l, d).astype(x.dtype)
    x = x + rmsnorm(params["out_norm"], y, cfg.norm_eps)
    # GLU feed-forward
    f = jnp.einsum("bld,df->blf", rmsnorm(params["ffn_norm"], x, cfg.norm_eps), params["ffn_up"])
    f1, f2 = jnp.split(f, 2, axis=-1)
    return x + jnp.einsum("blf,fd->bld", jax.nn.silu(f1) * f2, params["ffn_down"])


def slstm_decode(params, cfg: ModelConfig, x, cache):
    b = x.shape[0]
    h = rmsnorm(params["norm"], x, cfg.norm_eps)
    wx = jnp.einsum("bld,dhgk->blhgk", h, params["w"])[:, 0]
    new = slstm_cell(params["r"], wx, cache)
    y = new["h"].reshape(b, 1, -1).astype(x.dtype)
    x = x + rmsnorm(params["out_norm"], y, cfg.norm_eps)
    f = jnp.einsum("bld,df->blf", rmsnorm(params["ffn_norm"], x, cfg.norm_eps), params["ffn_up"])
    f1, f2 = jnp.split(f, 2, axis=-1)
    return x + jnp.einsum("blf,fd->bld", jax.nn.silu(f1) * f2, params["ffn_down"]), new
