"""Plain reference for granite-4.0-h (GraniteMoeHybridForCausalLM with no
experts), and its weights.

The weights, the keys and values of the attention layers' seeded context,
and each request's seeded recurrent state are drawn here from the seed,
layer by layer, so that the reference can draw them again after the
program's copy is freed. `forward` is a teacher-forced pass in float32 at
the highest matmul precision, written from the published block:

- a layer of the mamba kind: RMSNorm, in_proj to [z, x, B, C, dt], a
  depthwise causal convolution of width d_conv over [x, B, C] with bias and
  SiLU, dt = softplus(dt + dt_bias), the selective recurrence
  h <- exp(dt·A) h + dt·(x ⊗ B), y = C·h + D·x per head (A = -exp(A_log),
  one group of B and C), a gated RMSNorm of y·silu(z) over all channels,
  out_proj, times the residual multiplier; the recurrence runs position
  by position;
- a layer of the attention kind: RMSNorm, GQA attention with no positional
  encoding and the attention multiplier as its scale, o_proj, times the
  residual multiplier;
- after either, RMSNorm, a SwiGLU MLP, times the residual multiplier;
- the embedding times its multiplier; the tied head divided by the logits
  scaling.

It imports nothing of the program, and of `reference/granite.py` only its
numeric helpers. `forward(..., fp8=True)` is the control: every matrix
product with both operands rounded to float8 e4m3, as `granite.forward`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from reference.granite import _mm, _rms, numbers, widest_gap, worst_rel_err  # noqa: F401

# fold_in tags: weights of a mamba / attention layer, the attention layers'
# context keys (+1: values), a request's recurrent state (+1: conv window)
MAMBA_TAG, ATTN_TAG, KV_TAG, STATE_TAG = 100, 200, 1000, 2000


def dims(c: dict) -> dict:
    D = c["hidden_size"]
    Di = c["mamba_expand"] * D
    P = c["mamba_d_head"]
    N = c["mamba_d_state"] * c["mamba_n_groups"]
    return dict(D=D, H=c["num_attention_heads"], K=c["num_key_value_heads"],
                hd=D // c["num_attention_heads"], F=c["shared_intermediate_size"],
                V=c["vocab_size"], L=c["num_hidden_layers"], Di=Di, P=P,
                Hm=Di // P, N=N, W=c["mamba_d_conv"], C=Di + 2 * N)


def layer_types(c: dict) -> list:
    """The kind of each of the first num_hidden_layers layers."""
    return list(c["layer_types"][: c["num_hidden_layers"]])


def kinds(c: dict):
    """(mamba layer ids, attention layer ids), in the model's order."""
    t = layer_types(c)
    return ([l for l, k in enumerate(t) if k == "mamba"],
            [l for l, k in enumerate(t) if k == "attention"])


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)


def embedding(c: dict, key) -> jnp.ndarray:
    d = dims(c)
    return _normal(jax.random.fold_in(key, 0), (d["V"], d["D"]),
                   c.get("embedding_std", c["initializer_range"]))


def _mlp_weights(c: dict, k) -> dict:
    d, std = dims(c), c["initializer_range"]
    return {"wg": _normal(jax.random.fold_in(k, 11), (d["D"], d["F"]), std),
            "wu": _normal(jax.random.fold_in(k, 12), (d["D"], d["F"]), std),
            "wd": _normal(jax.random.fold_in(k, 13), (d["F"], d["D"]), std)}


def mamba_weights(c: dict, key, i) -> dict:
    """The i-th mamba layer's weights: matrices in bf16 from
    normal(0, initializer_range); conv weight and bias uniform within
    1/sqrt(d_conv) (a depthwise Conv1d's default); A = -exp(A_log) with
    exp(A_log) uniform in [1, 16] and dt log-uniform in [0.001, 0.1]
    (dt_bias its inverse softplus), D = 1: Mamba2's initialisation."""
    d, std = dims(c), c["initializer_range"]
    k = jax.random.fold_in(jax.random.fold_in(key, MAMBA_TAG), i)
    f = lambda j: jax.random.fold_in(k, j)  # noqa: E731
    bound = d["W"] ** -0.5
    uni = lambda j, shape, lo, hi: jax.random.uniform(f(j), shape, jnp.float32, lo, hi)  # noqa: E731
    dt = jnp.exp(uni(5, (d["Hm"],), jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "w_in": _normal(f(1), (d["D"], 2 * d["Di"] + 2 * d["N"] + d["Hm"]), std),
        "conv_w": uni(2, (d["W"], d["C"]), -bound, bound).astype(jnp.bfloat16),
        "conv_b": uni(3, (d["C"],), -bound, bound).astype(jnp.bfloat16),
        "A_log": jnp.log(uni(4, (d["Hm"],), 1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "D": jnp.ones((d["Hm"],), jnp.float32),
        "w_out": _normal(f(6), (d["Di"], d["D"]), std),
        **_mlp_weights(c, k),
    }


def attention_weights(c: dict, key, i) -> dict:
    """The i-th attention layer's seven matrices in bf16."""
    d, std = dims(c), c["initializer_range"]
    k = jax.random.fold_in(jax.random.fold_in(key, ATTN_TAG), i)
    D, H, K, hd = d["D"], d["H"], d["K"], d["hd"]
    return {"wq": _normal(jax.random.fold_in(k, 1), (D, H, hd), std),
            "wk": _normal(jax.random.fold_in(k, 2), (D, K, hd), std),
            "wv": _normal(jax.random.fold_in(k, 3), (D, K, hd), std),
            "wo": _normal(jax.random.fold_in(k, 4), (H, hd, D), std),
            **_mlp_weights(c, k)}


def context_kv(c: dict, key, i, lanes: int, n: int):
    """Seeded keys and values of the first `n` positions of the i-th
    attention layer, (lanes, n, Hkv, hd) float32 each: a context standing in
    for a prefilled prompt."""
    d = dims(c)
    shape = (lanes, n, d["K"], d["hd"])
    kk = jax.random.fold_in(jax.random.fold_in(key, KV_TAG), i)
    kv = jax.random.fold_in(jax.random.fold_in(key, KV_TAG + 1), i)
    return (jax.random.normal(kk, shape, jnp.float32),
            jax.random.normal(kv, shape, jnp.float32))


def context_state(c: dict, key, request, i, lanes: int):
    """A request's seeded recurrent state at the i-th mamba layer: the SSM
    state (lanes, H, P, N), normal(0, context_state_std), and the last
    d_conv - 1 inputs of the convolution (lanes, d_conv - 1, C), oldest
    first, normal with the std of in_proj's outputs. A context that stands
    in for a prefilled prompt of the request `request`."""
    d = dims(c)
    k = jax.random.fold_in(jax.random.fold_in(key, STATE_TAG), request)
    k = jax.random.fold_in(k, i)
    ssm = jax.random.normal(jax.random.fold_in(k, 0),
                            (lanes, d["Hm"], d["P"], d["N"]), jnp.float32)
    conv = jax.random.normal(jax.random.fold_in(k, 1),
                             (lanes, d["W"] - 1, d["C"]), jnp.float32)
    return (ssm * c["context_state_std"],
            conv * c["initializer_range"] * d["D"] ** 0.5)


# --------------------------------------------------------------- forward
def _mlp(c, h, w, fp8):
    x = _rms(h, c["rms_norm_eps"])
    up = jax.nn.silu(_mm("bsd,df->bsf", x, w["wg"], fp8, -1, 0))
    up = up * _mm("bsd,df->bsf", x, w["wu"], fp8, -1, 0)
    return h + c["residual_multiplier"] * _mm("bsf,fd->bsd", up, w["wd"], fp8, -1, 0)


@partial(jax.jit, static_argnames=("c_items", "fp8"))
def _mamba(h, w, ssm0, conv0, *, c_items, fp8):
    c = dict(c_items)
    d = dims(c)
    Di, N, Hm, P = d["Di"], d["N"], d["Hm"], d["P"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    B, n, _ = h.shape
    eps = c["rms_norm_eps"]
    zxbcdt = _mm("bsd,de->bse", _rms(h, eps), w["w_in"], fp8, -1, 0)
    z, xbc, dt = zxbcdt[..., :Di], zxbcdt[..., Di:2 * Di + 2 * N], zxbcdt[..., 2 * Di + 2 * N:]
    seq = jnp.concatenate([conv0, xbc], axis=1)  # (B, W - 1 + n, C)
    W = d["W"]
    xbc = jax.nn.silu(sum(seq[:, j:j + n] * w["conv_w"][j] for j in range(W)) + w["conv_b"])
    x, b, cc = xbc[..., :Di], xbc[..., Di:Di + N], xbc[..., Di + N:]
    dt = jax.nn.softplus(dt + w["dt_bias"])  # (B, n, Hm)
    a = jnp.exp(dt * -jnp.exp(w["A_log"]))
    xh = x.reshape(B, n, Hm, P)

    def step(state, inp):  # state (B, Hm, P, N)
        a_t, dt_t, x_t, b_t, c_t = inp
        state = (a_t[:, :, None, None] * state
                 + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :])
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t)

    t = lambda v: jnp.moveaxis(v, 1, 0)  # noqa: E731
    _, y = jax.lax.scan(step, ssm0, (t(a), t(dt), t(xh), t(b), t(cc)))
    y = t(y) + w["D"][:, None] * xh  # (B, n, Hm, P)
    y = _rms(y.reshape(B, n, Di) * jax.nn.silu(z), eps)
    h = h + c["residual_multiplier"] * _mm("bse,ed->bsd", y, w["w_out"], fp8, -1, 0)
    return _mlp(c, h, w, fp8)


@partial(jax.jit, static_argnames=("c_items", "fp8"))
def _attention(h, w, kpre, vpre, *, c_items, fp8):
    c = dict(c_items)
    d = dims(c)
    H, K, hd = d["H"], d["K"], d["hd"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    B, n, _ = h.shape
    x = _rms(h, c["rms_norm_eps"])
    q = _mm("bsd,dhk->bshk", x, w["wq"], fp8, -1, 0)
    k = _mm("bsd,dhk->bshk", x, w["wk"], fp8, -1, 0)
    v = _mm("bsd,dhk->bshk", x, w["wv"], fp8, -1, 0)
    ks = jnp.concatenate([kpre, k], axis=1)  # (B, P0 + n, K, hd)
    vs = jnp.concatenate([vpre, v], axis=1)
    qg = q.reshape(B, n, K, H // K, hd)
    s = _mm("bskgd,btkd->bkgst", qg, ks, fp8, -1, -1) * c["attention_multiplier"]
    p0 = kpre.shape[1]
    allowed = jnp.arange(p0 + n)[None, :] <= (p0 + jnp.arange(n))[:, None]
    s = jnp.where(allowed[None, None, None], s, -jnp.inf)
    o = _mm("bkgst,btkd->bskgd", jax.nn.softmax(s, axis=-1), vs, fp8, -1, 1)
    attn = _mm("bshk,hkd->bsd", o.reshape(B, n, H, hd), w["wo"], fp8, (-2, -1), (0, 1))
    return _mlp(c, h + c["residual_multiplier"] * attn, w, fp8)


@partial(jax.jit, static_argnames=("c_items", "fp8"))
def _head(h, emb, *, c_items, fp8):
    c = dict(c_items)
    x = _rms(h, c["rms_norm_eps"])
    return _mm("bsd,vd->bsv", x, emb.astype(jnp.float32), fp8, -1, 1) / c["logits_scaling"]


def forward(c: dict, seed, tokens, start: int, request=None, *, fp8: bool = False):
    """Logits (lanes, n, V) float32 of `tokens` (lanes, n) fed at positions
    start..start+n-1. Before them: the attention layers' seeded context of
    `start` positions, and the mamba layers' recurrent state of request
    `request` (`context_state`; zeros where `request` is None, as for a
    prompt read from its first token). Weights, context and state are drawn
    again from the seed, one layer at a time."""
    d = dims(c)
    items = numbers(c)
    lanes = tokens.shape[0]
    key = jax.random.key(seed)
    mambas, attns = kinds(c)
    with jax.default_matmul_precision("highest"):
        emb = jax.jit(partial(embedding, c))(key)
        h = c["embedding_multiplier"] * emb[jnp.asarray(tokens)].astype(jnp.float32)
        for layer, kind in enumerate(layer_types(c)):
            if kind == "mamba":
                i = mambas.index(layer)
                if request is None:
                    ssm = jnp.zeros((lanes, d["Hm"], d["P"], d["N"]), jnp.float32)
                    conv = jnp.zeros((lanes, d["W"] - 1, d["C"]), jnp.float32)
                else:
                    ssm, conv = jax.jit(partial(context_state, c), static_argnums=(3,))(
                        key, request, i, lanes)
                w = jax.jit(partial(mamba_weights, c))(key, i)
                h = _mamba(h, w, ssm, conv, c_items=items, fp8=fp8)
            else:
                i = attns.index(layer)
                kpre, vpre = jax.jit(partial(context_kv, c), static_argnums=(2, 3))(
                    key, i, lanes, start)
                w = jax.jit(partial(attention_weights, c))(key, i)
                h = _attention(h, w, kpre, vpre, c_items=items, fp8=fp8)
        return _head(h, emb, c_items=items, fp8=fp8)
