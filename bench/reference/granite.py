"""Plain reference for granite-3.0 (GraniteForCausalLM), and its weights.

The weights, and the keys and values of a seeded context, are drawn here
from the seed, layer by layer, so that the reference can draw them again
after the program's copy is freed. `forward` is a teacher-forced pass in
float32 at the highest matmul precision, written from the published block:
RMSNorm, GQA attention with rotary positions, SwiGLU, and the four granite
multipliers (embedding, attention, residual, logits). It imports nothing
of the program.

`forward(..., fp8=True)` is the control: the same pass with both operands
of every matrix product rounded to float8 e4m3 (scaled per row and per
output column), the precision a later change to the bf16 weights would be
tempted by.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

KV_TAG = 1000  # fold_in tags of the seeded context's keys (+1: values)


def dims(c: dict) -> dict:
    D, H, K = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    return dict(D=D, H=H, K=K, hd=D // H, F=c["intermediate_size"],
                V=c["vocab_size"], L=c["num_hidden_layers"])


def leaf_shapes(c: dict) -> dict:
    d = dims(c)
    D, H, K, hd, F = d["D"], d["H"], d["K"], d["hd"], d["F"]
    return {"wq": (D, H, hd), "wk": (D, K, hd), "wv": (D, K, hd),
            "wo": (H, hd, D), "wg": (D, F), "wu": (D, F), "wd": (F, D)}


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)


def embedding(c: dict, key) -> jnp.ndarray:
    d = dims(c)
    return _normal(jax.random.fold_in(key, 0), (d["V"], d["D"]),
                   c.get("embedding_std", c["initializer_range"]))


def layer_weights(c: dict, key, layer) -> dict:
    """One layer's seven matrices in bf16; `layer` may be traced."""
    return {
        name: _normal(
            jax.random.fold_in(jax.random.fold_in(key, i + 1), layer),
            shape, c["initializer_range"],
        )
        for i, (name, shape) in enumerate(leaf_shapes(c).items())
    }


def context_kv(c: dict, key, layer, lanes: int, n: int):
    """Seeded keys and values of the first `n` cached positions of one
    layer, (lanes, n, Hkv, hd) float32 each: a context that stands in for a
    prefilled prompt (the keys are taken as already rotated)."""
    d = dims(c)
    shape = (lanes, n, d["K"], d["hd"])
    kk = jax.random.fold_in(jax.random.fold_in(key, KV_TAG), layer)
    kv = jax.random.fold_in(jax.random.fold_in(key, KV_TAG + 1), layer)
    return (jax.random.normal(kk, shape, jnp.float32),
            jax.random.normal(kv, shape, jnp.float32))


# --------------------------------------------------------------- forward
def _fp8(x, axis):
    """Round to float8 e4m3, scaled so the largest |x| along `axis` is 448."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, fp8, a_axis, b_axis):
    if fp8:
        a, b = _fp8(a, a_axis), _fp8(b, b_axis)
    return jnp.einsum(spec, a, b)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """Rotate-half rotary embedding. x (B, n, h, hd); pos (n,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]  # (n, hd/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("c_items", "fp8"))
def _layer(h, w, kpre, vpre, start, *, c_items, fp8):
    c = dict(c_items)
    d = dims(c)
    H, K, hd = d["H"], d["K"], d["hd"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    B, n, _ = h.shape
    eps = c["rms_norm_eps"]
    pos = start + jnp.arange(n)
    x = _rms(h, eps)
    q = _mm("bsd,dhk->bshk", x, w["wq"], fp8, -1, 0)
    k = _mm("bsd,dhk->bshk", x, w["wk"], fp8, -1, 0)
    v = _mm("bsd,dhk->bshk", x, w["wv"], fp8, -1, 0)
    q = _rope(q, pos, c["rope_theta"])
    k = _rope(k, pos, c["rope_theta"])
    ks = jnp.concatenate([kpre, k], axis=1)  # (B, P0 + n, K, hd)
    vs = jnp.concatenate([vpre, v], axis=1)
    g = H // K
    qg = q.reshape(B, n, K, g, hd)
    s = _mm("bskgd,btkd->bkgst", qg, ks, fp8, -1, -1) * c["attention_multiplier"]
    p0 = kpre.shape[1]
    t = jnp.arange(p0 + n)[None, :]
    allowed = t <= (p0 + jnp.arange(n))[:, None]  # context + causal
    s = jnp.where(allowed[None, None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = _mm("bkgst,btkd->bskgd", a, vs, fp8, -1, 1).reshape(B, n, H, hd)
    attn = _mm("bshk,hkd->bsd", o, w["wo"], fp8, (-2, -1), (0, 1))
    h = h + c["residual_multiplier"] * attn
    x = _rms(h, eps)
    up = jax.nn.silu(_mm("bsd,df->bsf", x, w["wg"], fp8, -1, 0))
    up = up * _mm("bsd,df->bsf", x, w["wu"], fp8, -1, 0)
    mlp = _mm("bsf,fd->bsd", up, w["wd"], fp8, -1, 0)
    return h + c["residual_multiplier"] * mlp


@partial(jax.jit, static_argnames=("c_items", "fp8"))
def _head(h, emb, *, c_items, fp8):
    c = dict(c_items)
    x = _rms(h, c["rms_norm_eps"])
    e = emb.astype(jnp.float32)
    return _mm("bsd,vd->bsv", x, e, fp8, -1, 1) / c["logits_scaling"]


def forward(c: dict, seed, tokens, start: int, *, fp8: bool = False):
    """Logits (lanes, n, V) float32 of `tokens` (lanes, n) fed at positions
    start..start+n-1, after the seeded context of `start` positions.
    Weights and context are drawn again from the seed, one layer at a time."""
    d = dims(c)
    items = numbers(c)
    lanes = tokens.shape[0]
    key = jax.random.key(seed)
    weights = jax.jit(partial(layer_weights, c))
    ctx = jax.jit(partial(context_kv, c), static_argnums=(2, 3))
    with jax.default_matmul_precision("highest"):
        emb = jax.jit(partial(embedding, c))(key)
        h = c["embedding_multiplier"] * emb[jnp.asarray(tokens)].astype(jnp.float32)
        for layer in range(d["L"]):
            kpre, vpre = ctx(key, layer, lanes, start)
            h = _layer(h, weights(key, layer), kpre, vpre, start,
                       c_items=items, fp8=fp8)
        return _head(h, emb, c_items=items, fp8=fp8)


def numbers(c: dict) -> tuple:
    """The configuration's numbers, hashable (a static jit argument)."""
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float)) and not isinstance(v, bool)))


def widest_gap(ref_logits, tokens) -> float:
    """Widest gap by which a chosen token's logit lies below the best."""
    ref_logits = jnp.asarray(ref_logits, jnp.float32)
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(tokens)[..., None], -1)[..., 0]
    return float(jnp.max(best - got))


def worst_rel_err(logits, ref_logits) -> float:
    """Worst ||logits - ref|| / ||ref|| over the rows of the last axis."""
    a = jnp.asarray(logits, jnp.float32)
    r = jnp.asarray(ref_logits, jnp.float32)
    return float(jnp.max(jnp.linalg.norm(a - r, axis=-1) / jnp.linalg.norm(r, axis=-1)))
