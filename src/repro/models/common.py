"""Shared model building blocks.

Pure-functional JAX modules: every block is `init(key, cfg) -> params` plus
`apply(params, x, ...) -> y`. Parameters are plain dict pytrees so that layer
stacks can be vmapped/scanned and sharded with NamedSharding rules.

Supports: RMSNorm, SwiGLU / GELU MLPs, GQA attention with RoPE, M-RoPE
(Qwen2-VL style 3-section rotary), sliding-window attention (SWA), qk_norm
(Qwen3), and single-token decode against a KV cache (ring-buffered for SWA).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config object drives every architecture in the zoo."""

    arch_id: str
    family: str  # dense | moe | ssm | hybrid | mamba_attn | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    # attention variants
    head_dim: int = 0  # 0 -> d_model // n_heads
    qk_norm: bool = False
    sliding_window: int = 0  # 0 -> full causal attention
    rope: bool = True  # False: no positional encoding (NoPE)
    rope_theta: float = 10_000.0
    mrope_sections: Tuple[int, int, int] = ()  # Qwen2-VL M-RoPE (t, h, w)

    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_interleave: int = 1  # every `i`-th layer is MoE (1 = all, 2 = alternate)
    shared_expert: bool = False
    capacity_factor: float = 1.25
    moe_group: int = 256  # tokens per routing group (dispatch-einsum cost lever)

    # SSM / hybrid / xLSTM
    ssm_state: int = 0  # Mamba2 state dim N
    ssm_heads: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 256
    ssm_conv_bias: bool = False
    ssm_norm_eps: float = 0.0  # the gated norm's eps (0 -> norm_eps)
    attn_every: int = 0  # zamba2: shared attention block applied every k layers
    # mamba_attn (granite-4.0-h): the layers at these indices are attention
    # blocks, every other layer a Mamba2 mixer; an MLP follows every layer
    attn_layers: Tuple[int, ...] = ()
    slstm_every: int = 0  # xlstm: one sLSTM per `k` blocks (others mLSTM)

    # attention/CE chunking (memory): query-block size for training
    # attention (0 = dense S×S), token-chunk for the cross-entropy head
    attn_qchunk: int = 512
    ce_chunk: int = 1024

    # audio (musicgen): number of parallel codebooks
    n_codebooks: int = 0

    # vlm: number of image patch positions reserved at sequence start
    vision_patches: int = 0

    gated_mlp: bool = True  # SwiGLU; False = plain GELU MLP (starcoder2)
    # §Perf lever: pad the vocab to this size (0 = off). Unshardable vocabs
    # (granite's 49155) force the LM head onto the d_model contraction dim,
    # all-reducing full fp32 logits per CE chunk; padding to a multiple of
    # the model-axis size makes the head vocab-parallel (logsumexp then
    # reduces a scalar per token instead). Pad logits are masked to -1e30.
    vocab_pad: int = 0
    # §Perf lever: remat policy for the layer-stack checkpointing.
    #   "full"    — recompute everything in bwd (min memory, replays the
    #               forward collectives a second time)
    #   "outputs" — save attention/MLP/MoE block outputs (skips the fwd
    #               replay and its collectives; +2 activations per layer)
    remat_policy: str = "full"
    norm_eps: float = 1e-5
    # unroll the layer stack into a python loop instead of lax.scan. lax.scan
    # keeps HLO O(1) in depth (fast compiles, the production path); unrolling
    # makes XLA's cost_analysis see every layer (while-loop bodies are
    # counted ONCE by HloCostAnalysis), so the dry-run lowers with
    # unroll=True for honest roofline terms.
    unroll: bool = False
    tie_embeddings: bool = False
    dtype: Any = jnp.float32

    # citation for the assigned-architecture table
    source: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def ssm_layers(self) -> Tuple[int, ...]:
        """The Mamba2 layers of a `mamba_attn` model, in order."""
        return tuple(l for l in range(self.n_layers) if l not in self.attn_layers)

    @property
    def is_moe_arch(self) -> bool:
        return self.n_experts > 0

    @property
    def padded_vocab(self) -> int:
        return max(self.vocab, self.vocab_pad)

    def checkpoint(self):
        """jax.checkpoint with the configured policy (see remat_policy)."""
        if self.remat_policy == "outputs":
            policy = jax.checkpoint_policies.save_only_these_names(
                "attn_out", "mlp_out", "moe_out", "ssm_out"
            )
            return lambda f: jax.checkpoint(f, policy=policy)
        return jax.checkpoint

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------
def dense_init(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init (matches common LLM init schemes)."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def rmsnorm_init(dim, dtype):
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params, x, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE + M-RoPE)
# ---------------------------------------------------------------------------
def rope_freqs(hd: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float, sections: Tuple[int, int, int]
) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE. positions: (B, 3, S) for (t, h, w) ids.

    The hd/2 frequency channels are split into `sections` (t, h, w); each
    section rotates by its own position id stream. [arXiv:2409.12191]
    """
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    # (B, 3, S, hd/2) angles, then select the (t|h|w) stream per channel.
    angles_all = positions[..., None].astype(jnp.float32) * freqs  # (B,3,S,hd/2)
    sec_ids = jnp.repeat(
        jnp.arange(3), jnp.asarray(sections), total_repeat_length=hd // 2
    )  # (hd/2,) in {0,1,2}
    sel = jax.nn.one_hot(sec_ids, 3, dtype=jnp.float32)  # (hd/2, 3)
    angles = jnp.einsum("bksc,ck->bsc", angles_all, sel)  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def default_positions(cfg: ModelConfig, batch: int, seq: int, offset=0) -> jnp.ndarray:
    pos = jnp.arange(seq, dtype=jnp.int32)[None, :] + offset  # (1, S) or (B, S)
    pos = jnp.broadcast_to(pos, (batch, seq))
    if cfg.mrope_sections:
        return jnp.broadcast_to(pos[:, None, :], (batch, 3, seq))
    return pos


# ---------------------------------------------------------------------------
# Attention (GQA; train: full causal or SWA; decode: KV cache)
# ---------------------------------------------------------------------------
def attention_init(key, cfg: ModelConfig):
    hd = cfg.hd
    k_q, k_k, k_v, k_o = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k_q, (cfg.d_model, cfg.n_heads, hd), cfg.dtype),
        "wk": dense_init(k_k, (cfg.d_model, cfg.n_kv_heads, hd), cfg.dtype),
        "wv": dense_init(k_v, (cfg.d_model, cfg.n_kv_heads, hd), cfg.dtype),
        "wo": dense_init(k_o, (cfg.n_heads, hd, cfg.d_model), cfg.dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, cfg.dtype)
        p["k_norm"] = rmsnorm_init(hd, cfg.dtype)
    return p


def _rotate(cfg: ModelConfig, x, positions):
    if not cfg.rope:
        return x
    if cfg.mrope_sections:
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return apply_rope(x, positions, cfg.rope_theta)


def _qkv(params, cfg: ModelConfig, x, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    q = _rotate(cfg, q, positions)
    k = _rotate(cfg, k, positions)
    return q, k, v


def _attn_block(cfg, q_blk, k, v, offset, S, window):
    """Attention of one query block vs the full K/V. q_blk: (B,qs,n_kv,g,hd)."""
    hd = cfg.hd
    qs = q_blk.shape[1]
    scores = jnp.einsum("bsngk,btnk->bnsgt", q_blk, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    i = offset + jnp.arange(qs)[:, None]
    j = jnp.arange(S)[None, :]
    mask = j <= i
    if window and window > 0:
        mask &= j > i - window
    scores = jnp.where(mask[None, None, :, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(k.dtype)
    return jnp.einsum("bnsgt,btnk->bsngk", probs, v)


def attention(params, cfg: ModelConfig, x, positions, window: int = -1):
    """Training-mode causal (optionally sliding-window) GQA attention.

    x: (B, S, D). window: -1 -> cfg.sliding_window, 0 -> full causal.
    Long sequences process queries in blocks of `attn_qchunk` so the S×S
    score tensor is never materialized (flash-attention via remat; the
    Pallas kernel is the TPU fast path for decode, this is the train path).
    """
    B, S, _ = x.shape
    hd = cfg.hd
    group = cfg.n_heads // cfg.n_kv_heads
    q, k, v = _qkv(params, cfg, x, positions)

    # (B, S, n_kv, group, hd) grouped query layout keeps the GQA broadcast
    # explicit for the partitioner (n_kv shards over `model`).
    q = q.reshape(B, S, cfg.n_kv_heads, group, hd)
    w = cfg.sliding_window if window == -1 else window

    qc = cfg.attn_qchunk
    if qc <= 0 or S <= qc:
        out = _attn_block(cfg, q, k, v, 0, S, w)
    else:
        assert S % qc == 0, (S, qc)
        nb = S // qc
        qb = q.reshape(B, nb, qc, cfg.n_kv_heads, group, hd)

        @jax.checkpoint
        def body(_, inp):
            q_i, i = inp
            return None, _attn_block(cfg, q_i, k, v, i * qc, S, w)

        if cfg.unroll:
            outs = [
                _attn_block(cfg, qb[:, i], k, v, i * qc, S, w) for i in range(nb)
            ]
            out = jnp.stack(outs, axis=1)
        else:
            _, out = jax.lax.scan(
                body, None, (qb.transpose(1, 0, 2, 3, 4, 5), jnp.arange(nb))
            )
            out = out.transpose(1, 0, 2, 3, 4, 5)
        out = out.reshape(B, S, cfg.n_kv_heads, group, hd)

    out = out.reshape(B, S, cfg.n_heads, hd)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def attention_decode(params, cfg: ModelConfig, x, cache, window: int = -1):
    """Single-token decode: x (B, 1, D); cache dict(k, v, index).

    cache["k"], cache["v"]: (B, C, n_kv, hd); C = full seq or SWA ring size.
    cache["index"]: scalar int32, number of tokens already cached. With a
    ring cache (C < true seq len) positions keep counting up but writes wrap.
    """
    B = x.shape[0]
    hd = cfg.hd
    group = cfg.n_heads // cfg.n_kv_heads
    C = cache["k"].shape[1]
    idx = cache["index"]

    positions = default_positions(cfg, B, 1, offset=idx)
    q, k, v = _qkv(params, cfg, x, positions)  # (B,1,h,hd)

    slot = jnp.mod(idx, C)
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))

    q = q.reshape(B, 1, cfg.n_kv_heads, group, hd)
    scores = jnp.einsum("bsngk,btnk->bnsgt", q, ck).astype(jnp.float32) / math.sqrt(hd)

    # valid slots: those already written (ring-aware).
    t = jnp.arange(C)
    n_written = jnp.minimum(idx + 1, C)
    # ring order irrelevant for softmax; validity mask only.
    valid = t < n_written
    w = cfg.sliding_window if window == -1 else window
    if w and 0 < w < C:
        # ring cache sized >= window: all written slots are within-window.
        age = jnp.mod(slot - t, C)
        valid &= age < w
    scores = jnp.where(valid[None, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bnsgt,btnk->bsngk", probs, cv).reshape(B, 1, cfg.n_heads, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    new_cache = {"k": ck, "v": cv, "index": idx + 1}
    return y, new_cache


def attention_cache_init(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    """KV cache for one layer. SWA archs get a ring buffer of the window size."""
    C = max_seq
    if cfg.sliding_window and cfg.sliding_window < max_seq:
        C = cfg.sliding_window
    dtype = dtype or cfg.dtype
    return {
        "k": jnp.zeros((batch, C, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((batch, C, cfg.n_kv_heads, cfg.hd), dtype),
        "index": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------
def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    k_g, k_u, k_d = jax.random.split(key, 3)
    p = {
        "wu": dense_init(k_u, (cfg.d_model, d_ff), cfg.dtype),
        "wd": dense_init(k_d, (d_ff, cfg.d_model), cfg.dtype),
    }
    if cfg.gated_mlp:
        p["wg"] = dense_init(k_g, (cfg.d_model, d_ff), cfg.dtype)
    return p


def mlp(params, x):
    if "wg" in params:  # SwiGLU
        h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, params["wg"]))
        h = h * jnp.einsum("bsd,df->bsf", x, params["wu"])
    else:  # plain GELU (starcoder2)
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, params["wu"]))
    return jnp.einsum("bsf,fd->bsd", h, params["wd"])


# ---------------------------------------------------------------------------
# Standard pre-norm transformer block (attention + MLP)
# ---------------------------------------------------------------------------
def block_init(key, cfg: ModelConfig):
    k_a, k_m = jax.random.split(key)
    return {
        "attn_norm": rmsnorm_init(cfg.d_model, cfg.dtype),
        "attn": attention_init(k_a, cfg),
        "mlp_norm": rmsnorm_init(cfg.d_model, cfg.dtype),
        "mlp": mlp_init(k_m, cfg),
    }


def block_apply(params, cfg: ModelConfig, x, positions, window: int = -1):
    a = attention(params["attn"], cfg, rmsnorm(params["attn_norm"], x, cfg.norm_eps), positions, window)
    x = x + _checkpoint_name(a, "attn_out")
    m = mlp(params["mlp"], rmsnorm(params["mlp_norm"], x, cfg.norm_eps))
    return x + _checkpoint_name(m, "mlp_out")


def block_decode(params, cfg: ModelConfig, x, cache, window: int = -1):
    a, cache = attention_decode(
        params["attn"], cfg, rmsnorm(params["attn_norm"], x, cfg.norm_eps), cache, window
    )
    x = x + a
    x = x + mlp(params["mlp"], rmsnorm(params["mlp_norm"], x, cfg.norm_eps))
    return x, cache
