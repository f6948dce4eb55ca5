"""Full-model assembly for all six architecture families.

Layer parameters are *stacked* (leading axis = depth) and applied with
`lax.scan` + `jax.checkpoint` (remat), so HLO size and compile time are O(1)
in depth — required for the 94-layer MoE — and activation memory is
O(sqrt-ish) via rematerialization. Heterogeneous stacks (zamba2's shared
attention, xlstm's mLSTM/sLSTM pattern, llama4's dense/MoE alternation) are
expressed as *superblocks*: the scan unit contains one of each sub-layer
type, so every scan step has homogeneous parameter shapes and no lax.cond.
granite-4.0-h's pattern (`mamba_attn`: attention at the layers listed in
`attn_layers`, Mamba2 elsewhere, an MLP after each) runs as segments: each
run of Mamba2 layers is one scan, each attention layer is applied alone.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import ssm
from repro.models.common import (
    ModelConfig,
    attention,
    attention_cache_init,
    attention_decode,
    attention_init,
    block_apply,
    block_decode,
    block_init,
    default_positions,
    embed_init,
    mlp,
    rmsnorm,
    rmsnorm_init,
)
from repro.models.moe import (
    moe_block_apply,
    moe_block_decode,
    moe_block_init,
)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------
def embed_tokens(params, cfg: ModelConfig, tokens):
    if cfg.n_codebooks:
        # musicgen: tokens (B, n_codebooks, S); sum the codebook embeddings.
        # params["embed"]: (n_codebooks, vocab, D)
        x = 0.0
        for c in range(cfg.n_codebooks):
            x = x + jnp.take(params["embed"][c], tokens[:, c], axis=0)
        return x
    return jnp.take(params["embed"], tokens, axis=0)


def lm_logits(params, cfg: ModelConfig, x):
    if cfg.n_codebooks:
        # (B,S,D) x (nc,D,V) -> (B,S,nc,V)
        return jnp.einsum("bsd,cdv->bscv", x, params["heads"])
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    if cfg.padded_vocab > cfg.vocab:
        # mask pad slots (elementwise on the vocab-sharded axis: no comm)
        pad_bias = jnp.where(
            jnp.arange(cfg.padded_vocab) < cfg.vocab, 0.0, -1e30
        ).astype(logits.dtype)
        logits = logits + pad_bias
    return logits


# ---------------------------------------------------------------------------
# Superblock definitions per family
# ---------------------------------------------------------------------------
def _stacked_init(key, n: int, init_fn):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def backbone_init(key, cfg: ModelConfig) -> Dict[str, Any]:
    keys = jax.random.split(key, 8)
    p: Dict[str, Any] = {}
    f = cfg.family
    if f in ("dense", "vlm", "audio"):
        p["blocks"] = _stacked_init(keys[0], cfg.n_layers, lambda k: block_init(k, cfg))
    elif f == "moe":
        if cfg.moe_interleave == 1:
            p["blocks"] = _stacked_init(
                keys[0], cfg.n_layers, lambda k: moe_block_init(k, cfg)
            )
        else:
            n_pairs = cfg.n_layers // 2
            p["dense_blocks"] = _stacked_init(
                keys[0], n_pairs, lambda k: block_init(k, cfg)
            )
            p["moe_blocks"] = _stacked_init(
                keys[1], n_pairs, lambda k: moe_block_init(k, cfg)
            )
    elif f == "hybrid":
        # zamba2: n_super superblocks of (attn_every mamba + 1 shared attn),
        # plus leftover mamba layers; the attention block weights are SHARED.
        n_super = cfg.n_layers // cfg.attn_every
        leftover = cfg.n_layers - n_super * cfg.attn_every
        p["mamba"] = _stacked_init(
            keys[0], n_super * cfg.attn_every, lambda k: ssm.mamba2_init(k, cfg)
        )
        p["mamba"] = jax.tree.map(
            lambda a: a.reshape(n_super, cfg.attn_every, *a.shape[1:]), p["mamba"]
        )
        if leftover:
            p["mamba_tail"] = _stacked_init(
                keys[1], leftover, lambda k: ssm.mamba2_init(k, cfg)
            )
        p["shared_attn"] = block_init(keys[2], cfg)  # one copy, reused
    elif f == "mamba_attn":
        p["ssm_blocks"] = _stacked_init(
            keys[0], len(cfg.ssm_layers), lambda k: ssm.mamba2_mlp_init(k, cfg)
        )
        if cfg.attn_layers:
            p["attn_blocks"] = _stacked_init(
                keys[1], len(cfg.attn_layers), lambda k: block_init(k, cfg)
            )
    elif f == "ssm":
        # xlstm: groups of (slstm_every-1 mLSTM + 1 sLSTM).
        g = cfg.slstm_every
        n_groups = cfg.n_layers // g
        p["mlstm"] = _stacked_init(
            keys[0], n_groups * (g - 1), lambda k: ssm.mlstm_init(k, cfg)
        )
        p["mlstm"] = jax.tree.map(
            lambda a: a.reshape(n_groups, g - 1, *a.shape[1:]), p["mlstm"]
        )
        p["slstm"] = _stacked_init(keys[1], n_groups, lambda k: ssm.slstm_init(k, cfg))
    else:
        raise ValueError(f"unknown family {f}")
    return p


def model_init(key, cfg: ModelConfig) -> Dict[str, Any]:
    k_e, k_b, k_h = jax.random.split(key, 3)
    p = {"backbone": backbone_init(k_b, cfg), "final_norm": rmsnorm_init(cfg.d_model, cfg.dtype)}
    if cfg.n_codebooks:
        p["embed"] = embed_init(k_e, (cfg.n_codebooks, cfg.vocab, cfg.d_model), cfg.dtype)
        p["heads"] = jax.vmap(lambda k: embed_init(k, (cfg.d_model, cfg.vocab), cfg.dtype))(
            jax.random.split(k_h, cfg.n_codebooks)
        )
    else:
        p["embed"] = embed_init(k_e, (cfg.padded_vocab, cfg.d_model), cfg.dtype)
        if not cfg.tie_embeddings:
            p["head"] = embed_init(k_h, (cfg.d_model, cfg.padded_vocab), cfg.dtype)
    if cfg.family == "vlm":
        # projector for the (stubbed) vision frontend's patch embeddings
        p["vis_proj"] = embed_init(jax.random.fold_in(k_h, 1), (cfg.d_model, cfg.d_model), cfg.dtype)
    return p


# ---------------------------------------------------------------------------
# Forward (training) pass
# ---------------------------------------------------------------------------
def _scan_or_unroll(cfg: ModelConfig, body, carry, stacked):
    """lax.scan over stacked layer params, or a python loop when
    cfg.unroll (dry-run cost analysis needs unrolled while-bodies)."""
    if not cfg.unroll:
        out, _ = jax.lax.scan(lambda c, p: body(c, p), carry, stacked)
        return out
    n = jax.tree.leaves(stacked)[0].shape[0]
    for i in range(n):
        p_i = jax.tree.map(lambda a: a[i], stacked)
        carry, _ = body(carry, p_i)
    return carry


def layer_segments(cfg: ModelConfig):
    """A `mamba_attn` stack in layer order: ("ssm", first, stop) for a run of
    the stacked Mamba2 layers, ("attn", i) for the i-th attention layer."""
    segs, start = [], 0
    for i, layer in enumerate(cfg.attn_layers):
        stop = layer - i  # Mamba2 layers below this one
        if stop > start:
            segs.append(("ssm", start, stop))
        segs.append(("attn", i))
        start = stop
    if len(cfg.ssm_layers) > start:
        segs.append(("ssm", start, len(cfg.ssm_layers)))
    return segs


def _take(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _rows(tree, start, stop):
    return jax.tree.map(lambda a: a[start:stop], tree)


def backbone_apply(params, cfg: ModelConfig, x, positions, window: int = -1):
    """x: (B,S,D) -> (B,S,D), aux dict. Scan over stacked layers w/ remat."""
    f = cfg.family
    aux = {"lb_loss": jnp.zeros((), jnp.float32), "z_loss": jnp.zeros((), jnp.float32)}
    ckpt = cfg.checkpoint()

    if f in ("dense", "vlm", "audio"):

        @ckpt
        def body(x, p):
            return block_apply(p, cfg, x, positions, window), None

        x = _scan_or_unroll(cfg, body, x, params["blocks"])

    elif f == "moe" and cfg.moe_interleave == 1:

        @ckpt
        def body(carry, p):
            x, lb, zl = carry
            x, a = moe_block_apply(p, cfg, x, positions, window)
            return (x, lb + a["lb_loss"], zl + a["z_loss"]), None

        (x, lb, zl) = _scan_or_unroll(
            cfg, body, (x, aux["lb_loss"], aux["z_loss"]), params["blocks"]
        )
        aux = {"lb_loss": lb / cfg.n_layers, "z_loss": zl / cfg.n_layers}

    elif f == "moe":  # alternating dense / MoE (llama4)

        @ckpt
        def body(carry, p):
            x, lb, zl = carry
            pd, pm = p
            x = block_apply(pd, cfg, x, positions, window)
            x, a = moe_block_apply(pm, cfg, x, positions, window)
            return (x, lb + a["lb_loss"], zl + a["z_loss"]), None

        (x, lb, zl) = _scan_or_unroll(
            cfg,
            body,
            (x, aux["lb_loss"], aux["z_loss"]),
            (params["dense_blocks"], params["moe_blocks"]),
        )
        n_pairs = cfg.n_layers // 2
        aux = {"lb_loss": lb / n_pairs, "z_loss": zl / n_pairs}

    elif f == "hybrid":
        shared = params["shared_attn"]

        @ckpt
        def body(x, p):
            def mamba_layer(x, pm):
                return ssm.mamba2_apply(pm, cfg, x), None

            x = _scan_or_unroll(cfg, mamba_layer, x, p)
            x = block_apply(shared, cfg, x, positions, window)
            return x, None

        x = _scan_or_unroll(cfg, body, x, params["mamba"])
        if "mamba_tail" in params:

            @ckpt
            def tail(x, pm):
                return ssm.mamba2_apply(pm, cfg, x), None

            x = _scan_or_unroll(cfg, tail, x, params["mamba_tail"])

    elif f == "mamba_attn":

        @ckpt
        def ssm_body(x, p):
            return ssm.mamba2_mlp_apply(p, cfg, x), None

        @ckpt
        def attn_body(x, p):
            return block_apply(p, cfg, x, positions, window)

        for seg in layer_segments(cfg):
            if seg[0] == "attn":
                x = attn_body(x, _take(params["attn_blocks"], seg[1]))
            else:
                x = _scan_or_unroll(
                    cfg, ssm_body, x, _rows(params["ssm_blocks"], *seg[1:])
                )

    elif f == "ssm":

        @ckpt
        def body(x, p):
            pm, ps = p

            def mlstm_layer(x, pp):
                return ssm.mlstm_apply(pp, cfg, x), None

            x = _scan_or_unroll(cfg, mlstm_layer, x, pm)
            x = ssm.slstm_apply(ps, cfg, x)
            return x, None

        x = _scan_or_unroll(cfg, body, x, (params["mlstm"], params["slstm"]))
    else:
        raise ValueError(f)
    return x, aux


def forward_hidden(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray], window: int = -1):
    """Embed → backbone → final norm. Returns (hidden (B,S,D), aux)."""
    tokens = batch["tokens"]
    B = tokens.shape[0]
    x = embed_tokens(params, cfg, tokens)

    if cfg.family == "vlm" and "image_embeds" in batch:
        # Early fusion: prepend (stubbed) vision patch embeddings.
        vis = jnp.einsum("bpd,de->bpe", batch["image_embeds"].astype(x.dtype), params["vis_proj"])
        x = jnp.concatenate([vis, x], axis=1)

    S = x.shape[1]
    positions = batch.get("positions")
    if positions is None:
        positions = default_positions(cfg, B, S)

    x, aux = backbone_apply(params["backbone"], cfg, x, positions, window)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.family == "vlm" and "image_embeds" in batch:
        x = x[:, batch["image_embeds"].shape[1] :]  # logits over text positions
    return x, aux


def _ce_block(params, cfg: ModelConfig, h_blk, tgt_blk, mask_blk):
    """CE over one token block. h_blk: (B,T,D); tgt (B,T[,nc]); mask (B,T)."""
    logits = lm_logits(params, cfg, h_blk)
    lg = logits.astype(jnp.float32)
    if cfg.n_codebooks:
        # lg: (B,T,nc,V); tgt_blk: (B,T,nc)
        lse = jax.nn.logsumexp(lg, axis=-1)
        pick = jnp.take_along_axis(lg, tgt_blk[..., None], axis=-1)[..., 0]
        per_tok = jnp.mean(lse - pick, axis=-1)  # mean over codebooks
    else:
        lse = jax.nn.logsumexp(lg, axis=-1)
        pick = jnp.take_along_axis(lg, tgt_blk[..., None], axis=-1)[..., 0]
        per_tok = lse - pick
    return jnp.sum(per_tok * mask_blk)


def head_ce(params, cfg: ModelConfig, hidden, tokens):
    """Next-token cross-entropy, computed in token chunks so the (B,S,V)
    logits tensor is never materialized (fp32 logits at 150k vocab are the
    dominant activation otherwise)."""
    if cfg.n_codebooks:
        tgt = tokens[:, :, 1:].transpose(0, 2, 1)  # (B,S-1,nc)
    else:
        tgt = tokens[:, 1:]  # (B,S-1)
    h = hidden[:, :-1]
    B, Sm1 = h.shape[0], h.shape[1]
    mask = (tgt >= 0 if not cfg.n_codebooks else jnp.ones(tgt.shape[:2], bool)).astype(jnp.float32)
    tgt = jnp.maximum(tgt, 0)

    T = cfg.ce_chunk
    if T <= 0 or Sm1 <= T:
        total = _ce_block(params, cfg, h, tgt, mask)
        return total / jnp.maximum(mask.sum(), 1.0)

    pad = (-Sm1) % T
    h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    tgt = jnp.pad(tgt, ((0, 0), (0, pad)) + ((0, 0),) * (tgt.ndim - 2))
    mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nb = h.shape[1] // T
    hb = h.reshape(B, nb, T, -1).transpose(1, 0, 2, 3)
    tb = tgt.reshape((B, nb, T) + tgt.shape[2:]).transpose((1, 0, 2) + tuple(range(3, tgt.ndim + 1)))
    mb = mask.reshape(B, nb, T).transpose(1, 0, 2)

    @jax.checkpoint
    def body(acc, inp):
        h_i, t_i, m_i = inp
        return acc + _ce_block(params, cfg, h_i, t_i, m_i), None

    if cfg.unroll:
        total = 0.0
        for i in range(nb):
            total, _ = body(total, (hb[i], tb[i], mb[i]))
    else:
        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hb, tb, mb))
    return total / jnp.maximum(mask.sum(), 1.0)


def forward(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray], window: int = -1):
    """Returns (logits, aux). batch: tokens (+ image_embeds, positions)."""
    x, aux = forward_hidden(params, cfg, batch, window)
    logits = lm_logits(params, cfg, x)
    return logits, aux


def loss_fn(params, cfg: ModelConfig, batch, window: int = -1):
    """Next-token cross-entropy (+ MoE aux). Returns (loss, metrics)."""
    hidden, aux = forward_hidden(params, cfg, batch, window)
    ce = head_ce(params, cfg, hidden, batch["tokens"])
    loss = ce + 0.01 * aux.get("lb_loss", 0.0) + 1e-3 * aux.get("z_loss", 0.0)
    return loss, {"ce": ce, **aux}


# ---------------------------------------------------------------------------
# Decode (serving) pass — one new token against cached state
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    """Stacked per-layer caches matching the scan structure."""
    f = cfg.family
    dtype = dtype or cfg.dtype

    def stack(n, make):
        one = make()
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n, *a.shape)), one)

    if f in ("dense", "vlm", "audio"):
        return {"blocks": stack(cfg.n_layers, lambda: attention_cache_init(cfg, batch, max_seq, dtype))}
    if f == "moe" and cfg.moe_interleave == 1:
        return {"blocks": stack(cfg.n_layers, lambda: attention_cache_init(cfg, batch, max_seq, dtype))}
    if f == "moe":
        n_pairs = cfg.n_layers // 2
        return {
            "dense_blocks": stack(n_pairs, lambda: attention_cache_init(cfg, batch, max_seq, dtype)),
            "moe_blocks": stack(n_pairs, lambda: attention_cache_init(cfg, batch, max_seq, dtype)),
        }
    if f == "hybrid":
        n_super = cfg.n_layers // cfg.attn_every
        leftover = cfg.n_layers - n_super * cfg.attn_every
        c = {
            "mamba": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_super, cfg.attn_every, *a.shape)),
                ssm.mamba2_cache_init(cfg, batch, dtype),
            ),
            "attn": stack(n_super, lambda: attention_cache_init(cfg, batch, max_seq, dtype)),
        }
        if leftover:
            c["mamba_tail"] = stack(leftover, lambda: ssm.mamba2_cache_init(cfg, batch, dtype))
        return c
    if f == "mamba_attn":
        return {
            "ssm": stack(len(cfg.ssm_layers), lambda: ssm.mamba2_cache_init(cfg, batch, dtype)),
            "attn": stack(
                len(cfg.attn_layers),
                lambda: attention_cache_init(cfg, batch, max_seq, dtype),
            ),
        }
    if f == "ssm":
        g = cfg.slstm_every
        n_groups = cfg.n_layers // g
        return {
            "mlstm": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_groups, g - 1, *a.shape)),
                ssm.mlstm_cache_init(cfg, batch, dtype),
            ),
            "slstm": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_groups, *a.shape)),
                ssm.slstm_state_init(cfg, batch),
            ),
        }
    raise ValueError(f)


def _scan_or_unroll_cache(cfg: ModelConfig, body, x, stacked):
    """Like _scan_or_unroll but the scanned pytree carries caches that are
    consumed and re-emitted per layer (ys of the scan)."""
    if not cfg.unroll:
        return jax.lax.scan(body, x, stacked)
    n = jax.tree.leaves(stacked)[0].shape[0]
    outs = []
    for i in range(n):
        x, o = body(x, jax.tree.map(lambda a: a[i], stacked))
        outs.append(o)
    stacked_out = jax.tree.map(lambda *ys: jnp.stack(ys), *outs)
    return x, stacked_out


def decode_step(params, cfg: ModelConfig, tokens, cache, window: int = -1):
    """tokens: (B,1) (or (B,nc,1) audio) -> (logits (B,1,V...), new cache)."""
    x = embed_tokens(params, cfg, tokens)
    f = cfg.family
    bb = params["backbone"]

    if f in ("dense", "vlm", "audio") or (f == "moe" and cfg.moe_interleave == 1):
        key = "blocks"
        dec = block_decode if f != "moe" else moe_block_decode

        def body(x, pc):
            p, c = pc
            x, c = dec(p, cfg, x, c, window)
            return x, c

        x, new_cache_blocks = _scan_or_unroll_cache(cfg, body, x, (bb[key], cache[key]))
        new_cache = {key: new_cache_blocks}

    elif f == "moe":

        def body(x, pc):
            pd, pm, cd, cm = pc
            x, cd = block_decode(pd, cfg, x, cd, window)
            x, cm = moe_block_decode(pm, cfg, x, cm, window)
            return x, (cd, cm)

        x, (cds, cms) = _scan_or_unroll_cache(
            cfg, body, x,
            (bb["dense_blocks"], bb["moe_blocks"], cache["dense_blocks"], cache["moe_blocks"]),
        )
        new_cache = {"dense_blocks": cds, "moe_blocks": cms}

    elif f == "hybrid":
        shared = bb["shared_attn"]

        def body(x, pc):
            pm, cm, ca = pc

            def inner(x, pcm):
                p, c = pcm
                x, c = ssm.mamba2_decode(p, cfg, x, c)
                return x, c

            x, cm = _scan_or_unroll_cache(cfg, inner, x, (pm, cm))
            x, ca = block_decode(shared, cfg, x, ca, window)
            return x, (cm, ca)

        x, (cms, cas) = _scan_or_unroll_cache(cfg, body, x, (bb["mamba"], cache["mamba"], cache["attn"]))
        new_cache = {"mamba": cms, "attn": cas}
        if "mamba_tail" in bb:

            def tail(x, pcm):
                p, c = pcm
                x, c = ssm.mamba2_decode(p, cfg, x, c)
                return x, c

            x, cts = _scan_or_unroll_cache(cfg, tail, x, (bb["mamba_tail"], cache["mamba_tail"]))
            new_cache["mamba_tail"] = cts

    elif f == "mamba_attn":

        def ssm_body(x, pc):
            p, c = pc
            return ssm.mamba2_mlp_decode(p, cfg, x, c)

        ssm_caches, attn_caches = [], []
        for seg in layer_segments(cfg):
            if seg[0] == "attn":
                x, c = block_decode(
                    _take(bb["attn_blocks"], seg[1]), cfg, x,
                    _take(cache["attn"], seg[1]), window,
                )
                attn_caches.append(c)
            else:
                x, c = _scan_or_unroll_cache(
                    cfg, ssm_body, x,
                    (_rows(bb["ssm_blocks"], *seg[1:]), _rows(cache["ssm"], *seg[1:])),
                )
                ssm_caches.append(c)
        new_cache = {
            "ssm": jax.tree.map(lambda *a: jnp.concatenate(a), *ssm_caches),
            "attn": (jax.tree.map(lambda *a: jnp.stack(a), *attn_caches)
                     if attn_caches else cache["attn"]),
        }

    elif f == "ssm":

        def body(x, pc):
            pm, ps, cm, cs = pc

            def inner(x, pcm):
                p, c = pcm
                x, c = ssm.mlstm_decode(p, cfg, x, c)
                return x, c

            x, cm = _scan_or_unroll_cache(cfg, inner, x, (pm, cm))
            x, cs = ssm.slstm_decode(ps, cfg, x, cs)
            return x, (cm, cs)

        x, (cms, css) = _scan_or_unroll_cache(
            cfg, body, x, (bb["mlstm"], bb["slstm"], cache["mlstm"], cache["slstm"])
        )
        new_cache = {"mlstm": cms, "slstm": css}
    else:
        raise ValueError(f)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_logits(params, cfg, x)
    return logits, new_cache
