"""Incremental per-cohort decode over the paged KV cache.

The serving-plane fast path for `TransformerTask` cohort models: all live
cohorts' decode lanes advance one token in ONE jitted dispatch — gather
each cohort's params row from the (snapshot) stacked bank, vmap a
single-row decode step over rows, greedy-pick the next token — with one
step kept in flight, so that each token's copy to the host overlaps the
next step on the device. Attention against the paged cache runs through
`kernels.ops.decode_attention` (the Pallas flash-decode kernel; interpret
mode off-TPU) with `kernels.ref.decode_attention` as the selectable
bit-check oracle — backends must produce identical greedy token streams.

The per-row step mirrors `models.transformer.decode_step` for the dense
family, with the ring-buffer `attention_decode` swapped for a paged
append + length-masked kernel call. The cache is updated in place: the
fleet step donates it (on accelerators), the row step carries the whole
row cache through its layer loop and writes each layer's new position
with one `dynamic_update_slice`, and the kernel reads each layer's pages
where they lie in the stacked cache.

A Mamba2 hybrid (`mamba_attn`, granite-4.0-h) runs its layer pattern in
`make_hybrid_row_step`: the attention layers as above, on their own paged
cache, and each Mamba2 layer through `kernels.ops.ssm_decode`, which updates
that layer's recurrent state where it lies in the stacked, donated state
(`kernels.ref.ssm_decode` is its oracle), beside a convolution window
written in place with one `dynamic_update_slice`.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models.common import (
    default_positions,
    mlp,
    rmsnorm,
    _qkv,
)
from repro.models import ssm as ssm_model
from repro.models.transformer import embed_tokens, layer_segments, lm_logits
from repro.serve.kv_cache import PagedKVCache
from repro.utils.trace import span


def _ref_attend(q, kc, vc, layer, n):
    """The oracle on layer `layer` of a stacked (L, lanes, S, Hkv·hd) cache;
    it copies the layer out, as the oracle may."""
    lanes, S, W = kc.shape[1:]
    shape = (lanes, S, W // q.shape[-1], q.shape[-1])
    return kref.decode_attention(
        q, kc[layer].reshape(shape), vc[layer].reshape(shape), n
    )


# attend(q (lanes, H, hd), kc, vc (L, lanes, S, Hkv·hd), layer, length)
ATTEND = {
    "pallas": lambda q, kc, vc, layer, n: kops.decode_attention(
        q, kc, vc, n, layer
    ),
    "ref": _ref_attend,
}


# update(x (lanes, H, P), dt (lanes, H), A_log, dt_bias, D, b, c (lanes, N),
#        state (Lm, lanes, N, H·P), layer) -> (y (lanes, H, P), state)
UPDATE = {"pallas": kops.ssm_decode, "ref": kref.ssm_decode}


def check_supported(cfg):
    """Paged decode runs dense models and Mamba2 hybrids, full attention."""
    assert cfg.family in ("dense", "mamba_attn"), (
        f"paged decode supports dense and mamba_attn, got {cfg.family}"
    )
    assert not cfg.sliding_window, "paged decode is full-attention only"


@jax.jit
def gather_bank_rows(bank, slots):
    """Every leaf's rows at `slots`: the decode's copy of the bank, in one
    program."""
    return jax.tree.map(lambda a: a[slots], bank)


@jax.jit
def pick_tokens(logits, index):
    """Greedy next token (rows, lanes, 1) of a fleet step, and the
    positions advanced by one."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, :, None], index + 1


def _attention_layer(cfg, attend: Callable, lanes: int, index):
    """Layer `l` of the paged cache kc/vc (La, lanes, S, Hkv·hd): an
    attention block and its MLP at position `index`, the position written
    in place. Returns (x, kc, vc)."""
    positions = default_positions(cfg, lanes, 1, offset=index)

    def layer(x, kc, vc, p, l):
        row = (1, lanes, 1, kc.shape[-1])  # one position, every lane
        xa = rmsnorm(p["attn_norm"], x, cfg.norm_eps)
        q, k, v = _qkv(p["attn"], cfg, xa, positions)  # (lanes,1,H|Hkv,hd)
        at = (l, 0, index, 0)
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype).reshape(row), at)
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype).reshape(row), at)
        a = attend(q[:, 0], kc, vc, l, index + 1)  # (lanes, H, hd)
        x = x + jnp.einsum("bhk,hkd->bd", a, p["attn"]["wo"])[:, None, :]
        x = x + mlp(p["mlp"], rmsnorm(p["mlp_norm"], x, cfg.norm_eps))
        return x, kc, vc

    return layer


def make_row_decode_step(cfg, attend: Callable):
    """One cohort row, one decode step. Vmapped over rows by the caller.

    params: one bank row; tokens (lanes, 1) int32; kc/vc (L, lanes, S,
    Hkv·hd), the row's cache in storage form; index scalar int32 (current
    position). Returns (logits (lanes, V), kc, vc) with the position
    written. The cache is carried through the layer loop (not scanned as
    xs/ys), so XLA updates it in place and never slices a layer out.
    """
    check_supported(cfg)
    assert cfg.family == "dense", "a mamba_attn model steps in make_hybrid_row_step"

    def step(params, tokens, kc, vc, index):
        x = embed_tokens(params, cfg, tokens)  # (lanes, 1, D)
        layer = _attention_layer(cfg, attend, tokens.shape[0], index)
        blocks = params["backbone"]["blocks"]
        n_layers = kc.shape[0]
        if cfg.unroll:
            for l in range(n_layers):
                x, kc, vc = layer(x, kc, vc, jax.tree.map(lambda a: a[l], blocks), l)
        else:
            (x, kc, vc), _ = jax.lax.scan(
                lambda c, xs: (layer(*c, *xs), None),
                (x, kc, vc), (blocks, jnp.arange(n_layers)),
            )
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return lm_logits(params, cfg, x)[:, 0], kc, vc

    return step


def make_hybrid_row_step(cfg, attend: Callable, update: Callable):
    """One cohort row, one decode step of a Mamba2 hybrid (`mamba_attn`).
    Vmapped over rows by the caller.

    params: one bank row; tokens (lanes, 1) int32; kc/vc (La, lanes, S,
    Hkv·hd), the attention layers' paged cache; ssm (Lm, lanes, N, H·P) and
    conv (Lm, d_conv - 1, lanes, conv channels), the Mamba2 layers' state
    (`serve/kv_cache.py`);
    index scalar int32. Returns (logits (lanes, V), kc, vc, ssm, conv). The
    layers run in the model's order: each run of Mamba2 layers is a loop
    over their indices carrying the whole state (a scan over indices, not
    over the state as xs/ys), each attention layer is applied alone. Every
    state and page is written where it lies.
    """
    check_supported(cfg)
    d_inner, H, P, N = ssm_model.mamba2_dims(cfg)
    eps = ssm_model.gated_eps(cfg)
    ring = cfg.ssm_conv - 1

    def mamba_layer(x, ssm, conv, p, m, index):
        mx = p["mixer"]
        lanes = x.shape[0]
        h = rmsnorm(mx["norm"], x, cfg.norm_eps)[:, 0]  # (lanes, D)
        zxbcdt = jnp.einsum("bd,de->be", h, mx["w_in"])
        z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * N], axis=-1)
        # causal conv over the last d_conv positions: conv[m] is a ring in
        # which position p lies in slot p % ring; this position's input
        # overwrites the oldest slot
        xbc = xbc.astype(jnp.float32)
        w = mx["conv_w"].astype(jnp.float32)  # (d_conv, C)
        out = xbc * w[ring]
        for j in range(ring):  # position index - ring + j
            slot = jax.lax.dynamic_slice(
                conv, (m, (index + j) % ring, 0, 0), (1, 1) + conv.shape[2:]
            )
            out = out + slot[0, 0] * w[j]
        if "conv_b" in mx:
            out = out + mx["conv_b"].astype(jnp.float32)
        out = jax.nn.silu(out)
        conv = jax.lax.dynamic_update_slice(
            conv, xbc[None, None], (m, index % ring, 0, 0)
        )
        xs, b, c = jnp.split(out, [d_inner, d_inner + N], axis=-1)
        y, ssm = update(xs.reshape(lanes, H, P), dt, mx["A_log"], mx["dt_bias"],
                        mx["D"], b, c, ssm, m)
        y = y.reshape(lanes, d_inner) * jax.nn.silu(z.astype(jnp.float32))
        y = rmsnorm(mx["gated_norm"], y, eps).astype(x.dtype)
        x = x + jnp.einsum("be,ed->bd", y, mx["w_out"])[:, None, :]
        x = x + mlp(p["mlp"], rmsnorm(p["mlp_norm"], x, cfg.norm_eps))
        return x, ssm, conv

    def step(params, tokens, kc, vc, ssm, conv, index):
        x = embed_tokens(params, cfg, tokens)  # (lanes, 1, D)
        attn_layer = _attention_layer(cfg, attend, tokens.shape[0], index)
        bb = params["backbone"]
        blocks = bb["ssm_blocks"]
        for seg in layer_segments(cfg):
            if seg[0] == "attn":
                p = jax.tree.map(lambda a: a[seg[1]], bb["attn_blocks"])
                x, kc, vc = attn_layer(x, kc, vc, p, seg[1])
            elif cfg.unroll:
                for m in range(*seg[1:]):
                    p = jax.tree.map(lambda a: a[m], blocks)
                    x, ssm, conv = mamba_layer(x, ssm, conv, p, m, index)
            else:
                def body(carry, m):
                    p = jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(a, m, keepdims=False),
                        blocks,
                    )
                    return mamba_layer(*carry, p, m, index), None

                (x, ssm, conv), _ = jax.lax.scan(
                    body, (x, ssm, conv), jnp.arange(*seg[1:])
                )
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return lm_logits(params, cfg, x)[:, 0], kc, vc, ssm, conv

    return step


class CohortDecoder:
    """Fleet decoder: every live cohort × lane advances in one dispatch.

    `params_fn` yields the stacked bank params to read (the serving
    plane's round-boundary snapshot), `slots_fn` the live cohort slots;
    `sync()` reconciles the paged cache against them with the bank's
    slot-scatter discipline (pages freed on partition/merge).
    """

    def __init__(
        self,
        model,
        params_fn: Callable,
        slots_fn: Callable,
        lanes: int = 4,
        page_size: int = 128,
        backend: str = "pallas",
    ):
        self.model = model
        self.cfg = model.cfg
        self.params_fn = params_fn
        self.slots_fn = slots_fn
        self.lanes = int(lanes)
        self.backend = backend
        cfg = self.cfg
        check_supported(cfg)
        if cfg.family == "mamba_attn":
            d_inner, _, _, n = ssm_model.mamba2_dims(cfg)
            lm = len(cfg.ssm_layers)
            state = dict(ssm_shape=(lm, self.lanes, n, d_inner),
                         conv_shape=(lm, cfg.ssm_conv - 1, self.lanes, d_inner + 2 * n))
            row_step = make_hybrid_row_step(cfg, ATTEND[backend], UPDATE[backend])
        else:
            state, row_step = {}, make_row_decode_step(cfg, ATTEND[backend])
        self.cache = PagedKVCache(
            n_layers=len(cfg.attn_layers) if state else cfg.n_layers,
            lanes=self.lanes,
            n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.hd,
            page_size=page_size,
            dtype=jnp.float32,
            **state,
        )
        # one jitted fleet step; jax retraces per (rows, seq) bucket.
        # `decode` calls `_step`, which a caller may wrap (a timer, a hook);
        # `_fleet_step` stays the jitted step itself. The cache (and the
        # recurrent state) is donated on accelerators, so each step writes it
        # in place; on the CPU donation is gated off, as fl/pipeline.py gates
        # EXEC_DONATE.
        n_state = len(self.cache.arrays)
        donate = (
            {} if jax.default_backend() == "cpu"
            else {"donate_argnums": tuple(range(2, 2 + n_state))}  # kc, vc[, ssm, conv]
        )
        self._fleet_step = jax.jit(jax.vmap(row_step), **donate)
        self._step = self._fleet_step
        self.decode_dispatches = 0
        self.overlapped_fetches = 0  # token fetches with a later step in flight
        self.tokens: Optional[np.ndarray] = None  # (rows, lanes) last token

    @classmethod
    def from_engine(cls, engine, **kw) -> "CohortDecoder":
        model = engine.task.model  # TransformerTask
        pipe = engine.pipeline

        def slots_fn():
            return [
                pipe.bank.slot_of[l] for l in engine.coordinator.tree.leaves()
            ]

        return cls(
            model, lambda: pipe.serve_params, slots_fn, **kw
        )

    # ------------------------------------------------------------ plumbing
    @property
    def step_compiles(self) -> int:
        """Compiled variants of the fleet step (one per (rows, seq) bucket)."""
        return self._fleet_step._cache_size()

    @property
    def kv_nbytes(self) -> int:
        return self.cache.nbytes

    def sync(self):
        """Reconcile cache rows with the live cohort set (call after any
        round that may have partitioned)."""
        live = self.slots_fn()
        if self.cache.slots != [int(s) for s in live]:
            self.tokens = None  # fresh rows restart their lanes
        self.cache.sync(live)

    def _seed_tokens(self) -> np.ndarray:
        # deterministic per (slot, lane) seed token
        slots = np.asarray(self.cache.slots, np.int64)
        lane = np.arange(self.lanes, dtype=np.int64)[None, :]
        return ((slots[:, None] * self.lanes + lane) % self.cfg.vocab).astype(
            np.int32
        )

    # -------------------------------------------------------------- decode
    def decode(self, n_steps: int) -> Tuple[np.ndarray, np.ndarray]:
        """Greedy-decode `n_steps` tokens on every live cohort lane.

        Returns (tokens (live_rows, lanes, n_steps) int32,
                 last-step logits (live_rows, lanes, V) float32).
        One jitted dispatch per step for the WHOLE fleet, which consumes the
        cache it is given (donated): the arrays `cache.k/.v` (and
        `cache.ssm/.conv`) held before the call are invalid after it.

        One step is kept in flight: step i is dispatched on token i-1 as the
        device holds it, and only then is token i-1 copied to the host, so
        the copy overlaps step i on the device. The host is never more than
        one step ahead of the tokens it has received; `overlapped_fetches`
        counts such copies (n_steps - 1 a call).

        Each stage runs in a span (`repro.utils.trace`), in the order
        ``decode.prepare``; ``decode.dispatch`` (the fleet step's call) and
        ``decode.pick`` (the next token); (``decode.dispatch``,
        ``decode.pick``, ``decode.fetch``) for each further step, the fetch
        copying the token of the step before to the host; ``decode.fetch``
        of the last token; ``decode.writeback``.
        """
        with span("decode.prepare"):
            self.sync()
            live = self.cache.slots
            assert live, "no live cohorts to decode"
            assert n_steps >= 1, n_steps
            self.cache.ensure(n_steps + 1)
            r_pad = self.cache.rows
            # pad rows re-use row 0's slot params; their lanes are discarded
            slots_p = np.asarray(
                live + [live[0]] * (r_pad - len(live)), np.int32
            )
            if self.tokens is None:
                self.tokens = self._seed_tokens()
            tok = np.zeros((r_pad, self.lanes), np.int32)
            tok[: len(live)] = self.tokens
            tok = jnp.asarray(tok[:, :, None])  # (R, lanes, 1)
            params = gather_bank_rows(self.params_fn(), slots_p)
            state = self.cache.arrays
            index = jnp.asarray(self.cache.index)
        out = []
        for i in range(int(n_steps)):
            with span("decode.dispatch"):
                logits, *state = self._step(params, tok, *state, index)
            self.decode_dispatches += 1
            queued = tok  # token i-1, step i's input
            with span("decode.pick"):
                tok, index = pick_tokens(logits, index)
            if i:  # token i-1 to the host, step i queued behind its step
                with span("decode.fetch"):
                    out.append(np.asarray(queued)[:, :, 0])
                self.overlapped_fetches += 1
        with span("decode.fetch"):
            out.append(np.asarray(tok)[:, :, 0])
        with span("decode.writeback"):
            self.cache.arrays = state
            self.cache.index = np.asarray(index, np.int32)
            toks = np.stack(out, axis=-1)  # (R, lanes, n_steps)
            self.tokens = toks[: len(live), :, -1]
            last = np.asarray(logits)[: len(live)]
        return toks[: len(live)], last
