"""Incremental per-cohort decode over the paged KV cache.

The serving-plane fast path for `TransformerTask` cohort models: all live
cohorts' decode lanes advance one token in ONE jitted dispatch — gather
each cohort's params row from the (snapshot) stacked bank, vmap a
single-row decode step over rows, greedy-pick the next token. Attention
against the paged cache runs through `kernels.ops.decode_attention` (the
Pallas flash-decode kernel; interpret mode off-TPU) with
`kernels.ref.decode_attention` as the selectable bit-check oracle —
backends must produce identical greedy token streams.

The per-row step mirrors `models.transformer.decode_step` for the dense
family, with the ring-buffer `attention_decode` swapped for a paged
append + length-masked kernel call. The cache is updated in place: the
fleet step donates it (on accelerators), the row step carries the whole
row cache through its layer loop and writes each layer's new position
with one `dynamic_update_slice`, and the kernel reads each layer's pages
where they lie in the stacked cache.
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
from repro.models.transformer import embed_tokens, lm_logits
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


def make_row_decode_step(cfg, attend: Callable):
    """One cohort row, one decode step. Vmapped over rows by the caller.

    params: one bank row; tokens (lanes, 1) int32; kc/vc (L, lanes, S,
    Hkv·hd), the row's cache in storage form; index scalar int32 (current
    position). Returns (logits (lanes, V), kc, vc) with the position
    written. The cache is carried through the layer loop (not scanned as
    xs/ys), so XLA updates it in place and never slices a layer out.
    """
    assert cfg.family == "dense", f"paged decode supports dense, got {cfg.family}"
    assert not cfg.sliding_window, "paged decode is full-attention only"

    def step(params, tokens, kc, vc, index):
        x = embed_tokens(params, cfg, tokens)  # (lanes, 1, D)
        positions = default_positions(cfg, tokens.shape[0], 1, offset=index)
        row = (1, tokens.shape[0], 1, kc.shape[-1])  # one position, every lane

        def layer(x, kc, vc, p, l):
            xa = rmsnorm(p["attn_norm"], x, cfg.norm_eps)
            q, k, v = _qkv(p["attn"], cfg, xa, positions)  # (lanes,1,H|Hkv,hd)
            at = (l, 0, index, 0)
            kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype).reshape(row), at)
            vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype).reshape(row), at)
            a = attend(q[:, 0], kc, vc, l, index + 1)  # (lanes, H, hd)
            x = x + jnp.einsum("bhk,hkd->bd", a, p["attn"]["wo"])[:, None, :]
            x = x + mlp(p["mlp"], rmsnorm(p["mlp_norm"], x, cfg.norm_eps))
            return x, kc, vc

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
        self.cache = PagedKVCache(
            n_layers=self.cfg.n_layers,
            lanes=self.lanes,
            n_kv_heads=self.cfg.n_kv_heads,
            head_dim=self.cfg.hd,
            page_size=page_size,
            dtype=jnp.float32,
        )
        # one jitted fleet step; jax retraces per (rows, seq) bucket.
        # `decode` calls `_step`, which a caller may wrap (a timer, a hook);
        # `_fleet_step` stays the jitted step itself. The cache is donated on
        # accelerators, so each step writes it in place; on the CPU donation
        # is gated off, as fl/pipeline.py gates EXEC_DONATE.
        donate = (
            {} if jax.default_backend() == "cpu"
            else {"donate_argnums": (2, 3)}  # kc, vc
        )
        self._fleet_step = jax.jit(
            jax.vmap(make_row_decode_step(self.cfg, ATTEND[backend])), **donate
        )
        self._step = self._fleet_step
        self.decode_dispatches = 0
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
        cache it is given (donated): the arrays `cache.k/.v` held before the
        call are invalid after it. Each stage runs in
        a span (`repro.utils.trace`): ``decode.prepare`` once, then per step
        ``decode.dispatch`` (the fleet step's call), ``decode.pick`` (the
        next token) and ``decode.fetch`` (the token to the host), and
        ``decode.writeback`` once.
        """
        with span("decode.prepare"):
            self.sync()
            live = self.cache.slots
            assert live, "no live cohorts to decode"
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
            k, v = self.cache.k, self.cache.v
            index = jnp.asarray(self.cache.index)
        out = []
        logits = None
        for _ in range(int(n_steps)):
            with span("decode.dispatch"):
                logits, k, v = self._step(params, tok, k, v, index)
            self.decode_dispatches += 1
            with span("decode.pick"):
                tok, index = pick_tokens(logits, index)
            with span("decode.fetch"):
                out.append(np.asarray(tok)[:, :, 0])
        with span("decode.writeback"):
            self.cache.k, self.cache.v = k, v
            self.cache.index = np.asarray(index, np.int32)
            toks = np.stack(out, axis=-1)  # (R, lanes, n_steps)
            self.tokens = toks[: len(live), :, -1]
            last = np.asarray(logits)[: len(live)]
        return toks[: len(live)], last
