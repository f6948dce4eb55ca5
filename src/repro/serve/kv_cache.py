"""Paged per-cohort KV cache for the serving plane's decode fast path.

One row of pages per LIVE cohort slot, stacked so the whole fleet decodes
in one vmapped dispatch: k/v are (R, L, lanes, S, Hkv·hd) with R the
pow2-bucketed live-cohort count, L the paged (attention) layers, `lanes`
concurrent decode streams per cohort, and S a pow2 number of
`page_size`-token pages that doubles on demand. Resident bytes are
therefore ∝ live cohorts — never ∝ N clients. This module alone decides
the decode state's layout: `PagedKVCache(cfg, lanes, page_size)` derives
every shape and the dtype from the model's configuration.

Storage form: the head axes are flattened into one minor axis of width
W = Hkv·hd. That shape's device layout is row-major and compact, so the
decode kernel's (block, W) tile of one layer is the stored bytes as they
lie. The fleet step donates k/v and returns them updated in place. A
cache handed in as (R, L, lanes, S, Hkv, hd) is put into storage form
once, by the next `sync`, and counted in `relayouts`.

Recurrent state (a Mamba2 hybrid, `mamba_attn`): beside the pages of its
attention layers the cache holds, per row and Mamba2 layer, the SSM state
`ssm` (R, Lm, lanes, N, H·P) float32 (N leading: the layout
`kernels/ssm_decode.py` updates in place) and the convolution window `conv`
(R, Lm, d_conv - 1, lanes, conv channels) float32, a ring in which the
input of position p lies in slot p % (d_conv - 1), so a step writes one
slot. Their size does not depend on the position, so `ensure` never grows
them; the fleet step donates and returns them with k/v.

Partition/merge discipline: `sync(live_slots)` reconciles rows against
the current leaf slots with the same scatter idiom `spawn_children` uses
on the bank (`new.at[dst].set(old[src])`) — rows of retained cohorts keep
their pages, recurrent state and decode positions, rows of retired parents
are freed, and fresh children start on zeroed pages and a zeroed state at
position 0 (counted in `state_resets` where there is recurrent state).
"""
from __future__ import annotations

from typing import List, Sequence

import jax.numpy as jnp
import numpy as np

from repro.models.ssm import mamba2_dims

# Every page and all recurrent state are float32: the check's limits in
# bench/limits/ were calibrated on float32 state, and a bf16 KV or state is
# a different result, with a check of its own.
STATE_DTYPE = jnp.float32


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


class PagedKVCache:
    def __init__(self, cfg, lanes: int, page_size: int = 128):
        """The decode state of model `cfg`: pages for its attention layers
        (all `n_layers` of a dense model, the `attn_layers` of a
        `mamba_attn` one) and, for a `mamba_attn` model, the recurrent
        state of its Mamba2 layers."""
        hybrid = cfg.family == "mamba_attn"
        self.L = len(cfg.attn_layers) if hybrid else cfg.n_layers  # paged layers
        self.W = cfg.n_kv_heads * cfg.hd
        self.lanes = int(lanes)
        self.page_size = int(page_size)
        self.slots: List[int] = []  # row -> cohort bank slot
        self.k = self.v = None      # (R, L, lanes, S, Hkv·hd)
        self.index = np.zeros(0, np.int32)  # per-row decode position
        self.relayouts = 0  # caches put into storage form by `sync`
        # recurrent state of the Mamba2 layers, (R,) + its shape per row
        self.state_shapes = {}
        if hybrid:
            d_inner, H, P, N = mamba2_dims(cfg)
            lm = len(cfg.ssm_layers)
            self.state_shapes = {
                "ssm": (lm, self.lanes, N, H * P),
                "conv": (lm, cfg.ssm_conv - 1, self.lanes, d_inner + 2 * N),
            }
        self.ssm = self.conv = None
        self.state_resets = 0  # rows whose recurrent state `sync` zeroed

    # ------------------------------------------------------------- shape
    @property
    def rows(self) -> int:
        return 0 if self.k is None else self.k.shape[0]

    @property
    def seq(self) -> int:
        return 0 if self.k is None else self.k.shape[3]

    @property
    def pages(self) -> int:
        return self.seq // self.page_size

    @property
    def nbytes(self) -> int:
        return 0 if self.k is None else int(self.k.nbytes + self.v.nbytes)

    @property
    def state_nbytes(self) -> int:
        """Bytes of the recurrent state (SSM state and conv windows)."""
        return sum(int(a.nbytes) for a in (self.ssm, self.conv) if a is not None)

    @property
    def arrays(self) -> tuple:
        """What the fleet step takes and returns: (k, v), then (ssm, conv)
        where the model has recurrent state."""
        return (self.k, self.v) + ((self.ssm, self.conv) if self.state_shapes else ())

    @arrays.setter
    def arrays(self, arrays):
        self.k, self.v, *state = arrays
        if state:
            self.ssm, self.conv = state

    def _zeros(self, r: int, s: int):
        return jnp.zeros((r, self.L, self.lanes, s, self.W), STATE_DTYPE)

    def _zero_state(self, r: int, name: str):
        return jnp.zeros((r,) + self.state_shapes[name], STATE_DTYPE)

    def _to_storage(self):
        """k/v handed in as (R, L, lanes, S, Hkv, hd) to storage form: one
        relayout on the device, counted."""
        if self.k is not None and self.k.ndim == 6:
            self.k, self.v = (a.reshape(a.shape[:4] + (-1,)) for a in (self.k, self.v))
            self.relayouts += 1

    # ---------------------------------------------------------- lifecycle
    def sync(self, live_slots: Sequence[int]):
        """Reconcile rows against the live cohort slots (partition/merge).

        Retained slots keep their pages + position, vanished slots free
        theirs, new slots allocate zeroed rows. No-op when the live set is
        unchanged.
        """
        self._to_storage()
        live = [int(s) for s in live_slots]
        if live == self.slots and self.k is not None:
            return
        s = self.seq or self.page_size
        r = max(1, _next_pow2(len(live)))
        new_k, new_v = self._zeros(r, s), self._zeros(r, s)
        new_index = np.zeros(r, np.int32)
        old = {slot: i for i, slot in enumerate(self.slots)}
        src = np.asarray(
            [old[slot] for slot in live if slot in old], np.int64
        )
        dst = np.asarray(
            [j for j, slot in enumerate(live) if slot in old], np.int64
        )
        if src.size:
            new_k = new_k.at[dst].set(self.k[src])
            new_v = new_v.at[dst].set(self.v[src])
            new_index[dst] = self.index[src]
        if self.state_shapes:
            state = {n: self._zero_state(r, n) for n in self.state_shapes}
            if src.size:
                state = {n: a.at[dst].set(getattr(self, n)[src])
                         for n, a in state.items()}
            self.ssm, self.conv = state["ssm"], state["conv"]
            self.state_resets += len(live) - src.size
        self.k, self.v, self.index, self.slots = new_k, new_v, new_index, live

    def ensure(self, extra: int):
        """Grow pages (doubling) so every live row fits `extra` more tokens."""
        assert self.k is not None, "sync() before ensure()"
        need = int(self.index.max(initial=0)) + int(extra)
        while self.seq < need:
            s = self.seq
            self.k = jnp.concatenate([self.k, self._zeros(self.rows, s)], axis=3)
            self.v = jnp.concatenate([self.v, self._zeros(self.rows, s)], axis=3)
