"""Cohort-LM decode through `CohortDecoder.decode`, timed.

Set-up draws the weights on the device from the seed in one jitted call,
in the program's bank layout and in bf16, with the granite multipliers
folded into them (see the configuration file), and fills the first
`start_position` cached positions of every lane with the seeded context
that `reference/granite.py` draws too. Each chunk is a new request on
every lane: it rewinds the cache index to `start_position`, gives each lane
a first token drawn from the seed, and decodes `chunk_steps` greedy tokens
on every lane, one fleet step per token. The window runs whole chunks, and
a last shorter one when a whole one no longer fits. The cache is sized so that
`PagedKVCache.ensure` never grows it.

One whole chunk of the window, drawn from the seed, is checked: the
reference runs teacher-forced over every token it served, on every lane.
`logit_gap` is the widest gap by which a served token's logit lies below
the reference's best; `logit_err` is the worst, over every lane and every
`KEEP_EVERY`-th step of the chunk, of ||program - reference|| / ||reference||
for the logits the fleet step itself returned (kept on the device in the
window, no work added; the chunk drawn is decided as the window goes, by a
priority drawn from the seed for each chunk, so at most two chunks' logits
are held). `control` reads both numbers off the reference in float8 put in
the program's place: the gap of the token it puts first, and its logits.
`FAULTS` plants faults in the fleet step.
"""
from __future__ import annotations

import contextlib
import time
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from reference import granite

KEEP_EVERY = 4  # the steps of a chunk whose logits the check compares


def program_config(c: dict):
    """The program's ModelConfig for this configuration (multipliers folded
    into the weights, so the program's norm eps scales with the stream)."""
    from repro.models.common import ModelConfig

    return ModelConfig(
        arch_id=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], tie_embeddings=c["tie_word_embeddings"],
        rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"] / c["embedding_multiplier"] ** 2,
        dtype=jnp.bfloat16,
    )


def program_bank(c: dict, key):
    """The served bank (1 slot): granite weights with the multipliers
    folded in. The residual stream runs 1/embedding_multiplier of granite's,
    which RMSNorm does not see."""
    d = granite.dims(c)
    emb = granite.embedding(c, key)
    blocks = jax.vmap(lambda l: granite.layer_weights(c, key, l))(jnp.arange(d["L"]))
    fold = lambda w, s: (w.astype(jnp.float32) * s).astype(jnp.bfloat16)  # noqa: E731
    branch = c["residual_multiplier"] / c["embedding_multiplier"]
    ones = jnp.ones((d["L"], d["D"]), jnp.bfloat16)
    attn = {"wq": fold(blocks["wq"], c["attention_multiplier"] * d["hd"] ** 0.5),
            "wk": blocks["wk"], "wv": blocks["wv"], "wo": fold(blocks["wo"], branch)}
    mlp = {"wg": blocks["wg"], "wu": blocks["wu"], "wd": fold(blocks["wd"], branch)}
    params = {
        "embed": emb,
        "backbone": {"blocks": {"attn_norm": {"scale": ones}, "attn": attn,
                                "mlp_norm": {"scale": ones}, "mlp": mlp}},
        "final_norm": {"scale": jnp.full((d["D"],), 1.0 / c["logits_scaling"],
                                         jnp.bfloat16)},
    }
    return jax.tree.map(lambda a: a[None], params)


def program_cache(c: dict, key, lanes: int, positions: int, start: int):
    """(1, L, lanes, positions, Hkv, hd) float32 keys and values: the seeded
    context in the first `start` positions, zeros after."""
    d = granite.dims(c)

    def layer(l):
        k, v = granite.context_kv(c, key, l, lanes, start)
        pad = ((0, 0), (0, positions - start), (0, 0), (0, 0))
        return jnp.pad(k, pad), jnp.pad(v, pad)

    k, v = jax.vmap(layer)(jnp.arange(d["L"]))
    return k[None], v[None]


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, span=None):
        self.c, self.t, self.seed = config, traffic, int(seed)
        self.span = span
        self.chunks: List[dict] = []  # every chunk of the window
        self._ref = None
        self._kept = None  # the running chunk's logits, every KEEP_EVERY-th step
        self._best = None  # the whole chunk checked so far

    def setup(self):
        from repro.models import build_model
        from repro.serve import CohortDecoder

        c, t = self.c, self.t
        self.cfg = program_config(c)
        model = build_model(self.cfg)
        key = jax.random.key(self.seed)
        bank = jax.jit(partial(program_bank, c))(key)
        k, v = jax.jit(partial(program_cache, c), static_argnums=(1, 2, 3))(
            key, t["lanes"], t["cache_positions"], t["start_position"])
        self.bank = bank
        dec = CohortDecoder(model, lambda: self.bank, lambda: [0], lanes=t["lanes"],
                            page_size=t["page_size"], backend="pallas")
        dec.sync()
        dec.cache.k, dec.cache.v = k, v
        del k, v
        self.dec = dec
        self.stamps: List[float] = []
        step = dec._step

        def stamped(*a):
            self.stamps.append(time.perf_counter())
            out = step(*a)
            if self._kept is not None:
                if self._j % KEEP_EVERY == 0:
                    self._kept.append(out[0])
                self._j += 1
            return out

        dec._step = stamped
        # two steps compile the step and the eager gather and argmax; the
        # step's shapes do not depend on the number of steps
        self._chunk(2, record=False)
        self.stamps.clear()

    def _chunk(self, n: int, record: bool = True):
        dec, t = self.dec, self.t
        dec.cache.index = np.full(dec.cache.rows, t["start_position"], np.int32)
        # a new request on every lane: its first token, drawn from the seed
        first = np.random.default_rng([self.seed, len(self.chunks)]).choice(
            self.cfg.vocab, (1, t["lanes"]), replace=False).astype(np.int32)
        dec.tokens = first
        self._kept, self._j = ([] if record else None), 0
        toks, _ = dec.decode(n)
        if record:
            ch = {"first": first[0], "served": np.array(toks[0]), "steps": n,
                  "priority": np.random.default_rng(
                      [self.seed, len(self.chunks), 1]).random()}
            self.chunks.append(ch)
            if n == self.t["chunk_steps"] and (
                    self._best is None or ch["priority"] > self._best["priority"]):
                if self._best is not None:
                    self._best.pop("logits")
                ch["logits"], self._best = self._kept, ch
        self._kept = None

    def window(self, seconds: float, chunks: int = 0) -> dict:
        """Whole chunks while they fit in `seconds`, then one shorter chunk
        for what is left; or exactly `chunks` whole chunks."""
        t = self.t
        if self.span is not None:
            self.dec._step = self.span.wrap("decode_step", self.dec._step)
        n, stamps = t["chunk_steps"], self.stamps
        t0 = time.perf_counter()
        while True:
            left = seconds - (time.perf_counter() - t0)
            if chunks:
                steps = n if len(self.chunks) < chunks else 0
            elif not self.chunks:
                steps = n
            else:  # seconds per step, from the last chunk
                last = stamps[-self.chunks[-1]["steps"]:]
                per_step = (time.perf_counter() - last[0]) / len(last)
                steps = min(n, int(left / per_step))
            if steps < 1:
                break
            if self.span is not None:
                with self.span("chunk"):
                    self._chunk(steps)
            else:
                self._chunk(steps)
        wall = time.perf_counter() - t0
        stamps = np.asarray(self.stamps + [t0 + wall])
        gaps = np.diff(stamps) * 1e3
        tokens = sum(ch["steps"] for ch in self.chunks) * t["lanes"]
        positions = [t["start_position"] + j for ch in self.chunks for j in range(ch["steps"])]
        return {
            "seconds": wall, "steps": len(positions), "attempted": tokens, "failed": 0,
            "positions": positions, "lanes": t["lanes"],
            "end_to_end": {
                "decode_tok_s": tokens / wall,
                "decode_itl_p95_ms": float(np.percentile(gaps, 95)),
            },
        }

    def release(self):
        """Frees the program's state; the checked chunk's logits go to the
        host as (lanes, kept steps, V)."""
        ch = self._best
        ch["logits"] = np.stack([np.asarray(x)[0] for x in ch["logits"]], axis=1)
        self.dec.cache.k = self.dec.cache.v = None
        self.dec = self.bank = None

    # -------------------------------------------------------------- check
    def checked(self) -> dict:
        """The whole chunk of the highest priority (drawn from the seed for
        each chunk): all of its lanes' served tokens."""
        return self._best

    def reference_logits(self, ch: dict, fp8: bool = False):
        """Reference logits at each served position of a chunk (lanes, n, V):
        position j is fed the token served before it (the first token at j = 0)."""
        fed = np.concatenate([ch["first"][:, None], ch["served"][:, :-1]], axis=1)
        return granite.forward(self.c, self.seed, fed, self.t["start_position"], fp8=fp8)

    def _reference(self):
        if self._ref is None:
            self._ref = self.reference_logits(self.checked())
        return self._ref

    def check(self) -> Dict[str, float]:
        ref, ch = self._reference(), self.checked()
        return {"logit_gap": granite.widest_gap(ref, ch["served"]),
                "logit_err": granite.worst_rel_err(ch["logits"], ref[:, ::KEEP_EVERY])}

    def control(self) -> Dict[str, float]:
        """The reference in float8 in the program's place: the gap of the
        token it puts first at every step, and its logits."""
        ref = self._reference()
        low = self.reference_logits(self.checked(), fp8=True)
        return {"logit_gap": granite.widest_gap(ref, jnp.argmax(low, -1)),
                "logit_err": granite.worst_rel_err(low[:, ::KEEP_EVERY],
                                                   ref[:, ::KEEP_EVERY])}


# ------------------------------------------------------------------ faults
@contextlib.contextmanager
def _broken_step(fault: str):
    """Fleet steps built inside the block carry `fault`."""
    import repro.serve.decode as decode

    make = decode.make_row_decode_step

    def broken(cfg, attend):
        step = make(cfg, attend)

        def f(params, tokens, kc, vc, index):
            logits, ks, vs = step(params, tokens, kc, vc, index)
            if fault == "state_unchanged":  # the cache is never written
                return logits, kc, vc
            if fault == "half_batch":  # the second half of the lanes copies the first
                h = logits.shape[0] // 2
                return jnp.concatenate([logits[:h], logits[:h]]), ks, vs
            if fault == "token_altered":  # lane 0 serves token 0 at every step
                return logits.at[0, 0].set(logits.max() + 1.0), ks, vs
            return logits, ks, vs
        return f

    decode.make_row_decode_step = broken
    try:
        yield
    finally:
        decode.make_row_decode_step = make


FAULTS = {name: partial(_broken_step, name)
          for name in ("state_unchanged", "half_batch", "token_altered")}
