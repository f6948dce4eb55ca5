"""Mamba2 / NoPE-GQA hybrid decode through `CohortDecoder.decode`, timed.

Set-up draws the weights on the device from the seed in one jitted call,
in the program's bank layout and in bf16, with the granite multipliers
folded into them (see the configuration file), and fills the first
`start_position` cached positions of every lane of the attention layers
with the seeded context that `reference/granite_hybrid.py` draws too (once).
Each chunk is a new request on every lane: it rewinds the cache index to
`start_position`, gives each lane a first token drawn from the seed, writes
the request's recurrent state (SSM state and convolution window of every
Mamba2 layer, drawn from the seed and the chunk's number) into the cache's
state buffers in place (donated, not allocated beside them), and decodes
`chunk_steps` greedy tokens on every lane, one fleet step per token. The
window runs whole chunks, and a last shorter one when a whole one no longer
fits. The cache is sized so that `PagedKVCache.ensure` never grows it.

One whole chunk of the window, drawn from the seed, is checked: the
reference runs teacher-forced over every token it served, on every lane,
from the same context and recurrent state. `logit_gap` is the widest gap by
which a served token's logit lies below the reference's best, over every
lane; `logit_err` is the worst ||program - reference|| / ||reference||,
over every lane and every `KEEP_EVERY`-th step of the chunk, of the logits
the fleet step itself returned (kept on the device in the window, no work
added: at most two chunks' worth, 0.1 GB a chunk). `control` reads both
numbers off the reference in float8 put in the program's place. `FAULTS`
plants faults in the fleet step.
"""
from __future__ import annotations

import contextlib
import time
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from reference import granite_hybrid as hybrid

# the steps of a chunk whose logits the check compares: 32 lanes of a
# 100,352-token vocabulary in f32 are 12.8 MB a step
KEEP_EVERY = 32


def program_config(c: dict, dtype=jnp.bfloat16):
    """The program's ModelConfig for this configuration (multipliers folded
    into the weights, so the residual norms' eps scales with the stream)."""
    from repro.models.common import ModelConfig

    assert c["position_embedding_type"] == "nope" and c["mamba_n_groups"] == 1
    d = hybrid.dims(c)
    return ModelConfig(
        arch_id=c["name"], family="mamba_attn", n_layers=d["L"], d_model=d["D"],
        n_heads=d["H"], n_kv_heads=d["K"], d_ff=d["F"], vocab=d["V"],
        tie_embeddings=c["tie_word_embeddings"], rope=False,
        attn_layers=tuple(hybrid.kinds(c)[1]), ssm_state=d["N"], ssm_heads=d["Hm"],
        ssm_expand=c["mamba_expand"], ssm_conv=d["W"],
        ssm_conv_bias=c["mamba_conv_bias"],
        norm_eps=c["rms_norm_eps"] / c["embedding_multiplier"] ** 2,
        ssm_norm_eps=c["rms_norm_eps"], dtype=dtype,
    )


def program_bank(c: dict, key, dtype=jnp.bfloat16):
    """The served bank (1 slot): the reference's weights with the
    multipliers folded in. The residual stream runs 1/embedding_multiplier
    of granite's, which the residual RMSNorms do not see."""
    d = hybrid.dims(c)
    mambas, attns = hybrid.kinds(c)
    fold = lambda w, s=1.0: (w.astype(jnp.float32) * s).astype(dtype)  # noqa: E731
    branch = c["residual_multiplier"] / c["embedding_multiplier"]
    ones = lambda n, w: jnp.ones((n, w), dtype)  # noqa: E731

    def mlp(w):
        return {"wg": fold(w["wg"]), "wu": fold(w["wu"]), "wd": fold(w["wd"], branch)}

    m = jax.vmap(lambda i: hybrid.mamba_weights(c, key, i))(jnp.arange(len(mambas)))
    mixer = {"norm": {"scale": ones(len(mambas), d["D"])},
             "w_in": fold(m["w_in"]), "conv_w": fold(m["conv_w"]),
             "conv_b": fold(m["conv_b"]), "dt_bias": m["dt_bias"],
             "A_log": m["A_log"], "D": m["D"],
             "gated_norm": {"scale": ones(len(mambas), d["Di"])},
             "w_out": fold(m["w_out"], branch)}
    backbone = {"ssm_blocks": {"mixer": mixer, "mlp_norm": {"scale": ones(len(mambas), d["D"])},
                               "mlp": mlp(m)}}
    if attns:
        a = jax.vmap(lambda i: hybrid.attention_weights(c, key, i))(jnp.arange(len(attns)))
        attn = {"wq": fold(a["wq"], c["attention_multiplier"] * d["hd"] ** 0.5),
                "wk": fold(a["wk"]), "wv": fold(a["wv"]), "wo": fold(a["wo"], branch)}
        backbone["attn_blocks"] = {"attn_norm": {"scale": ones(len(attns), d["D"])},
                                   "attn": attn,
                                   "mlp_norm": {"scale": ones(len(attns), d["D"])},
                                   "mlp": mlp(a)}
    params = {
        "embed": fold(hybrid.embedding(c, key)),
        "backbone": backbone,
        "final_norm": {"scale": jnp.full((d["D"],), 1.0 / c["logits_scaling"], dtype)},
    }
    return jax.tree.map(lambda x: x[None], params)


def program_kv(c: dict, key, lanes: int, positions: int, start: int):
    """(1, La, lanes, positions, Hkv, hd) float32 keys and values of the
    attention layers: the seeded context in the first `start` positions."""
    def layer(i):
        k, v = hybrid.context_kv(c, key, i, lanes, start)
        pad = ((0, 0), (0, positions - start), (0, 0), (0, 0))
        return jnp.pad(k, pad), jnp.pad(v, pad)

    k, v = jax.vmap(layer)(jnp.arange(len(hybrid.kinds(c)[1])))
    return k[None], v[None]


def write_state(c: dict, ssm, conv, key, request, start: int):
    """Request `request`'s recurrent state written into the cache's state
    buffers (ssm (1, Lm, lanes, N, H·P), conv (1, Lm, d_conv - 1, lanes, C),
    a ring holding position p in slot p % (d_conv - 1)), one layer at a
    time, where they lie."""
    lanes, ring = ssm.shape[2], conv.shape[2]
    slots = [(s - start) % ring for s in range(ring)]  # window index of each slot
    for i in range(ssm.shape[1]):
        s, w = hybrid.context_state(c, key, request, i, lanes)
        s = s.transpose(0, 3, 1, 2).reshape(ssm.shape[2:])  # (lanes, N, H·P)
        ssm = ssm.at[0, i].set(s)
        conv = conv.at[0, i].set(w[:, slots].transpose(1, 0, 2))
    return ssm, conv


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, span=None):
        self.c, self.t, self.seed = config, traffic, int(seed)
        self.span = span
        self.chunks: List[dict] = []  # every chunk of the window
        self._ref = None
        self._kept = None  # the running chunk's logits, every KEEP_EVERY-th step
        self._best = None  # the whole chunk checked so far

    def setup(self):
        c, t = self.c, self.t
        self.cfg = program_config(c)  # refuses a program without the hybrid path
        from repro.models import build_model
        from repro.serve import CohortDecoder

        model = build_model(self.cfg)
        self.key = jax.random.key(self.seed)
        self.bank = jax.jit(partial(program_bank, c))(self.key)
        dec = CohortDecoder(model, lambda: self.bank, lambda: [0], lanes=t["lanes"],
                            page_size=t["page_size"], backend="pallas")
        dec.sync()
        if self.cfg.attn_layers:
            dec.cache.k, dec.cache.v = jax.jit(partial(program_kv, c), static_argnums=(1, 2, 3))(
                self.key, t["lanes"], t["cache_positions"], t["start_position"])
        self.dec = dec
        donate = {} if jax.default_backend() == "cpu" else {"donate_argnums": (0, 1)}
        self._write = jax.jit(partial(write_state, c), static_argnums=(4,), **donate)
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
        # two steps compile the step and the state's writer; the step's
        # shapes do not depend on the number of steps
        self._chunk(2, record=False)
        self.stamps.clear()

    def _chunk(self, n: int, record: bool = True):
        dec, t = self.dec, self.t
        request = len(self.chunks)
        dec.cache.index = np.full(dec.cache.rows, t["start_position"], np.int32)
        dec.cache.ssm, dec.cache.conv = self._write(
            dec.cache.ssm, dec.cache.conv, self.key, request, t["start_position"])
        # a new request on every lane: its first token, drawn from the seed
        first = np.random.default_rng([self.seed, request]).choice(
            self.cfg.vocab, (1, t["lanes"]), replace=False).astype(np.int32)
        dec.tokens = first
        self._kept, self._j = ([] if record else None), 0
        toks, _ = dec.decode(n)
        if record:
            ch = {"first": first[0], "served": np.array(toks[0]), "steps": n,
                  "request": request,
                  "priority": np.random.default_rng([self.seed, request, 1]).random()}
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
        self.dec.cache.arrays = (None,) * len(self.dec.cache.arrays)
        self.dec = self.bank = None

    # -------------------------------------------------------------- check
    def checked(self) -> dict:
        """The whole chunk of the highest priority (drawn from the seed for
        each chunk): all of its lanes' served tokens."""
        return self._best

    def reference_logits(self, ch: dict, fp8: bool = False):
        """Reference logits at each served position of a chunk (lanes, n, V):
        position j is fed the token served before it (the first token at
        j = 0), after the chunk's request's recurrent state."""
        fed = np.concatenate([ch["first"][:, None], ch["served"][:, :-1]], axis=1)
        return hybrid.forward(self.c, self.seed, fed, self.t["start_position"],
                              ch["request"], fp8=fp8)

    def _reference(self):
        if self._ref is None:
            self._ref = self.reference_logits(self.checked())
        return self._ref

    def check(self) -> Dict[str, float]:
        ref, ch = self._reference(), self.checked()
        return {"logit_gap": hybrid.widest_gap(ref, ch["served"]),
                "logit_err": hybrid.worst_rel_err(ch["logits"], ref[:, ::KEEP_EVERY])}

    def control(self) -> Dict[str, float]:
        """The reference in float8 in the program's place: the gap of the
        token it puts first at every step, and its logits."""
        ref = self._reference()
        low = self.reference_logits(self.checked(), fp8=True)
        return {"logit_gap": hybrid.widest_gap(ref, jnp.argmax(low, -1)),
                "logit_err": hybrid.worst_rel_err(low[:, ::KEEP_EVERY],
                                                   ref[:, ::KEEP_EVERY])}


# ------------------------------------------------------------------ faults
@contextlib.contextmanager
def _broken_step(fault: str):
    """Fleet steps built inside the block carry `fault`."""
    import repro.serve.decode as decode

    make = decode.make_hybrid_row_step

    def broken(cfg, attend, update):
        step = make(cfg, attend, update)

        def f(params, tokens, kc, vc, ssm, conv, index):
            logits, *state = step(params, tokens, kc, vc, ssm, conv, index)
            if fault == "state_unchanged":  # neither the pages nor the state written
                return logits, kc, vc, ssm, conv
            if fault == "half_batch":  # the second half of the lanes copies the first
                h = logits.shape[0] // 2
                return (jnp.concatenate([logits[:h], logits[:h]]), *state)
            if fault == "token_altered":  # lane 0 serves token 0 at every step
                return (logits.at[0, 0].set(logits.max() + 1.0), *state)
            return (logits, *state)
        return f

    decode.make_hybrid_row_step = broken
    try:
        yield
    finally:
        decode.make_hybrid_row_step = make


FAULTS = {name: partial(_broken_step, name)
          for name in ("state_unchanged", "half_batch", "token_altered")}
