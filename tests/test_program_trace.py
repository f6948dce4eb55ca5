"""The program's spans and compile counter (repro.utils.trace), read back
from a live `jax.profiler` trace on the CPU, and the stable names of the
programs the decode loop and the round pipeline dispatch."""
import collections
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.data import make_population
from repro.fl import AuxoConfig, AuxoEngine, FLConfig
from repro.fl.task import MLPTask
from repro.models import build_model
from repro.serve import CohortDecoder
from repro.serve.decode import gather_bank_rows, pick_tokens
from repro.utils.trace import PREFIX, compiles, span


def _host_events(trace_dir):
    """(name, start, end) of every event on the trace's host plane."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in ProfileData.from_file(path).planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events]


def _traced(tmp_path, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, _host_events(tmp_path)


def _count(events, prefix):
    """Events by name, counting one that lies inside another of its name
    once (the runtime nests a `PjitFunction(...)` event in one of its own)."""
    out = collections.Counter()
    end = {}
    for n, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        if n.startswith(prefix):
            if s > end.get(n, -1):
                out[n] += 1
            end[n] = max(end.get(n, e), e)
    return out


def test_span_names_and_nests(tmp_path):
    def run():
        with span("outer"):
            with span("inner"):
                jnp.ones(3).block_until_ready()
            with span("inner"):
                pass

    _, events = _traced(tmp_path, run)
    spans = [e for e in events if e[0].startswith(PREFIX)]
    assert _count(spans, PREFIX) == {"auxo:outer": 1, "auxo:inner": 2}
    (outer,) = [e for e in spans if e[0] == "auxo:outer"]
    for _, s, e in spans:
        assert outer[1] <= s <= e <= outer[2]


def _tiny_decoder(live=(0, 2), arch="qwen3-8b"):
    """Two layers at d 64: dense, or for granite-4.0-h-micro one Mamba2
    layer and one attention layer."""
    cfg = reduce_config(get_config(arch)).replace(d_model=64, vocab=128, n_layers=2)
    model = build_model(cfg)
    key = jax.random.key(0)
    ps = [model.init(jax.random.fold_in(key, i)) for i in range(3)]
    bank = jax.tree.map(lambda *a: jnp.stack(a), *ps)
    return CohortDecoder(model, lambda: bank, lambda: list(live), lanes=2,
                         page_size=64, backend="ref")


@pytest.mark.parametrize("steps", [1, 5])
def test_decode_emits_each_stage_span(tmp_path, steps):
    dec = _tiny_decoder()
    dec.decode(2)  # compiles outside the trace
    (toks, _), events = _traced(tmp_path, lambda: dec.decode(steps))
    assert toks.shape == (2, 2, steps)
    assert _count(events, "auxo:decode.") == {
        "auxo:decode.prepare": 1, "auxo:decode.writeback": 1,
        "auxo:decode.dispatch": steps, "auxo:decode.pick": steps,
        "auxo:decode.fetch": steps}
    # the stages run one after another, in the order of the loop: step i is
    # dispatched and picked before token i-1 is fetched, so one step is in
    # flight at each fetch but the last
    order = [n for n, _, _ in sorted(events, key=lambda e: e[1])
             if n.startswith("auxo:decode.")]
    assert order == (["auxo:decode.prepare", "auxo:decode.dispatch",
                      "auxo:decode.pick"]
                     + ["auxo:decode.dispatch", "auxo:decode.pick",
                        "auxo:decode.fetch"] * (steps - 1)
                     + ["auxo:decode.fetch", "auxo:decode.writeback"])
    # each stage dispatches one named program: the bank gather and the
    # token pick are no longer per-leaf eager ops
    calls = _count(events, "PjitFunction(")
    assert calls["PjitFunction(gather_bank_rows)"] == 1
    assert calls["PjitFunction(pick_tokens)"] == steps
    assert not {"PjitFunction(_argmax)", "PjitFunction(gather)"} & set(calls)


def test_decode_program_names():
    """The decode loop's programs have stable names; no new one holds the
    word `jit_step`, by which the benchmark finds the fleet step."""
    logits = jnp.zeros((1, 2, 8))
    index = jnp.zeros((1,), jnp.int32)
    bank = {"w": jnp.zeros((3, 4)), "b": jnp.zeros((3,))}
    for fn, args, name in ((pick_tokens, (logits, index), "jit_pick_tokens"),
                           (gather_bank_rows, (bank, jnp.asarray([2], jnp.int32)),
                            "jit_gather_bank_rows")):
        text = fn.lower(*args).as_text()
        assert f"module @{name} " in text and "jit_step" not in name
    dec = _tiny_decoder()
    dec.sync()
    dec.cache.ensure(2)
    c = dec.cache
    text = dec._fleet_step.lower(
        gather_bank_rows(dec.params_fn(), jnp.asarray(c.slots, jnp.int32)),
        jnp.zeros((c.rows, 2, 1), jnp.int32), c.k, c.v, jnp.asarray(c.index),
    ).as_text()
    assert "module @jit_step " in text


@pytest.mark.parametrize("steps", [1, 6])
@pytest.mark.parametrize("arch", ["qwen3-8b", "granite-4.0-h-micro"])
def test_decode_tokens_match_per_leaf_gather_and_eager_pick(arch, steps):
    """The named gather and pick, with one step in flight, serve exactly the
    tokens, last logits and positions of a synchronous loop of per-leaf
    eager indexing, eager argmax and a host copy of each token."""
    dec = _tiny_decoder(live=(2, 0, 1), arch=arch)
    toks, last = dec.decode(steps)
    ref = _tiny_decoder(live=(2, 0, 1), arch=arch)
    ref.sync()
    ref.cache.ensure(steps + 1)
    live = ref.cache.slots
    slots = np.asarray(live + [live[0]] * (ref.cache.rows - len(live)), np.int64)
    params = jax.tree.map(lambda a: a[slots], ref.params_fn())
    tok = np.zeros((ref.cache.rows, 2), np.int32)
    tok[: len(live)] = ref._seed_tokens()
    tok = jnp.asarray(tok[:, :, None])
    state, index = ref.cache.arrays, jnp.asarray(ref.cache.index)
    out = []
    for _ in range(steps):
        logits, *state = ref._step(params, tok, *state, index)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, :, None]
        index = index + 1
        out.append(np.asarray(tok)[:, :, 0])
    np.testing.assert_array_equal(toks, np.stack(out, -1)[: len(live)])
    np.testing.assert_array_equal(last, np.asarray(logits)[: len(live)])
    np.testing.assert_array_equal(dec.cache.index, np.asarray(index))
    for got, want in zip(dec.cache.arrays, state):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_overlapped_fetches_counts_all_tokens_but_the_last():
    dec = _tiny_decoder()
    for n, overlapped, dispatches in ((1, 0, 1), (4, 3, 5), (2, 4, 7)):
        dec.decode(n)
        assert dec.overlapped_fetches == overlapped
        assert dec.decode_dispatches == dispatches


def test_host_stays_one_step_ahead_of_the_tokens(monkeypatch):
    """Token i is fetched after step i+1 is dispatched and before step i+2
    is: at the dispatch of step j the host holds tokens 0..j-2."""
    import repro.serve.decode as decode_mod

    dec = _tiny_decoder()
    fetched, held = [], []
    step, real_span = dec._step, decode_mod.span

    def hooked(*a):
        held.append(len(fetched))
        return step(*a)

    def recording(name):
        if name == "decode.fetch":
            fetched.append(name)
        return real_span(name)

    dec._step = hooked
    monkeypatch.setattr(decode_mod, "span", recording)
    for n in (1, 5):
        fetched.clear()
        held.clear()
        dec.decode(n)
        assert held == [0] + list(range(n - 1))
        assert len(fetched) == n


def test_step_compiles_stays_one_under_a_wrapper():
    dec = _tiny_decoder()
    dec.decode(3)
    assert dec.step_compiles == 1
    seen = []
    step = dec._step

    def hooked(*a):
        seen.append(1)
        return step(*a)

    dec._step = hooked  # what a benchmark driver does to time each step
    dec.decode(4)
    dec.decode(2)
    assert len(seen) == 6 and dec.decode_dispatches == 9
    assert dec.step_compiles == 1


def test_compile_counter_counts_one_fresh_compile():
    x = jnp.arange(7.0)
    x.block_until_ready()
    fresh = jax.jit(lambda a: a * 3.0 + 1.0)
    before = compiles()
    fresh(x).block_until_ready()
    mid = compiles()
    fresh(x).block_until_ready()  # compiled already
    after = compiles()
    assert (mid - before).count == 1 and (mid - before).seconds > 0
    assert (after - mid).count == 0


def test_round_step_named_and_stages_timed(tmp_path):
    pop = make_population(n_clients=80, n_groups=2, seed=3)
    fl = FLConfig(rounds=3, participants_per_round=16, eval_every=100,
                  use_availability=False, seed=3)
    eng = AuxoEngine(MLPTask(dim=pop.dim, n_classes=pop.n_classes), pop, fl,
                     AuxoConfig(d_sketch=16, max_cohorts=2))
    eng.step(0)  # compiles outside the trace
    _, events = _traced(tmp_path, lambda: eng.step(1))
    # the fused round step has a name of its own, not jit__unknown
    assert _count(events, "PjitFunction(")["PjitFunction(fused_round_step)"] == 1
    assert eng.pipeline._exec_step._cache_size() == 1
    stage = eng.pipeline.stage_seconds
    assert all(stage[k] > 0 for k in ("plan", "pack", "dispatch", "feedback"))
