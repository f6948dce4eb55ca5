"""§⑧ serving plane: snapshot flush rule, batched admission/routing,
paged per-cohort decode (Pallas vs ref oracle), churn cache invalidation.

The flush-rule acceptance test uses a TABLE-NEUTRAL training config
(epsilon0 = epsilon_decay = 1.0 → matching is always the uniform explore
draw; affinity_loss_rate = 0 → feedback consumes no host RNG; partitions
disabled and leaves pre-forced): there the overlapped schedule's one-round
plan staleness has nothing to act on, so a round_overlap=0 and a
round_overlap=1 engine walk BIT-IDENTICAL training trajectories. Serving
the same query stream at the same round boundary — one engine idle, the
other with the next round in flight — must then return bit-identical
answers, which is exactly the serve_params snapshot contract: serving
never reads the half-applied live bank.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.core.clustering import OnlineClustering
from repro.core.coordinator import CohortStats, PartitionEvent
from repro.data import make_population
from repro.fl import AuxoConfig, AuxoEngine, FLConfig
from repro.fl.pipeline import EXEC_DONATE
from repro.fl.task import MLPTask
from repro.models import build_model
from repro.scale.store import DictProbeCache
from repro.serve import (
    AdmissionBatcher,
    CohortDecoder,
    PagedKVCache,
    QueryStream,
    ServingPlane,
    StreamConfig,
)


def _force_leaves(eng: AuxoEngine, n_leaves: int):
    """Pre-partition the tree to n_leaves (benchmarks/round_latency.py)."""
    co = eng.coordinator
    while len(co.tree.leaves()) < n_leaves:
        leaf = co.tree.leaves()[0]
        children = co.tree.partition(leaf, co.cluster_k)
        for ch in children:
            co.clusterers[ch] = OnlineClustering(
                co.cluster_k, co.d_sketch, seed=co.seed + hash(ch) % 10_000
            )
            co.stats[ch] = CohortStats()
        event = PartitionEvent(
            parent=leaf, children=children, round_idx=0,
            cluster_to_child={i: ch for i, ch in enumerate(children)},
        )
        eng.pipeline.bank.spawn_children(event.parent, event.children)
        eng.pipeline.table.seed_children(
            eng.pipeline.bank.slot_of[event.parent],
            [eng.pipeline.bank.slot_of[ch] for ch in event.children],
        )
        co.partitions.append(event)


def _neutral_scenario(seed=7, rounds=12):
    pop = make_population(
        n_clients=200, n_groups=4, group_sep=0.0, dirichlet=3.0,
        label_conflict=1.0, seed=seed,
    )
    task = MLPTask(dim=pop.dim, n_classes=pop.n_classes)
    fl = FLConfig(
        rounds=rounds, participants_per_round=40, eval_every=10_000,
        use_availability=False, seed=seed,
    )
    auxo = AuxoConfig(
        d_sketch=64, cluster_k=2, max_cohorts=3, clustering_start_frac=0.03,
        partition_start_frac=2.0,  # no organic partitions in the window
        epsilon0=1.0, epsilon_decay=1.0,  # matching = pure explore draw
        reward_stick=-1e9,  # assisted to_root re-descent never fires
        neg_streak_explore=10**9,  # no plan-time forced-explore mutation
        min_members=6, margin_threshold=0.35,
    )  # FLConfig.affinity_loss_rate stays at its 0.0 default. Together
    # these make stage-① placement independent of the (one-round-stale
    # under overlap) affinity table, so the two schedules' trajectories
    # coincide bit-for-bit — see module docstring.
    return task, pop, fl, auxo


def _trained_scenario(seed=5, rounds=20):
    """The round-overlap scenario: organic partitions + mixed hot/cold."""
    pop = make_population(
        n_clients=300, n_groups=4, group_sep=0.0, dirichlet=3.0,
        label_conflict=1.0, seed=seed,
    )
    task = MLPTask(dim=pop.dim, n_classes=pop.n_classes)
    fl = FLConfig(
        rounds=rounds, participants_per_round=60, eval_every=10_000,
        use_availability=False, seed=seed,
    )
    auxo = AuxoConfig(
        d_sketch=64, cluster_k=2, max_cohorts=3, clustering_start_frac=0.03,
        partition_start_frac=0.08, partition_end_frac=0.9, min_members=6,
        margin_threshold=0.35,
    )
    return task, pop, fl, auxo


def _pools(eng, n):
    ids = np.arange(n, dtype=np.int64)
    hot = ids[np.asarray(eng.fp_seen[ids], bool)]
    cold = np.setdiff1d(ids, hot)
    return hot, cold


# ---------------------------------------------------------------- flush rule
def test_serving_bit_identical_idle_vs_training_in_flight():
    """Acceptance: round_overlap=0 (idle) vs =1 (round in flight) serve
    bit-identically at the same round boundary."""
    task, pop, fl, auxo = _neutral_scenario()
    T = fl.rounds

    eng_idle = AuxoEngine(task, pop, fl, auxo)
    eng_idle.pipeline.host_control = True  # same control math as overlap
    eng_ov = AuxoEngine(task, pop, dataclasses.replace(fl, round_overlap=1), auxo)
    for e in (eng_idle, eng_ov):
        _force_leaves(e, 3)
    for r in range(T):
        eng_idle.step(r)  # idle engine: rounds 0..T-1 fully applied
    for r in range(T + 1):
        eng_ov.step(r)  # overlapped: 0..T-1 applied, round T IN FLIGHT
    assert eng_ov.pipeline._inflight is not None
    assert len(eng_idle.coordinator.identity) >= 2  # matching is live

    # identical trajectories (the table-neutral config) ...
    np.testing.assert_array_equal(
        np.asarray(eng_idle.fp_seen[np.arange(pop.n_clients)]),
        np.asarray(eng_ov.fp_seen[np.arange(pop.n_clients)]),
    )
    # ... and identical serving snapshots at the boundary — even though
    # eng_ov's LIVE bank.params already hold round T's unretired futures
    for a, b in zip(
        jax.tree.leaves(eng_idle.pipeline.serve_params),
        jax.tree.leaves(eng_ov.pipeline.serve_params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    hot, cold = _pools(eng_idle, pop.n_clients)
    stream = QueryStream(
        StreamConfig(n_queries=400, hot_frac=0.7, seed=3), hot, cold
    )
    pa, batches_a = ServingPlane(eng_idle, max_batch=64).serve_stream(stream)
    pb, batches_b = ServingPlane(eng_ov, max_batch=64).serve_stream(stream)
    assert len(batches_a) == len(batches_b)
    np.testing.assert_array_equal(pa, pb)

    # draining the in-flight round moves the snapshot forward: round T's
    # feedback lands and the snapshot tracks the new boundary
    eng_ov.pipeline.flush()
    for a, b in zip(
        jax.tree.leaves(eng_ov.pipeline.serve_params),
        jax.tree.leaves(eng_ov.pipeline.bank.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_snapshot_follows_partition_flush():
    """After a partition-triggered pipeline flush the snapshot must expose
    the POST-partition bank (child slots live), never the stale pre-
    partition one."""
    task, pop, fl, auxo = _trained_scenario()
    eng = AuxoEngine(task, pop, dataclasses.replace(fl, round_overlap=1), auxo)
    flushed = 0
    for r in range(fl.rounds):
        eng.step(r)
        if eng.pipeline.flushes > flushed:
            flushed = eng.pipeline.flushes
            # drained: snapshot == live bank (both at the new boundary)
            for a, b in zip(
                jax.tree.leaves(eng.pipeline.serve_params),
                jax.tree.leaves(eng.pipeline.bank.params),
            ):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # serving through the plane never crashes mid-schedule and routes
        # every query to a live slot
        if r % 5 == 4:
            plane = ServingPlane(eng, max_batch=32)
            ids = np.arange(0, pop.n_clients, 17, dtype=np.int64)
            slots = plane.route_slots(ids)
            live = {eng.pipeline.bank.slot_of[l]
                    for l in eng.coordinator.tree.leaves()}
            live.add(eng.pipeline.bank.slot_of["0"])  # generalist fallback
            assert set(slots.tolist()) <= live
    assert flushed >= 1, "scenario must partition mid-flight"


@pytest.mark.parametrize("overlap", [0, 1])
def test_published_snapshot_is_never_donated(overlap):
    """No leaf of a published `serve_params` snapshot is ever passed in an
    argument position the fused step donates (`EXEC_DONATE`). On an
    accelerator such a leaf would be deleted under the serving plane — a
    snapshot held across a synchronous step, or boundary r-1 served while
    round r is in flight. Donation is gated off on the CPU, so the check
    reads the donated arguments of every dispatch instead."""
    task, pop, fl, auxo = _trained_scenario()
    eng = AuxoEngine(task, pop, dataclasses.replace(fl, round_overlap=overlap),
                     auxo)
    pipe = eng.pipeline
    step = pipe._exec_step
    donated = []

    def spy(*args):
        for i in EXEC_DONATE:
            donated.extend(jax.tree.leaves(args[i]))
        return step(*args)

    pipe._exec_step = spy
    published = list(jax.tree.leaves(pipe.serve_params))
    for r in range(fl.rounds):
        eng.step(r)
        published.extend(jax.tree.leaves(pipe.serve_params))
    pipe.flush()
    published.extend(jax.tree.leaves(pipe.serve_params))
    assert donated and pipe.exec_dispatches == fl.rounds
    assert len(eng.coordinator.tree.leaves()) >= 2  # spawns ran too
    donated_ids = {id(a) for a in donated}  # both lists keep every leaf alive
    assert not [a for a in published if id(a) in donated_ids]


# ------------------------------------------------------- admission/batching
def test_admission_batcher_size_and_deadline():
    stream = QueryStream(
        StreamConfig(n_queries=1000, rate=10_000.0, hot_frac=0.5, seed=2),
        np.arange(50), np.arange(50, 100),
    )
    batches = AdmissionBatcher(max_batch=64, max_wait=2e-3).admit(stream)
    ids = np.concatenate([b.ids for b in batches])
    assert ids.size == 1000  # every query admitted exactly once
    np.testing.assert_array_equal(ids, stream.ids)
    for b in batches:
        assert 1 <= b.ids.size <= 64
        # deadline rule: co-admitted arrivals within max_wait of the first
        assert b.arrivals[-1] - b.arrivals[0] <= 2e-3 + 1e-12


def test_one_dispatch_per_admitted_batch():
    task, pop, fl, auxo = _trained_scenario()
    eng = AuxoEngine(task, pop, dataclasses.replace(fl, round_overlap=1), auxo)
    for r in range(fl.rounds):
        eng.step(r)
    eng.pipeline.flush()
    plane = ServingPlane(eng, max_batch=64)
    hot, cold = _pools(eng, pop.n_clients)
    stream = QueryStream(
        StreamConfig(n_queries=600, hot_frac=0.8, seed=4), hot, cold
    )
    d0 = eng.probe_train_dispatches
    preds, batches = plane.serve_stream(stream)
    assert preds.size == 600
    # O(1) device dispatches per admitted batch, however many cohorts it
    # mixes: one fused inference + at most one probe batch
    assert plane.infer_dispatches == len(batches)
    assert eng.probe_train_dispatches - d0 <= len(batches)
    # replaying the same stream is all cache hits: zero new probe batches
    d1 = eng.probe_train_dispatches
    plane.serve_stream(stream)
    assert eng.probe_train_dispatches == d1


# --------------------------------------------------- churn cache (satellite)
def test_probe_cache_dropped_on_churn():
    """Regression: a departed client's cached probe fingerprint must not
    survive to route its re-arrival (stale identity)."""
    task, pop, fl, auxo = _trained_scenario(rounds=4)
    eng = AuxoEngine(
        task, pop, dataclasses.replace(fl, population_store=True), auxo
    )
    for r in range(4):
        eng.step(r)
    eng.pipeline.flush()
    c = np.array([7], np.int64)
    eng._probe_fingerprints(c)
    n1 = eng.probe_train_dispatches
    eng._probe_fingerprints(c)
    assert eng.probe_train_dispatches == n1  # cache hit
    eng.apply_churn(departures=[7])
    eng.apply_churn(arrivals=[7])
    eng._probe_fingerprints(c)
    assert eng.probe_train_dispatches == n1 + 1  # re-probed cold


def test_dict_probe_cache_drop():
    dc = DictProbeCache()
    dc.put(np.array([1, 2], np.int64), np.ones((2, 4), np.float32))
    dc.drop(np.array([1, 5], np.int64))  # 5 absent: no-op
    assert 1 not in dc and 2 in dc


# ------------------------------------------------ match_many edge (satellite)
def test_match_many_empty_batch():
    task, pop, fl, auxo = _trained_scenario(rounds=2)
    eng = AuxoEngine(task, pop, fl, auxo)
    best, margin, leaves = eng.coordinator.match_many(
        np.zeros((0, auxo.d_sketch), np.float32)
    )
    assert best.shape == (0,) and margin.shape == (0,)
    assert eng.serving_cohorts(np.zeros(0, np.int64)) == []
    plane = ServingPlane(eng)
    assert plane.route_slots(np.zeros(0, np.int64)).shape == (0,)
    assert plane.serve_batch(np.zeros(0, np.int64)).shape == (0,)


def test_match_many_all_never_trained():
    # fresh engine: nobody trained, no identities — everything routes to
    # the root generalist without a single probe dispatch
    task, pop, fl, auxo = _trained_scenario()
    eng = AuxoEngine(task, pop, fl, auxo)
    ids = np.arange(10, dtype=np.int64)
    assert not np.asarray(eng.fp_seen[ids], bool).any()
    assert eng.serving_cohorts(ids) == ["0"] * 10
    plane = ServingPlane(eng)
    slots = plane.route_slots(ids)
    np.testing.assert_array_equal(
        slots, np.full(10, eng.pipeline.bank.slot_of["0"])
    )
    assert eng.probe_train_dispatches == 0
    # trained engine, batch of ONLY never-trained ids: all probe in one
    # dispatch and land on live leaves
    for r in range(20):
        eng.step(r)
    _, cold = _pools(eng, pop.n_clients)
    if cold.size and len(eng.coordinator.identity) >= 2:
        d0 = eng.probe_train_dispatches
        slots = plane.route_slots(cold)
        assert eng.probe_train_dispatches == d0 + 1
        assert slots.shape == cold.shape


def test_match_many_immediately_after_partition():
    # the probe cache keys on the partition count: a batch issued right
    # after a partition must recompute against the new tree
    task, pop, fl, auxo = _trained_scenario()
    eng = AuxoEngine(task, pop, fl, auxo)
    for r in range(fl.rounds):
        eng.step(r)
    _, cold = _pools(eng, pop.n_clients)
    if not (cold.size and len(eng.coordinator.identity) >= 2):
        pytest.skip("scenario produced no cold clients / identities")
    plane = ServingPlane(eng)
    plane.route_slots(cold[:8])
    d0 = eng.probe_train_dispatches
    plane.route_slots(cold[:8])
    assert eng.probe_train_dispatches == d0  # cached
    eng.coordinator.partitions.append(eng.coordinator.partitions[0])
    try:
        plane.route_slots(cold[:8])
        assert eng.probe_train_dispatches == d0 + 1  # invalidated
    finally:
        eng.coordinator.partitions.pop()


# ------------------------------------------------------- paged Pallas decode
def _tiny_lm():
    cfg = reduce_config(get_config("qwen3-8b")).replace(
        d_model=64, vocab=128, n_layers=2
    )
    return build_model(cfg)


def _fake_bank(model, n_slots=4, seed=0):
    key = jax.random.key(seed)
    ps = [model.init(jax.random.fold_in(key, i)) for i in range(n_slots)]
    return jax.tree.map(lambda *a: jnp.stack(a), *ps)


def test_paged_decode_pallas_matches_ref_oracle():
    model = _tiny_lm()
    bank = _fake_bank(model)
    live = [0, 2, 3]
    mk = lambda b: CohortDecoder(  # noqa: E731
        model, lambda: bank, lambda: list(live), lanes=2, page_size=64,
        backend=b,
    )
    dec_p, dec_r = mk("pallas"), mk("ref")
    tp, lp = dec_p.decode(12)
    tr, lr = dec_r.decode(12)
    # the serving contract: greedy token streams are identical; raw logits
    # agree to fp32 accumulation-order noise
    np.testing.assert_array_equal(tp, tr)
    assert float(np.abs(lp - lr).max()) < 1e-4
    assert tp.shape == (3, 2, 12)
    # one fleet dispatch per decoded position
    assert dec_p.decode_dispatches == 12


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_paged_decode_adopts_a_cache_handed_in_head_split_form(backend):
    """A cache handed in as (R, L, lanes, S, Hkv, hd), as a caller that
    fills it itself does, serves what the storage form serves; it is put
    into storage form once and stays in it."""
    model = _tiny_lm()
    cfg = model.cfg
    bank = _fake_bank(model)
    live = [0, 2]
    mk = lambda: CohortDecoder(  # noqa: E731
        model, lambda: bank, lambda: list(live), lanes=2, page_size=64,
        backend=backend,
    )
    split = (2, cfg.n_layers, 2, 64, cfg.n_kv_heads, cfg.hd)
    key = jax.random.key(7)
    kv = [jax.random.normal(jax.random.fold_in(key, i), split) for i in (0, 1)]
    runs = []
    for handed in (kv, [a.reshape(split[:4] + (-1,)) for a in kv]):
        dec = mk()
        dec.sync()
        dec.cache.k, dec.cache.v = handed
        dec.cache.index = np.asarray([40, 9], np.int32)  # a seeded context
        first = dec.decode(5)
        assert dec.cache.k.shape == split[:4] + (cfg.n_kv_heads * cfg.hd,)
        runs.append((first, dec.decode(4), dec.cache.relayouts))
    (a1, a2, relayouts_split), (b1, b2, relayouts_storage) = runs
    for got, want in ((a1, b1), (a2, b2)):
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
    assert relayouts_split == 1 and relayouts_storage == 0


def _tiny_hybrid():
    """granite-4.0-h's pattern, small: two runs of two Mamba2 layers around
    one attention layer, so that a scanned run has more than one layer."""
    cfg = reduce_config(get_config("granite-4.0-h-micro")).replace(
        d_model=64, vocab=128, n_layers=5, attn_layers=(2,)
    )
    return build_model(cfg)


@pytest.mark.parametrize("make", [_tiny_lm, _tiny_hybrid], ids=["dense", "mamba_attn"])
def test_paged_decode_unrolled_layers_match_scanned(make):
    model = make()
    bank = _fake_bank(model)
    unrolled = build_model(model.cfg.replace(unroll=True))
    out = [
        CohortDecoder(m, lambda: bank, lambda: [1, 3], lanes=2, page_size=64,
                      backend="pallas").decode(6)
        for m in (model, unrolled)
    ]
    np.testing.assert_array_equal(out[0][0], out[1][0])
    np.testing.assert_allclose(out[0][1], out[1][1], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch, paged, state", [
    ("granite-3-2b", 40, {}),
    ("granite-4.0-h-micro", 4, {"ssm": (36, 1, 128, 4096), "conv": (36, 3, 1, 4352)}),
], ids=["dense", "mamba_attn"])
def test_paged_kv_cache_derives_its_layout_from_the_config(arch, paged, state):
    """Pages for every layer of a dense model and for the attention layers
    of a Mamba2 hybrid, Hkv·hd wide; the hybrid's SSM state (Lm, lanes, N,
    H·P) and conv windows (Lm, d_conv - 1, lanes, d_inner + 2N); all f32."""
    cache = PagedKVCache(get_config(arch), lanes=1, page_size=8)
    cache.sync([5])
    assert cache.k.shape == cache.v.shape == (1, paged, 1, 8, 8 * 64)
    assert {n: getattr(cache, n).shape[1:] for n in state} == state
    assert len(cache.arrays) == 2 + len(state)
    assert all(a.dtype == jnp.float32 for a in cache.arrays)


def test_paged_kv_partition_scatter_and_cohort_scaling():
    model = _tiny_lm()
    bank = _fake_bank(model, n_slots=6)
    live = [0, 1]
    dec = CohortDecoder(
        model, lambda: bank, lambda: list(live), lanes=2, page_size=64,
        backend="ref",
    )
    dec.decode(8)
    bytes2 = dec.kv_nbytes
    idx_before = {s: int(dec.cache.index[i]) for i, s in enumerate(dec.cache.slots)}
    # "partition": slot 0 splits into 4, 5; slot 1 survives
    live = [1, 4, 5]
    dec.decode(4)
    # survivor kept its pages and position; children started cold
    row1 = dec.cache.slots.index(1)
    assert int(dec.cache.index[row1]) == idx_before[1] + 4
    for s in (4, 5):
        assert int(dec.cache.index[dec.cache.slots.index(s)]) == 4
    assert 0 not in dec.cache.slots  # parent's pages freed
    # resident KV bytes scale with LIVE COHORTS (pow2 rows), nothing else
    live = [0, 1, 2, 3]
    dec.sync()
    bytes4 = dec.kv_nbytes
    assert bytes4 == 2 * bytes2
    # page growth doubles the page count, not the row count
    rows, pages = dec.cache.rows, dec.cache.pages
    dec.cache.ensure(dec.cache.seq + 1)
    assert dec.cache.rows == rows and dec.cache.pages == 2 * pages


def test_cohort_decoder_from_engine_wiring():
    model = _tiny_lm()
    bank = _fake_bank(model)

    class _Tree:
        def leaves(self):
            return ["0.0", "0.1"]

    class _NS:
        pass

    eng = _NS()
    eng.task = _NS()
    eng.task.model = model
    eng.pipeline = _NS()
    eng.pipeline.serve_params = bank
    eng.pipeline.bank = _NS()
    eng.pipeline.bank.slot_of = {"0": 0, "0.0": 1, "0.1": 2}
    eng.coordinator = _NS()
    eng.coordinator.tree = _Tree()

    dec = CohortDecoder.from_engine(eng, lanes=2, page_size=64, backend="ref")
    toks, _ = dec.decode(3)
    assert toks.shape == (2, 2, 3)
    assert dec.cache.slots == [1, 2]
