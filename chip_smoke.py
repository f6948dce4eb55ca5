#!/usr/bin/env python3
"""Run the Auxo main path once on a TPU and check what comes out.

One process, no fallback: the script exits non-zero, and prints no result
line, unless JAX finds a TPU.

  python3 chip_smoke.py             # one chip: phases A, B and C
  python3 chip_smoke.py --chips 4   # four chips: the cohort-sharded bank only

Phase A trains the quickstart population and the ``openimage-like``
scenario for 50 rounds through ``run_fl`` and ``run_auxo``, synchronous and
overlapped, and checks the fused step against the sequential oracle.
Phase B serves a 2,000-query stream through the ``ServingPlane`` between
rounds and again while a round is in flight. Phase C decodes granite-3-2b
(published widths, bf16, random weights) through ``CohortDecoder`` with the
Pallas kernel and with the reference attention. ``--chips 4`` runs only the
``cohort_shards=4`` bank against one device.

Each phase prints one JSON line with its wall time and compile seconds; the
last line of standard output is ``{"ok": true, "device": {...}}``.
The phase functions take their sizes, so tests/test_chip_smoke.py runs them
on the CPU at a tiny size. Importing this file touches no device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "benchmarks"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from common import SCENARIOS, default_auxo, default_fl  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.data import make_population  # noqa: E402
from repro.fl import AuxoConfig, AuxoEngine, FLConfig, run_auxo, run_fl  # noqa: E402
from repro.fl.task import MLPTask  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve import CohortDecoder, QueryStream, ServingPlane, StreamConfig  # noqa: E402
from repro.utils.compile_cache import use_persistent_cache  # noqa: E402
from repro.utils.trace import compiles  # noqa: E402

# Bank params of the fused step against the sequential oracle, and of the
# sharded bank against one device. FedYoGi moves a coordinate by at most
# about server_lr (0.05) per round, so a wrong row, weight or slot shows as
# a whole step. TPU f32 matmuls take one bf16 pass: the oracle's tensordot
# aggregation rounds its inputs to 8 bits (2^-8 relative), and YoGi's update
# has slope lr·0.1/(2·tau) = 2.5 in the aggregate, so three rounds stay far
# below a tenth of one server step.
PARAM_TOL = 5e-3
# Greedy decode: where the Pallas and reference token streams first differ,
# the reference's top-2 logit margin must be below this: a quarter of the
# logits' standard deviation (0.9 with these random weights). Long streams
# are not expected to agree: the random 40-layer bf16 model amplifies any
# rounding difference, and on a v5e the reference alone moves by up to 0.6
# logits one step after its first mixed softmax when only its matmul
# precision changes. Exact agreement is checked where it must hold, at the
# first step (see phase_decode).
LOGIT_TOL = 0.25
# Kernel vs reference on one attention call with bf16 inputs (the bf16
# tolerance of tests/test_decode_attention_kernel.py).
KERNEL_TOL = 3e-2


def report(name: str, t0: float, clock, **fields):
    line = {"phase": name, "wall_s": time.perf_counter() - t0}
    if clock is not None:
        c = compiles() - clock  # compiles since the run started
        line |= {"compiles": c.count, "compile_s": c.seconds,
                 "cache_hits": c.cache_hits}
    print(json.dumps(line | fields), flush=True)


def check(ok, what=None):
    """A failed check ends the run (also under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def check_tpu_kernel(fn, *args):
    """The compiled program of `fn(*args)` holds a Pallas TPU kernel.
    A jitted `fn` is lowered as it is, so its compile is the one it runs."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    text = jitted.lower(*args).compile().as_text()
    check("tpu_custom_call" in text, f"no Pallas kernel in {fn}")


def device_peak_bytes():
    """The device's peak bytes in use so far (None where not reported)."""
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def max_param_diff(eng_a, eng_b, leaves) -> float:
    """Largest |a - b| over the bank params of the given cohorts."""
    worst = 0.0
    for cid in leaves:
        pa = eng_a.pipeline.bank.params_of(cid)
        pb = eng_b.pipeline.bank.params_of(cid)
        for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            worst = max(worst, float(np.max(np.abs(np.asarray(a) - np.asarray(b)))))
    return worst


# ------------------------------------------------------------ populations
def quickstart(rounds=50, n_clients=600, participants=80):
    """examples/quickstart.py: 600 clients, 80 per round, 2 latent groups."""
    pop = make_population(n_clients=n_clients, n_groups=2, group_sep=0.0,
                          dirichlet=2.0, label_conflict=0.6, seed=0)
    task = MLPTask(dim=pop.dim, n_classes=pop.n_classes)
    fl = FLConfig(rounds=rounds, participants_per_round=participants,
                  eval_every=10, seed=0, use_availability=False)
    auxo = AuxoConfig(d_sketch=64, cluster_k=2, max_cohorts=2,
                      clustering_start_frac=0.05, partition_start_frac=0.1,
                      min_members=8)
    return task, pop, fl, auxo


def openimage(rounds=50, n_clients=None, participants=None):
    """benchmarks/common.py's openimage-like scenario with its defaults."""
    kw = dict(SCENARIOS["openimage-like"])
    if n_clients:
        kw["n_clients"] = n_clients
    pop = make_population(seed=1, **kw)
    task = MLPTask(dim=pop.dim, n_classes=pop.n_classes)
    fl = default_fl(rounds)
    if participants:
        fl = dataclasses.replace(fl, participants_per_round=participants)
    return task, pop, fl, default_auxo(rounds)


def four_groups(rounds=60, n_clients=600, participants=100):
    """Four latent groups with fully conflicting labels (the scenario of the
    elastic-restore tests, at twice the population): it partitions to four
    leaf cohorts within a dozen rounds."""
    pop = make_population(n_clients=n_clients, n_groups=4, group_sep=0.0,
                          dirichlet=3.0, label_conflict=1.0, seed=5)
    task = MLPTask(dim=pop.dim, n_classes=pop.n_classes)
    fl = FLConfig(rounds=rounds, participants_per_round=participants,
                  eval_every=rounds, seed=5, use_availability=False)
    auxo = AuxoConfig(d_sketch=64, cluster_k=2, max_cohorts=4,
                      clustering_start_frac=0.03, partition_start_frac=0.08,
                      partition_end_frac=0.9, min_members=6,
                      margin_threshold=0.35)
    return task, pop, fl, auxo


# ---------------------------------------------------------------- phase A
def phase_training(name, task, pop, fl, auxo, *, on_tpu, clock=None):
    """`fl.rounds` rounds of run_fl and run_auxo, synchronous and
    overlapped. Returns the overlapped Auxo engine (phase B serves from it)."""
    for overlap in (0, 1):
        t0 = time.perf_counter()
        f = dataclasses.replace(fl, round_overlap=overlap)
        base = run_fl(task, pop, f)
        eng, hist = run_auxo(task, pop, f, auxo)
        pipe = eng.pipeline
        leaves = eng.coordinator.tree.leaves()
        acc_base, acc_auxo = base[-1]["acc_mean"], hist[-1]["acc_mean"]
        report(f"A:{name}:overlap{overlap}", t0, clock, leaves=len(leaves),
               rounds=f.rounds, exec_dispatches=pipe.exec_dispatches,
               flushes=pipe.flushes, acc_baseline=acc_base, acc_auxo=acc_auxo)
        check(len(leaves) >= 2, f"no partition ran: {leaves}")
        check(pipe._exec_step._cache_size() == 1, "fused step recompiled")
        check(pipe.exec_dispatches == f.rounds, (pipe.exec_dispatches, f.rounds))
        check(acc_auxo > acc_base, (acc_auxo, acc_base))
    if on_tpu:
        # the clustering kernels the device control path runs, at the
        # widths of this population (matching cosine, feedback clustering)
        d = auxo.d_sketch
        fp = jnp.zeros((512, d), jnp.float32)
        check_tpu_kernel(kops.cosine_similarity, fp, jnp.ones((4, d), jnp.float32))
        check_tpu_kernel(
            lambda x, i, w: kops.segment_aggregate(x, i, auxo.cluster_k, weights=w),
            fp[:128], jnp.zeros(128, jnp.int32), jnp.ones(128, jnp.float32),
        )
    return eng


def phase_oracle(task, pop, fl, auxo, rounds=3, clock=None):
    """Fused batched step against execution="sequential", before any
    partition: the bank params must agree within PARAM_TOL."""
    t0 = time.perf_counter()
    engines = [
        AuxoEngine(task, pop, dataclasses.replace(fl, execution=mode), auxo)
        for mode in ("batched", "sequential")
    ]
    for r in range(rounds):
        for e in engines:
            e.step(r)
    leaves = [e.coordinator.tree.leaves() for e in engines]
    check(leaves[0] == leaves[1] == ["0"], leaves)
    diff = max_param_diff(*engines, leaves[0])
    report("A:oracle", t0, clock, rounds=rounds, max_param_diff=diff,
           tol=PARAM_TOL)
    check(diff <= PARAM_TOL, diff)


# ---------------------------------------------------------------- phase B
def phase_serving(eng, n_queries=2000, hot_frac=0.9, clock=None):
    """Serve every admitted batch between rounds, then again while a round
    is in flight; both passes read the same round-boundary snapshot."""
    t0 = time.perf_counter()
    pipe = eng.pipeline
    check(pipe.overlap == 1)
    ids = np.arange(eng.data.n_clients, dtype=np.int64)
    hot = ids[np.asarray(eng.fp_seen[ids], bool)]
    cold = np.setdiff1d(ids, hot)
    stream = QueryStream(
        StreamConfig(n_queries=n_queries, rate=50_000.0, hot_frac=hot_frac,
                     seed=7),
        hot, cold,
    )
    plane = ServingPlane(eng)
    batches = plane.batcher.admit(stream)
    idle = [plane.serve_batch(b.ids) for b in batches]
    eng.step(eng.round_cursor)
    check(pipe._inflight is not None, "round must be in flight")
    busy = [plane.serve_batch(b.ids) for b in batches]
    eng.pipeline.flush()
    preds = np.concatenate(idle)
    report("B:serving", t0, clock, queries=int(preds.size),
           batches=len(batches), infer_dispatches=plane.infer_dispatches)
    check(preds.size == n_queries)
    check(plane.infer_dispatches == 2 * len(batches))
    check(((preds >= 0) & (preds < eng.data.n_classes)).all())
    for a, b in zip(idle, busy):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------- phase C
def _first_diffs(tok_a, tok_b):
    """{lane: first step where the greedy streams differ}."""
    out = {}
    for lane in range(tok_a.shape[1]):
        d = np.flatnonzero(tok_a[0, lane] != tok_b[0, lane])
        if d.size:
            out[lane] = int(d[0])
    return out


def phase_decode(cfg, steps=160, lanes=4, page=128, *, on_tpu, clock=None,
                 seed=0):
    """Paged decode of a 1-slot bank with 1 live cohort: Pallas vs ref."""
    t0 = time.perf_counter()
    model = build_model(cfg)
    # the bank layout (leading slot axis), initialized in place: a separate
    # unstacked copy would double the peak at published widths
    bank = jax.jit(lambda k: jax.tree.map(lambda a: a[None], model.init(k)))(
        jax.random.key(seed)
    )
    bank_bytes = sum(a.nbytes for a in jax.tree.leaves(bank))
    peaks = {"bank": device_peak_bytes()}

    # the kernel alone against the reference, at this model's widths
    key = jax.random.key(seed + 1)
    q = jax.random.normal(key, (lanes, cfg.n_heads, cfg.hd), cfg.dtype)
    kv = jax.random.normal(
        jax.random.fold_in(key, 1), (2, lanes, 2 * page, cfg.n_kv_heads, cfg.hd),
        cfg.dtype,
    )
    n = jnp.asarray([1, page // 2, page + 3, 2 * page])[:lanes]
    got = kops.decode_attention(q, kv[0], kv[1], n)
    with jax.default_matmul_precision("highest"):
        want = kref.decode_attention(
            q.astype(jnp.float32), kv[0].astype(jnp.float32),
            kv[1].astype(jnp.float32), n,
        )
    kernel_err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))

    def decoder(backend):
        return CohortDecoder(model, lambda: bank, lambda: [0], lanes=lanes,
                             page_size=page, backend=backend)

    runs, first, overlapped = {}, {}, {}
    for backend in ("pallas", "ref"):
        dec = decoder(backend)
        dec.sync()
        dec.cache.ensure(steps + 1)
        c = dec.cache
        # one throwaway step at the decode's shapes compiles it outside the
        # timed loop (the bank already has the gathered rows' shape); the
        # step consumes the cache it is given, so the cache takes its output
        warm = (bank, jnp.zeros((1, lanes, 1), jnp.int32), c.k, c.v,
                jnp.asarray(c.index))
        if on_tpu and backend == "pallas":
            check_tpu_kernel(dec._step, *warm)
        logits, c.k, c.v = dec._step(*warm)
        first[backend] = np.asarray(logits, np.float32)
        del warm, logits
        t1 = time.perf_counter()
        toks, logits = dec.decode(steps)
        runs[backend] = (toks, np.asarray(logits, np.float32), dec.kv_nbytes,
                         dec.cache.pages, time.perf_counter() - t1)
        check(dec.step_compiles == 1, f"{backend} decode step recompiled")
        # every token but the last is fetched with the next step in flight
        overlapped[backend] = dec.overlapped_fetches
        check(overlapped[backend] == steps - 1, overlapped)
        peaks[backend] = device_peak_bytes()
        del dec, c
    (tok_p, lg_p, kv_nbytes, pages, t_p), (tok_r, lg_r, _, _, t_r) = (
        runs["pallas"], runs["ref"]
    )
    # at the first step one position is cached, its softmax weight is
    # exactly 1 and both paths return the cached value unchanged: the whole
    # model's logits must match bit for bit (a wrong head or block would not)
    first_step_err = float(np.abs(first["pallas"] - first["ref"]).max())
    diffs = _first_diffs(tok_p, tok_r)
    margins = {}
    for lane, step in diffs.items():
        # the reference's logits at the first differing step of this lane
        _, lg = decoder("ref").decode(step + 1)
        top2 = np.sort(np.asarray(lg, np.float32)[0, lane])[-2:]
        margins[lane] = float(top2[1] - top2[0])
    report("C:decode", t0, clock, arch=cfg.arch_id, layers=cfg.n_layers,
           d_model=cfg.d_model, dtype=jnp.dtype(cfg.dtype).name,
           bank_bytes=bank_bytes, steps=steps, lanes=lanes, pages=pages,
           kv_nbytes=kv_nbytes, overlapped_fetches=overlapped,
           peak_bytes_in_use=peaks,
           pallas_tok_s=tok_p.size / t_p, ref_tok_s=tok_r.size / t_r,
           kernel_max_err=kernel_err, first_step_logit_err=first_step_err,
           first_diff_step=diffs,
           ref_top2_margin=margins,
           last_logit_max_err=None if diffs else float(np.abs(lg_p - lg_r).max()))
    check(kernel_err <= KERNEL_TOL, kernel_err)
    check(first_step_err == 0.0, first_step_err)
    check(pages >= 2, pages)
    for lane, margin in margins.items():
        check(margin < LOGIT_TOL, (lane, diffs[lane], margin))


# ------------------------------------------------------------- four chips
def phase_sharded(task, pop, fl, auxo, shards=4, min_leaves=4, max_rounds=60,
                  clock=None):
    """cohort_shards=S against one device, same seed, until both hold at
    least `min_leaves` leaf cohorts."""
    t0 = time.perf_counter()
    single = AuxoEngine(task, pop, dataclasses.replace(fl, cohort_shards=1), auxo)
    width = single.pipeline.width
    sharded = AuxoEngine(
        task, pop,
        dataclasses.replace(fl, cohort_shards=shards, rows_per_shard=width),
        auxo,
    )
    r = 0
    while r < max_rounds and min(
        len(e.coordinator.tree.leaves()) for e in (single, sharded)
    ) < min_leaves:
        single.step(r)
        sharded.step(r)
        r += 1
    leaves = single.coordinator.tree.leaves()
    pipe = sharded.pipeline
    devs = set()
    for leaf in jax.tree.leaves(pipe.bank.params):
        devs |= {d.id for d in leaf.sharding.device_set}
    diff = max_param_diff(single, sharded, leaves)
    report("sharded", t0, clock, shards=shards, rounds=r, leaves=leaves,
           devices=len(devs), max_param_diff=diff, tol=PARAM_TOL,
           exec_dispatches=pipe.exec_dispatches)
    check(len(leaves) >= min_leaves, leaves)
    check(leaves == sharded.coordinator.tree.leaves())
    check(diff <= PARAM_TOL, diff)
    check(pipe._exec_step._cache_size() == 1, "sharded step recompiled")
    check(pipe.exec_dispatches == r)
    check(len(devs) == shards, devs)


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the cohort-sharded bank on four chips")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    cache_dir = use_persistent_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 1
    clock = compiles()
    report("setup", t0, clock, cache_dir=cache_dir, device_kind=dev.device_kind,
           count=len(devices))

    if args.chips == 4:
        phase_sharded(*four_groups(), shards=4, clock=clock)
    else:
        served = phase_training("quickstart", *quickstart(), on_tpu=True,
                                clock=clock)
        phase_training("openimage", *openimage(), on_tpu=True, clock=clock)
        phase_oracle(*quickstart(), clock=clock)
        phase_serving(served, clock=clock)
        phase_decode(get_config("granite-3-2b").replace(dtype=jnp.bfloat16),
                     on_tpu=True, clock=clock)
    report("total", t0, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
