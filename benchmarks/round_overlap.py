"""Round-overlap benchmark: synchronous vs depth-2 pipelined rounds.

PR 1/2 collapsed device work to ONE fused dispatch per round and sharded it
over a cohort mesh, leaving the host stages (① matching + data packing,
③ feedback/clustering) serialized between dispatches — the device idles
while the host plans, and the host idles while the device trains.
``FLConfig.round_overlap = 1`` (ARCHITECTURE.md §⑤) overlaps them: while
the device executes round r, the host retires round r-1's feedback and
plans/packs round r+1 against one-round-stale tables.

This benchmark measures steady-state wall-clock per global round for both
modes at C = 8 and C = 32 leaf cohorts on an 8-device (fake host) cohort
mesh with a FIXED participant budget, plus a stage breakdown and a
device-idle estimate:

- ``host_s_per_round``    — plan + pack + feedback host wall-time;
- ``device_s_per_round``  — measured on the sync engine by blocking on the
  fused step right after dispatch (enqueue + execution);
- ``device_idle_fraction`` — sync: host/(host+device), the idle share the
  overlap can reclaim; overlapped: max(0, 1 − device/observed), what is
  left after reclaiming.

Local work stays light (default ``--local-steps 3 --batch-size 16``, like
``cohort_scaling.py``): the benchmark measures the ENGINE's round
pipelining — the regime the ISSUE motivates, where the host stages
dominate and the device idles most of each round. BLAS threading is capped
to one thread (below, before numpy loads): the host control plane runs
numpy between device steps, and multi-threaded spinning BLAS kernels
starve the XLA CPU worker threads that stand in for devices here —
measured as 2-3x inflated fused-step latency and a wrecked overlap.

Compile-once and one-fused-dispatch-per-round must hold in BOTH modes
(asserted). Writes BENCH_round_overlap.json at the repo root unless
--smoke, which runs a quick CI check: invariants in both modes plus
live-device-bytes non-regression of the overlapped mode (between rounds
the double-buffered schedule must not keep a second bank copy alive).

Usage:  python benchmarks/round_overlap.py [--cohorts 8 32] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

N_DEVICES = int(os.environ.get("COHORT_BENCH_DEVICES", "8"))
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={N_DEVICES} "
        + os.environ.get("XLA_FLAGS", "")
    )
# single-threaded host BLAS (see module docstring) — must precede numpy
for _v in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_v, "1")

import jax  # noqa: E402  (after XLA_FLAGS)
import numpy as np  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.data import make_population  # noqa: E402
from repro.fl import AuxoConfig, AuxoEngine, FLConfig  # noqa: E402
from repro.fl.task import MLPTask  # noqa: E402
from round_latency import force_leaves  # noqa: E402


def live_device_bytes() -> int:
    return sum(a.nbytes for a in jax.live_arrays())


def make_engine(overlap: int, n_leaves: int, shards: int, rounds: int,
                seed: int, local_steps: int, batch_size: int,
                participants: int) -> AuxoEngine:
    pop = make_population(
        n_clients=1000,
        n_groups=n_leaves,
        group_sep=0.0,
        dirichlet=2.0,
        label_conflict=0.6,
        seed=seed,
    )
    task = MLPTask(dim=pop.dim, n_classes=pop.n_classes)
    width = int(participants * 1.25)
    fl = FLConfig(
        rounds=rounds,
        participants_per_round=participants,
        local_steps=local_steps,
        batch_size=batch_size,
        use_availability=False,
        seed=seed,
        execution="batched",
        cohort_shards=shards,
        round_overlap=overlap,
        rows_per_shard=-(-width // shards) if shards > 1 else 0,
    )
    auxo = AuxoConfig(
        d_sketch=64,
        cluster_k=2,
        max_cohorts=n_leaves,
        clustering_start_frac=0.0,
        partition_start_frac=2.0,  # no organic partitions during timing
        partition_end_frac=2.0,
    )
    eng = AuxoEngine(task, pop, fl, auxo)
    force_leaves(eng, n_leaves)
    return eng


def measure_device_time(eng: AuxoEngine, rounds: int, r0: int) -> float:
    """Per-round device time on a SYNC engine: dispatch the fused step and
    block on its outputs, timing only that window (stage ③ excluded)."""
    p = eng.pipeline
    times = []
    for r in range(r0, r0 + rounds):
        plan = p.plan_round(r)
        packed = p._pack_rows(plan)
        t0 = time.perf_counter()
        res = p.execute(plan, packed)
        jax.block_until_ready(p.bank.params)
        res.sketches, res.losses
        times.append(time.perf_counter() - t0)
        p.apply_feedback(plan, res)
    return float(np.median(times))


def bench(overlap: int, n_leaves: int, shards: int, rounds: int, warmup: int,
          seed: int, local_steps: int, batch_size: int, participants: int,
          trials: int = 3):
    """Steady-state s/round for one mode.

    The timed region is split into `trials` segments and the MINIMUM of
    the segment medians is reported (same noise model as timeit): this
    container's cores are shared, and multi-hundred-ms steal bursts would
    otherwise dominate either mode's median arbitrarily.
    """
    eng = make_engine(
        overlap, n_leaves, shards, warmup + trials * rounds + 8, seed,
        local_steps, batch_size, participants,
    )
    p = eng.pipeline
    for r in range(warmup):  # compile + k-means bootstraps + pipeline fill
        eng.step(r)
    d0 = p.exec_dispatches
    seg_times, seg_hosts = [], []
    r = warmup
    for _ in range(trials):
        times, hosts = [], []
        for _i in range(rounds):
            s0 = dict(p.stage_seconds)
            t0 = time.perf_counter()
            eng.step(r)
            times.append(time.perf_counter() - t0)
            hosts.append(
                sum(
                    p.stage_seconds[k] - s0[k]
                    for k in ("plan", "pack", "feedback")
                )
            )
            r += 1
        seg_times.append(float(np.median(times)))
        seg_hosts.append(float(np.median(hosts)))
    best = int(np.argmin(seg_times))
    out = {
        "mode": "overlapped" if overlap else "sync",
        "cohorts": n_leaves,
        "shards": p.n_shards,
        "participants_per_round": participants,
        "s_per_round": seg_times[best],
        "s_per_round_segments": seg_times,
        "host_s_per_round": seg_hosts[best],
        "exec_dispatches_per_round": (p.exec_dispatches - d0) / (trials * rounds),
        "compiled_executables": p._exec_step._cache_size(),
        "live_mbytes": live_device_bytes() / 1e6,
        "pipeline_flushes": p.flushes,
    }
    if not overlap:
        out["device_s_per_round"] = measure_device_time(
            eng, min(rounds, 8), warmup + trials * rounds
        )
        tot = out["host_s_per_round"] + out["device_s_per_round"]
        out["device_idle_fraction"] = out["host_s_per_round"] / max(tot, 1e-9)
    p.flush()
    # compile-once + one-fused-dispatch-per-round survive the overlap
    assert out["exec_dispatches_per_round"] == 1.0, out
    assert out["compiled_executables"] == 1, out
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cohorts", type=int, nargs="+", default=[8, 32])
    ap.add_argument("--shards", type=int, default=N_DEVICES)
    ap.add_argument("--rounds", type=int, default=12,
                    help="rounds per timed segment")
    ap.add_argument("--trials", type=int, default=3,
                    help="timed segments per mode (min of medians reported)")
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--participants", type=int, default=128)
    ap.add_argument("--local-steps", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="CI mode: C=8 only, few rounds, asserts invariants + memory",
    )
    args = ap.parse_args()
    if args.smoke:
        args.cohorts, args.rounds, args.warmup, args.trials = [8], 3, 2, 1

    sweep = []
    for c in args.cohorts:
        sync = bench(0, c, args.shards, args.rounds, args.warmup, args.seed,
                     args.local_steps, args.batch_size, args.participants,
                     args.trials)
        over = bench(1, c, args.shards, args.rounds, args.warmup, args.seed,
                     args.local_steps, args.batch_size, args.participants,
                     args.trials)
        dev = sync["device_s_per_round"]
        over["device_idle_fraction"] = max(0.0, 1.0 - dev / over["s_per_round"])
        row = {
            "cohorts": c,
            "sync": sync,
            "overlapped": over,
            "speedup": sync["s_per_round"] / over["s_per_round"],
        }
        sweep.append(row)
        print(
            f"C={c:3d}  sync {sync['s_per_round']*1e3:7.1f} ms/round "
            f"(host {sync['host_s_per_round']*1e3:5.1f} + device {dev*1e3:5.1f}, "
            f"idle {sync['device_idle_fraction']:.0%})  "
            f"overlapped {over['s_per_round']*1e3:7.1f} ms/round  "
            f"-> {row['speedup']:.2f}x"
        )
        # §⑤ double-buffering must not keep a second bank copy alive
        assert over["live_mbytes"] < sync["live_mbytes"] * 1.5 + 64.0, (
            sync["live_mbytes"], over["live_mbytes"])

    if args.smoke:
        print("smoke OK: compile-once + 1 dispatch/round + memory hold "
              "under round overlap")
        return

    out = {
        "benchmark": "round_overlap",
        "devices": args.shards,
        "rounds_timed": args.rounds,
        "trials": args.trials,
        "participant_budget": "fixed",
        "local_steps": args.local_steps,
        "batch_size": args.batch_size,
        "sweep": sweep,
    }
    by_c = {row["cohorts"]: row for row in sweep}
    if 32 in by_c:
        out["speedup_c32"] = by_c[32]["speedup"]
    if 8 in by_c:
        out["speedup_c8"] = by_c[8]["speedup"]
    path = Path(__file__).resolve().parent.parent / "BENCH_round_overlap.json"
    path.write_text(json.dumps(out, indent=2) + "\n")
    print(json.dumps({k: v for k, v in out.items() if k != "sweep"}, indent=2))


if __name__ == "__main__":
    main()
