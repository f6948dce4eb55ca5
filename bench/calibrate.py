#!/usr/bin/env python3
"""Readings that the limits in ``limits/<workload>.json`` are set from.

  python3 bench/calibrate.py --workload <name> --seeds 1 2 3 ... [--seconds S]
         [--control N] [--fault NAME ...] [--fault-seeds N]

For each seed, in one process, a whole run of the cell through `run.run_cell`
over a short window (by default one chunk or round), printed as one JSON line:
the numbers its check compares (``checks``) and, for the first N seeds, what
the driver's control reads (``control``). Each fault the cell's driver plants
(its ``FAULTS``) then runs on the first `--fault-seeds` seeds. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--fault", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(run.BENCH), str(run.ROOT / "src")]
    cell = run.load_cell(args.workload)
    import jax

    jax.config.update("jax_compilation_cache_dir", run.cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peaks = json.loads((run.BENCH / "peaks.json").read_text())
    devices = run.find_chip(cell.cell["chips"], peaks)
    if devices is None:
        return 1
    devices = devices[: cell.cell["chips"]]
    peak = peaks[devices[0].device_kind]
    faults = run.driver_module(cell).FAULTS

    def one(seed, fault=None, **kw):
        t0 = time.time()
        if fault:
            with faults[fault]():
                res = run.run_cell(cell, seed, args.seconds, False, devices, peak,
                                   start=t0, **kw)
        else:
            res = run.run_cell(cell, seed, args.seconds, False, devices, peak,
                               start=t0, **kw)
        line = {"workload": args.workload, "seed": seed, "fault": fault,
                "run_s": time.time() - t0, **res}
        print(json.dumps(line), flush=True)

    for i, seed in enumerate(args.seeds):
        one(seed, control=i < args.control)
    for name in args.fault:
        for seed in args.seeds[: args.fault_seeds]:
            one(seed, fault=name)
    return 0


if __name__ == "__main__":
    sys.exit(main())
