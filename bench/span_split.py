#!/usr/bin/env python3
"""Where a decode cell's device idle time goes, by the program's own spans.

  python3 bench/span_split.py --workload <decode cell> --seed <n> [--steps N]
         [--warm-chunks N] [--keep FILE]

Sets the cell up as `run.py` does, runs `--warm-chunks` whole chunks
untraced, then traces one whole chunk (of `--steps` fleet steps where given,
else the traffic's ``chunk_steps``) with the benchmark's spans and the
program's, and prints one JSON line: the traced window, its idle share, the
runtime's memory defragmentation per step, `spans.decode_readings`, the host
and device-idle milliseconds per step of each ``decode.*`` stage, and the
idle outside them (a program without the spans reads None and zeros there).
`--keep` copies the trace's ``.xplane.pb`` to FILE. The benchmark's own runs
never run this.

It repeats `run.run_cell`'s set-up and trace because a metric reader gets
only `trace.Summary`, which keeps no program span. The `benchmark` PR that
makes `summarize_planes` keep a `spans.SpanTrace` deletes this file: the
split is then read by per-layer metrics in every traced run.
"""
from __future__ import annotations

import argparse
import glob
import json
import shutil
import sys
import tempfile
import time

import run
import spans as program_spans


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--warm-chunks", type=int, default=0)
    ap.add_argument("--keep", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(run.BENCH), str(run.ROOT / "src")]
    cell = run.load_cell(args.workload)
    if args.steps:
        cell.traffic["chunk_steps"] = args.steps
    import jax
    from jax.profiler import ProfileData

    jax.config.update("jax_compilation_cache_dir", run.cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peaks = json.loads((run.BENCH / "peaks.json").read_text())
    if run.find_chip(cell.cell["chips"], peaks) is None:
        return 1
    t_setup = time.time()
    bench_spans = run.Spans()
    drv = run.driver_module(cell).Driver(cell.config, cell.traffic, args.seed,
                                         span=bench_spans)
    drv.setup()
    setup_s = time.time() - t_setup
    if args.warm_chunks:
        drv.window(0.0, chunks=args.warm_chunks)
    out = tempfile.mkdtemp()
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=opts)
        with bench_spans("window"):
            stats = drv.window(0.0, chunks=args.warm_chunks + 1)
        jax.profiler.stop_trace()
        (path,) = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
        if args.keep:
            shutil.copy(path, args.keep)
        planes = list(ProfileData.from_file(path).planes)
    finally:
        shutil.rmtree(out, ignore_errors=True)
    t0, t1 = run.load_module(run.BENCH / "trace.py").window_bounds(planes, "window")
    st = program_spans.SpanTrace.of(planes, t0, t1)
    steps = cell.traffic["chunk_steps"]
    stages = {name: {"host_ms": st.host_s(name) * 1e3 / steps,
                     "idle_ms": st.idle_in(name) * 1e3 / steps}
              for name in program_spans.DECODE_STAGES}
    idle_s = st.idle_s()
    inside = st.idle_in(*program_spans.DECODE_STAGES)
    readings = program_spans.decode_readings(st)
    # the share of the window's idle time the three idle readings account for
    split = ("decode_idle_dispatch_ms", "decode_idle_sync_ms", "decode_idle_prepare_ms")
    accounted = (sum(readings[k] for k in split) * steps / 1e3 / idle_s
                 if readings and idle_s else None)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "steps": steps,
        "warm_chunks": args.warm_chunks, "setup_s": setup_s,
        "step_ms_host_clock": stats["seconds"] * 1e3 / steps,
        "window_s": st.window_s, "idle_s": idle_s, "idle_share": idle_s / st.window_s,
        "defrag_ms": sum(e - s for s, e in st.defrag) / 1e6 / steps,
        "readings": readings, "accounted": accounted, "stages": stages,
        "idle_outside_decode_ms": (idle_s - inside) * 1e3 / steps,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
