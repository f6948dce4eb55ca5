#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (`BENCHMARK.json` ``workloads``) names a configuration
(``configs/<file>.json``) and a traffic mix (``traffic/<mix>.json``); the mix
names the driver (``drivers/<driver>.py``) that sets the system up, runs the
measured window and checks what it produced against a plain reference
(``reference/``), number by number against ``limits/<workload>.json``. With
``--trace 1`` the window runs under the profiler and each per-layer metric
is read by ``metrics/<metric>.py`` from the trace, the program's counters
and the window's own numbers. New cells, mixes, drivers and metrics are new
files: nothing here names one.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, optionally
``breakdown``, and last ``checks``); each compared number is also printed,
beside its limit, as the last lines of standard error. Without a TPU, or
with fewer chips than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
T0 = time.time()
TRACE_DIR = ROOT / ".bench_trace"


def cache_dir() -> str:
    """JAX's persistent compilation cache: where the environment says, else
    a fixed directory in the checkout (the path is part of the cache key)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")


def load_module(path: Path):
    """A module of the benchmark found by its file name (which may hold dots)."""
    name = "bench_" + path.stem.replace(".", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def load_cell(workload: str, root: Path = ROOT) -> SimpleNamespace:
    """Everything a cell is made of, found by the names in BENCHMARK.json."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{workload}.json").read_text())

    def listed(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if listed(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return SimpleNamespace(name=workload, cell=cell, config=config, traffic=traffic,
                           limits=limits, end_to_end=e2e, per_layer=layer,
                           run_seconds=bench["run_seconds"])


def driver_module(cell):
    """The driver module the cell's traffic names (``drivers/<driver>.py``)."""
    return load_module(BENCH / "drivers" / f"{cell.traffic['driver']}.py")


class Spans:
    """Host spans in the profiler's trace, named ``bench:<name>``."""

    def __call__(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation("bench:" + name)

    def wrap(self, name: str, fn):
        def wrapped(*a, **kw):
            with self(name):
                return fn(*a, **kw)
        return wrapped


class CompileClock:
    """Backend compiles (cache reads included) and persistent-cache hits."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def find_chip(chips: int, peaks: dict):
    """The TPU devices and their peaks; None where the chips are not there."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"run.py: no TPU (JAX found {dev.platform!r})", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"run.py: the cell asks for {chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return None
    if dev.device_kind not in peaks:
        raise SystemExit(f"run.py: no peaks for device kind {dev.device_kind!r} "
                         "in bench/peaks.json")
    return devices


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)


def read_layers(cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, peak: dict,
             start: float = T0, control: bool = False) -> dict:
    """Set up from `start` on, measure, check. `devices` None leaves out the
    device block (the tests drive it on the CPU). `control` adds what the
    driver's control reads (``control``), for calibration and never in a
    benchmark run."""
    import jax

    clock = CompileClock()
    spans = Spans() if trace else None
    drv = driver_module(cell).Driver(cell.config, cell.traffic, seed, span=spans)
    drv.setup()
    setup_s = time.time() - start
    setup_compiles = clock.compiles
    summary = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        # a traced window may be shorter than a timed one (traffic's
        # "trace_window": seconds, or whole chunks)
        tw = dict(cell.traffic.get("trace_window", {}))
        with spans("window"):
            stats = drv.window(tw.pop("seconds", seconds), **tw)
        jax.profiler.stop_trace()
        try:
            summary = load_module(BENCH / "trace.py").load(str(TRACE_DIR))
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        stats = drv.window(seconds)
    window_compiles = clock.compiles - setup_compiles
    mem = peak_bytes(devices) if devices else None
    if trace:
        ctx = SimpleNamespace(window=stats, trace=summary, peak=peak,
                              flops=load_module(BENCH / "flops.py"),
                              config=cell.config, traffic=cell.traffic)
        metrics = read_layers(cell, ctx)
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in cell.end_to_end:
            if m["name"] in stats["end_to_end"]:
                metrics[m["name"]] = {"value": float(stats["end_to_end"][m["name"]]),
                                      "unit": m["unit"]}
    drv.release()
    gc.collect()
    readings = drv.check()
    checks = {k: {"value": float(v), "limit": cell.limits[k]} for k, v in readings.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {
        "correct": correct,
        "attempted": int(stats["attempted"]),
        "failed": int(stats["failed"]),
        "metrics": metrics,
        "device": None,
        "setup_compiles": setup_compiles,
        "window_compiles": window_compiles,
        "window_s": stats["seconds"],
    }
    if devices:
        d = devices[0]
        result["device"] = {"platform": d.platform, "kind": d.device_kind,
                            "count": len(devices), "memory_peak_bytes": mem}
        if summary is not None:
            result["device"] |= {"busy_s": summary.busy_s, "window_s": summary.window_s}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    if control:
        result["control"] = {k: float(v) for k, v in drv.control().items()}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    cell = load_cell(args.workload)
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peaks = json.loads((BENCH / "peaks.json").read_text())
    devices = find_chip(cell.cell["chips"], peaks)
    if devices is None:
        return 1
    devices = devices[: cell.cell["chips"]]
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                      peaks[devices[0].device_kind])
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
