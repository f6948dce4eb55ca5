"""trace.py: busy and idle time, kernel time and the attribution of idle
gaps, on a hand-made trace and on two traces recorded on a TPU v5e: four
synchronous rounds of the federated engine, with the benchmark's spans
around its stages, and eight fleet steps of the decode-granite-long cell
(positions 1792-1799), which the decode readers read as a traced run does."""
import json
from collections import namedtuple
from types import SimpleNamespace

import pytest

from conftest import BENCH

import run

trace = run.load_module(BENCH / "trace.py")
RECORDED = BENCH / "tests" / "data" / "fl_sync_small.xplane.pb"
RECORDED_DECODE = BENCH / "tests" / "data" / "decode_long_8.xplane.pb"

Ev = namedtuple("Ev", "name start_ns duration_ns")
Line = namedtuple("Line", "name events")
Plane = namedtuple("Plane", "name lines")

KERNEL = ("%cosine_similarity.4 = f32[4,32,8]{2,1,0} custom-call(f32[4,32,128]{2,1,0} %x, "
          "f32[4,8,128]{2,1,0} %c), custom_call_target=\"tpu_custom_call\"")


def hand_made():
    host = Plane("/host:CPU", [Line("python3", [
        Ev("bench:window", 0, 1000),
        Ev("bench:plan", 100, 300),       # 100-400
        Ev("bench:feedback", 600, 300),   # 600-900
        Ev("bench:apply", 650, 100),      # 650-750, inside feedback
    ])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_step(123)", 0, 100),
                             Ev("jit_assign_and_update_batched(9)", 400, 200)]),
        Line("XLA Ops", [
            Ev("%fusion.1 = f32[8]{0} fusion()", 0, 60),
            Ev("%fusion.2 = f32[8]{0} fusion()", 40, 60),   # overlaps: union 0-100
            Ev("%while.3 = (f32[8]) while()", 400, 200),      # holds the next two
            Ev(KERNEL, 420, 50),
            Ev("%fusion.5 = f32[8]{0} fusion()", 500, 80),
        ]),
    ])
    return [host, dev]


def test_busy_idle_and_gaps():
    s = trace.summarize_planes(hand_made(), 0, 1000)
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == pytest.approx(300e-9)        # 0-100 and 400-600
    assert s.idle_share == pytest.approx(0.7)
    gaps = dict(s.gaps)
    # 100-400 (midpoint 250: plan); 600-1000 (midpoint 800: feedback, not
    # the shorter apply span, which ends at 750)
    assert gaps == {"plan": pytest.approx(300e-9), "feedback": pytest.approx(400e-9)}


def test_kernel_ops_and_nesting():
    s = trace.summarize_planes(hand_made(), 0, 1000)
    by_label = {o.label: o for o in s.ops}
    assert not by_label["%while.3"].leaf and by_label["%fusion.5"].leaf
    kernels = s.kernel_ops(("cosine_similarity",))
    assert [o.label for o in kernels] == ["%cosine_similarity.4"]
    assert s.kernel_ops(("segment_aggregate",)) == []
    alloc = trace.Op('%custom-call.5 = f32[40,8]{1,0} custom-call(), '
                     'custom_call_target="AllocateBuffer"', "jit_step", 0, 1, True)
    assert not alloc.is_kernel
    assert kernels[0].module == "jit_assign_and_update_batched"
    assert kernels[0].shapes() == [("f32", (4, 32, 8)), ("f32", (4, 32, 128)),
                                   ("f32", (4, 8, 128))]
    assert s.module_seconds("jit_step") == pytest.approx(100e-9)
    ops = dict(map(tuple, s.breakdown()["device_ops"]))
    assert "jit_assign_and_update_batched:%while.3" not in ops  # a container
    assert ops["jit_assign_and_update_batched:%fusion.5"] == pytest.approx(80e-9)


def test_window_clips_events():
    s = trace.summarize_planes(hand_made(), 50, 450)
    assert s.busy_s == pytest.approx(100e-9)  # 50-100 and 400-450


def test_no_device_plane_is_an_error():
    with pytest.raises(RuntimeError):
        trace.summarize_planes(hand_made()[:1], 0, 1000)


def test_recorded_trace():
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(RECORDED)).planes)
    s = trace.summarize_planes(planes, *trace.window_bounds(planes, "window"))
    assert s.chips == 1 and 0 < s.busy_s < s.window_s
    # the fused round step and the clustering kernels ran in it
    assert s.module_seconds("jit__unknown") > 0
    kernels = s.kernel_ops(("cosine_similarity", "segment_aggregate"))
    assert {o.module for o in kernels} == {"jit_cosine_similarity",
                                           "jit_assign_and_update_batched"}
    assert all(o.dur > 0 for o in kernels)
    # idle time is attributed to the benchmark's spans around the stages
    names = {n for n, _ in s.gaps}
    assert names & {"plan_round", "pack_rows", "execute", "apply_feedback"}
    assert sum(sec for _, sec in s.gaps) == pytest.approx(s.window_s - s.busy_s)


def test_recorded_decode_trace():
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(RECORDED_DECODE)).planes)
    s = trace.summarize_planes(planes, *trace.window_bounds(planes, "window"))
    steps, layers = 8, 40
    # one attention kernel call per layer and step, and no other op of the
    # step program counts as a kernel (XLA's AllocateBuffer calls do not)
    kernels = [o for o in s.ops if o.is_kernel]
    assert len(kernels) == steps * layers
    assert kernels == s.kernel_ops(("decode_attention",))
    assert {o.module for o in kernels} == {"jit_step"}
    assert [m for m, _ in s.modules].count("jit_step") == steps
    config = json.loads((BENCH / "configs" / "granite-3-2b.json").read_text())
    window = {"steps": steps, "lanes": 4, "positions": list(range(1792, 1792 + steps)),
              "seconds": s.window_s}
    ctx = SimpleNamespace(window=window, trace=s, config=config,
                          flops=run.load_module(BENCH / "flops.py"),
                          peak=json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"])
    read = {name: run.load_module(BENCH / "metrics" / f"{name}.py").read(ctx)
            for name in ("decode_attention_ms", "decode_hbm_share", "device_idle.decode",
                         "decode_mfu")}
    assert read["decode_attention_ms"] == pytest.approx(
        sum(o.dur for o in kernels) / 1e6 / steps)
    assert 0 < read["decode_attention_ms"] < s.module_seconds("jit_step") * 1e3 / steps
    assert 0 < read["decode_hbm_share"] < 100
    assert 0 < read["device_idle.decode"] < 100
    assert 0 < read["decode_mfu"] < 100
