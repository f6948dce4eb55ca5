"""spans.py: device idle time held against the program's own spans
(``auxo:decode.*``), on a hand-made trace and on two 8-step traces of the
decode-granite-long cell recorded on a TPU v5e (positions 1792-1799): one of
a program without the spans, on which the four decode readers must keep
reading what they read when it was recorded, and one with them."""
import json
from collections import namedtuple
from types import SimpleNamespace

import pytest

from conftest import BENCH

import run
import spans

trace = run.load_module(BENCH / "trace.py")
DATA = BENCH / "tests" / "data"
NO_SPANS = DATA / "decode_long_8.xplane.pb"
WITH_SPANS = DATA / "decode_long_8_spans.xplane.pb"
READERS = ("decode_attention_ms", "decode_hbm_share", "device_idle.decode", "decode_mfu")

Ev = namedtuple("Ev", "name start_ns duration_ns")
Line = namedtuple("Line", "name events")
Plane = namedtuple("Plane", "name lines")


def hand_made():
    host = Plane("/host:CPU", [Line("python3", [
        Ev("bench:window", 0, 1000),
        Ev("auxo:decode.prepare", 0, 100),      # device busy 50-100
        Ev("auxo:decode.dispatch", 100, 300),   # 100-400
        Ev("TpuClient::DefragmentMemory", 150, 200),
        Ev("auxo:decode.pick", 400, 50),
        Ev("auxo:decode.fetch", 450, 350),      # device busy 400-700
        Ev("auxo:decode.dispatch", 800, 100),   # 800-900
        Ev("TpuClient::DefragmentMemory", 950, 20),  # outside any span
    ])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_step(1)", 400, 300)]),
        Line("XLA Ops", [Ev("%fusion.1 = f32[8]{0} fusion()", 50, 50),
                         Ev("%fusion.2 = f32[8]{0} fusion()", 400, 300)]),
    ])
    return [host, dev]


def test_idle_by_overlap_with_spans():
    st = spans.SpanTrace.of(hand_made(), 0, 1000)
    s = trace.summarize_planes(hand_made(), 0, 1000)
    assert st.idle_s() == pytest.approx(s.window_s - s.busy_s) == pytest.approx(650e-9)
    assert st.count("decode.dispatch") == 2 and st.count("decode.prepare") == 1
    # idle 0-50, 100-400 and 700-1000, held against each span's intervals
    assert st.idle_in("decode.prepare") == pytest.approx(50e-9)
    assert st.idle_in("decode.dispatch") == pytest.approx(400e-9)
    assert st.idle_in("decode.pick", "decode.fetch") == pytest.approx(100e-9)
    assert st.defrag_in("decode.dispatch") == pytest.approx(200e-9)
    assert st.host_s("decode.fetch") == pytest.approx(350e-9)
    r = spans.decode_readings(st)
    assert r == pytest.approx({"decode_idle_dispatch_ms": 200e-6, "decode_defrag_ms": 100e-6,
                               "decode_idle_sync_ms": 50e-6, "decode_idle_prepare_ms": 25e-6})


def _recorded(path):
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(path)).planes)
    t0, t1 = trace.window_bounds(planes, "window")
    return planes, t0, t1, trace.summarize_planes(planes, t0, t1)


def _read(s, steps=8):
    config = json.loads((BENCH / "configs" / "granite-3-2b.json").read_text())
    window = {"steps": steps, "lanes": 4, "positions": list(range(1792, 1792 + steps)),
              "seconds": s.window_s}
    ctx = SimpleNamespace(window=window, trace=s, config=config,
                          flops=run.load_module(BENCH / "flops.py"),
                          peak=json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"])
    return {name: run.load_module(BENCH / "metrics" / f"{name}.py").read(ctx)
            for name in READERS}


def test_existing_readers_read_as_recorded():
    """The trace of a program without the spans reads what it read when it
    was recorded, and gives no program spans to split by."""
    planes, t0, t1, s = _recorded(NO_SPANS)
    assert _read(s) == pytest.approx({
        "decode_attention_ms": 0.423531625, "decode_hbm_share": 31.061850686532537,
        "device_idle.decode": 35.042016176121685, "decode_mfu": 0.2626153217072468},
        rel=1e-12)
    assert (s.window_s, s.busy_s) == pytest.approx((0.349806345, 0.227227149), rel=1e-12)
    b = s.breakdown()
    assert b["idle_gaps"] == [["decode_step", pytest.approx(0.104877049, rel=1e-9)],
                              ["chunk", pytest.approx(0.017702147, rel=1e-9)]]
    assert b["device_ops"][:3] == [
        ["jit_gather:%copy.1", pytest.approx(0.030797085, rel=1e-9)],
        ["jit_step:%bitcast_add_fusion.3", pytest.approx(0.028944889, rel=1e-9)],
        ["jit_step:%copy.30", pytest.approx(0.026576888, rel=1e-9)]]
    st = spans.SpanTrace.of(planes, t0, t1)
    assert st.idle_s() == pytest.approx(s.window_s - s.busy_s)
    assert st.spans == {} and spans.decode_readings(st) is None


def test_program_spans_split_the_idle_time():
    """The program's spans on the chip: each stage as often as the loop
    runs it, the runtime's memory defragmentation inside the fleet step's
    dispatch, and the three idle readings covering the window's idle."""
    planes, t0, t1, s = _recorded(WITH_SPANS)
    st = spans.SpanTrace.of(planes, t0, t1)
    steps = 8
    assert {n: st.count(n) for n in spans.DECODE_STAGES} == {
        "decode.prepare": 1, "decode.dispatch": steps, "decode.pick": steps,
        "decode.fetch": steps, "decode.writeback": 1}
    assert st.idle_s() == pytest.approx(s.window_s - s.busy_s)
    r = spans.decode_readings(st)
    assert r == pytest.approx({
        "decode_idle_dispatch_ms": 11.373747375, "decode_defrag_ms": 11.373539875,
        "decode_idle_sync_ms": 2.067315125, "decode_idle_prepare_ms": 0.10255625},
        rel=1e-9)
    assert r["decode_defrag_ms"] > 0
    split = (r["decode_idle_dispatch_ms"] + r["decode_idle_sync_ms"]
             + r["decode_idle_prepare_ms"]) * steps / 1e3
    assert 0.9 * st.idle_s() <= split <= st.idle_s()
    # every stage's idle lies inside its host time
    for name in spans.DECODE_STAGES:
        assert 0 <= st.idle_in(name) <= st.host_s(name)


def test_program_names_on_the_chip():
    """The decode loop runs three named programs; only the fleet step holds
    the word by which `decode_hbm_share` finds it, and the four decode
    readers read the trace as they read one without the spans."""
    _, _, _, s = _recorded(WITH_SPANS)
    runs = [m for m, _ in s.modules]
    assert sorted(set(runs)) == ["jit_gather_bank_rows", "jit_pick_tokens", "jit_step"]
    assert runs.count("jit_step") == runs.count("jit_pick_tokens") == 8
    assert [m for m in runs if "jit_step" in m] == ["jit_step"] * 8
    read = _read(s)
    assert all(0 < v < 100 for v in read.values())
    old = _read(_recorded(NO_SPANS)[3])
    assert read["decode_attention_ms"] == pytest.approx(old["decode_attention_ms"], rel=0.01)
    assert read["decode_hbm_share"] == pytest.approx(old["decode_hbm_share"], rel=0.05)
