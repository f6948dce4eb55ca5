"""Reduce a JAX profiler trace of the measured window to what metrics read.

The trace (an ``.xplane.pb``, read with ``jax.profiler.ProfileData``) holds
one plane per TPU (``/device:TPU:<i>``) whose ``XLA Modules`` line has one
event per program run and whose ``XLA Ops`` line has one event per HLO
instruction (its name is the instruction's text, shapes included), and a
host plane (``/host:CPU``) whose lines carry the ``TraceAnnotation`` spans
the benchmark places around its calls into each layer. Device and host
events share one clock.

`summarize` gives, averaged over the chips used: busy seconds (the union of
op intervals), the window, every op with the program it ran in, and the idle
gaps, each attributed to the innermost benchmark span that covers it.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import re
from typing import List, Sequence, Tuple

SPAN_PREFIX = "bench:"  # host spans the benchmark places (TraceAnnotation)
_SHAPE = re.compile(r"\b(pred|s8|s16|s32|s64|u8|u32|bf16|f16|f32|f64)\[([0-9,]*)\]")


@dataclasses.dataclass
class Op:
    name: str      # HLO text of the instruction
    module: str    # program it ran in (``jit_step`` etc., hash stripped)
    start: int     # ns
    dur: int       # ns
    leaf: bool     # holds no other op (a while or a call holds its body)

    @property
    def label(self) -> str:
        return self.name.split(" = ", 1)[0].strip()

    @property
    def is_kernel(self) -> bool:
        """A Pallas kernel (not a custom call XLA makes of its own, such as
        ``AllocateBuffer``)."""
        return 'custom_call_target="tpu_custom_call"' in self.name

    def shapes(self) -> List[Tuple[str, Tuple[int, ...]]]:
        """(dtype, dims) of the result, then of each operand."""
        return [(t, tuple(int(d) for d in s.split(",") if d))
                for t, s in _SHAPE.findall(self.name)]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    ops: List[Op]                   # every chip's ops
    gaps: List[Tuple[str, float]]   # (host span, idle seconds), each gap
    chips: int
    modules: List[Tuple[str, int]]  # (program, device ns), each run

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_ops(self, kernels: Sequence[str]) -> List[Op]:
        """Calls of the named Pallas kernels (the custom call carries the
        kernel's name: ``%cosine_similarity.1 = ... custom-call(...)``)."""
        return [o for o in self.ops if o.is_kernel
                and o.label.lstrip("%").split(".")[0] in kernels]

    def module_seconds(self, module_word: str) -> float:
        """Device seconds of the runs of programs whose name holds the word."""
        return sum(d for m, d in self.modules if module_word in m) / 1e9 / self.chips

    def breakdown(self, top: int = 10) -> dict:
        by_op = collections.Counter()
        for o in self.ops:
            if o.leaf:
                by_op[f"{o.module}:{o.label}"] += o.dur
        by_gap = collections.Counter()
        for name, s in self.gaps:
            by_gap[name] += s
        return {
            "device_ops": [[n, d / 1e9 / self.chips] for n, d in by_op.most_common(top)],
            "idle_gaps": [[n, s / self.chips] for n, s in by_gap.most_common(top)],
        }


def _module_name(text: str) -> str:
    return re.sub(r"\(\d+\)$", "", text)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _mark_leaves(ops: List[Op]) -> None:
    """An op that contains the start of the next op on its line holds it."""
    ops.sort(key=lambda o: (o.start, -o.dur))
    for a, b in zip(ops, ops[1:]):
        if b.start < a.start + a.dur:
            a.leaf = False


def _attribute(gaps: List[Tuple[int, int]], spans) -> List[str]:
    """For each gap, the innermost benchmark span covering its midpoint."""
    spans = sorted(spans)
    order = sorted(range(len(gaps)), key=lambda i: gaps[i][0] + gaps[i][1])
    out = ["outside any span"] * len(gaps)
    active: list = []
    j = 0
    for i in order:
        mid = (gaps[i][0] + gaps[i][1]) // 2
        while j < len(spans) and spans[j][0] <= mid:
            active.append(spans[j])
            j += 1
        active = [sp for sp in active if sp[1] >= mid]
        if active:
            out[i] = min(active, key=lambda sp: sp[1] - sp[0])[2]
    return out


def summarize_planes(planes, t0: int, t1: int) -> Summary:
    """Reduce ``ProfileData`` planes over the window [t0, t1] (ns)."""
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    spans = []
    for p in planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name[len(SPAN_PREFIX):]))
    ops: List[Op] = []
    modules: List[Tuple[str, int]] = []
    busy = 0
    gaps: List[Tuple[str, float]] = []
    chips = 0
    for dev in devices:
        lines = {line.name: line for line in dev.lines}
        if "XLA Ops" not in lines:
            continue
        chips += 1
        mods = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, _module_name(e.name))
            for e in (lines["XLA Modules"].events if "XLA Modules" in lines else [])
            if e.start_ns + e.duration_ns > t0 and e.start_ns < t1
        )
        modules += [(m, e - s) for s, e, m in mods]
        starts = [m[0] for m in mods]
        dev_ops = []
        for e in lines["XLA Ops"].events:
            s, d = e.start_ns, e.duration_ns
            if s + d <= t0 or s >= t1:
                continue
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and s < mods[i][1] else "unknown"
            dev_ops.append(Op(e.name, mod, s, d, True))
        _mark_leaves(dev_ops)
        ops += dev_ops
        merged = _union([(max(o.start, t0), min(o.start + o.dur, t1))
                         for o in dev_ops])
        busy += sum(e - s for s, e in merged)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        gaps += [(name, (e - s) / 1e9)
                 for name, (s, e) in zip(_attribute(idle, spans), idle)]
    if chips == 0:
        raise RuntimeError("the trace holds no XLA ops on any TPU")
    return Summary(window_s=(t1 - t0) / 1e9, busy_s=busy / 1e9 / chips,
                   ops=ops, gaps=gaps, chips=chips, modules=modules)


def window_bounds(planes, name: str) -> Tuple[int, int]:
    """Start and end (ns) of the host span ``bench:<name>``."""
    for p in planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                for e in line.events:
                    if e.name == SPAN_PREFIX + name:
                        return e.start_ns, e.start_ns + e.duration_ns
    raise RuntimeError(f"the trace holds no span {SPAN_PREFIX}{name}")


def load(trace_dir: str, window_span: str = "window") -> Summary:
    """Read the newest ``.xplane.pb`` under `trace_dir` and reduce the span
    ``bench:<window_span>`` of it."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise RuntimeError(f"no trace under {trace_dir}")
    planes = list(ProfileData.from_file(files[-1]).planes)
    t0, t1 = window_bounds(planes, window_span)
    return summarize_planes(planes, t0, t1)
