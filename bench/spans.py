"""The program's own host spans in a trace, held against device idle time.

The program names its stages with ``auxo:<name>`` spans
(`repro.utils.trace.span`), which the profiler writes on the host plane, on
the clock the device planes share. `SpanTrace.of` keeps, for the window
[t0, t1] (ns): each chip's idle intervals (the complement of the union of
its XLA ops, as `trace.summarize_planes` takes it), the intervals of every
program span, and those of the runtime's ``TpuClient::DefragmentMemory``
events. An idle interval counts toward a span by its overlap with the
span's intervals, not by its midpoint.

`decode_readings` gives the decode loop's split per fleet step. No metric of
the benchmark reads it yet: `trace.load` keeps no host span but the
benchmark's own, so a reader has nothing to hold it against (PERF.md,
section 7). `span_split.py` and the tests read it from a kept trace.

This is a second reduction of the trace beside `trace.summarize_planes`,
kept only until a `benchmark` PR lets `Summary` carry the program's spans.
That PR builds `SpanTrace` from the idle intervals `summarize_planes`
already computes, not from a second pass over the XLA ops, and deletes
`span_split.py`.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

PREFIX = "auxo:"
DEFRAG = "TpuClient::DefragmentMemory"
Intervals = List[Tuple[int, int]]


def union(intervals: Intervals) -> Intervals:
    """Sorted, disjoint intervals covering the same ns as `intervals`."""
    out: Intervals = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _overlap(a: Intervals, b: Intervals) -> int:
    """ns that two sorted lists of disjoint intervals share."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclasses.dataclass
class SpanTrace:
    window_s: float
    idle: List[Intervals]            # per chip
    spans: Dict[str, Intervals]      # program span (prefix dropped) -> intervals
    defrag: Intervals                # the runtime's DefragmentMemory, host

    @classmethod
    def of(cls, planes, t0: int, t1: int) -> "SpanTrace":
        spans: Dict[str, Intervals] = {}
        defrag: Intervals = []
        idle: List[Intervals] = []
        for p in planes:
            if p.name.startswith("/host:"):
                for line in p.lines:
                    for e in line.events:
                        s, end = max(e.start_ns, t0), min(e.start_ns + e.duration_ns, t1)
                        if end <= s:
                            continue
                        if e.name.startswith(PREFIX):
                            spans.setdefault(e.name[len(PREFIX):], []).append((s, end))
                        elif e.name == DEFRAG:
                            defrag.append((s, end))
            elif p.name.startswith("/device:TPU:"):
                lines = {line.name: line for line in p.lines}
                if "XLA Ops" not in lines:
                    continue
                busy = union([
                    (max(e.start_ns, t0), min(e.start_ns + e.duration_ns, t1))
                    for e in lines["XLA Ops"].events
                    if e.start_ns + e.duration_ns > t0 and e.start_ns < t1])
                edges = [t0] + [x for iv in busy for x in iv] + [t1]
                idle.append([(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s])
        if not idle:
            raise RuntimeError("the trace holds no XLA ops on any TPU")
        return cls(window_s=(t1 - t0) / 1e9, idle=idle,
                   spans={n: union(iv) for n, iv in spans.items()},
                   defrag=union(defrag))

    def count(self, name: str) -> int:
        """Calls of the span (nested calls of one name count once)."""
        return len(self.spans.get(name, []))

    def _of(self, names) -> Intervals:
        return union([iv for n in names for iv in self.spans.get(n, [])])

    def idle_s(self) -> float:
        """Device idle seconds of the window, averaged over chips."""
        return sum(e - s for chip in self.idle for s, e in chip) / 1e9 / len(self.idle)

    def idle_in(self, *names: str) -> float:
        """Device idle seconds inside the named spans, averaged over chips."""
        spans = self._of(names)
        return sum(_overlap(chip, spans) for chip in self.idle) / 1e9 / len(self.idle)

    def host_s(self, *names: str) -> float:
        """Host seconds inside the named spans."""
        return sum(e - s for s, e in self._of(names)) / 1e9

    def defrag_in(self, *names: str) -> float:
        """Host seconds of the runtime's DefragmentMemory inside the named spans."""
        return _overlap(self.defrag, self._of(names)) / 1e9


DECODE_STAGES = ("decode.prepare", "decode.dispatch", "decode.pick", "decode.fetch",
                 "decode.writeback")


def decode_readings(st: SpanTrace) -> Optional[Dict[str, float]]:
    """The decode loop's split, in ms per fleet step of the window; None
    where the program placed no decode spans (a program without them)."""
    steps = st.count("decode.dispatch")
    if not steps:
        return None
    per_step = 1e3 / steps
    return {
        "decode_idle_dispatch_ms": st.idle_in("decode.dispatch") * per_step,
        "decode_defrag_ms": st.defrag_in("decode.dispatch") * per_step,
        "decode_idle_sync_ms": st.idle_in("decode.pick", "decode.fetch") * per_step,
        "decode_idle_prepare_ms": st.idle_in("decode.prepare") * per_step,
    }
