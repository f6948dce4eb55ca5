"""The program's spans and compile counts, on the profiler's clock.

`span(name)` is a `jax.profiler.TraceAnnotation` named ``auxo:<name>``.
Under a running profiler it lands on the trace's host plane, whose clock the
device planes share, so a reduction of the trace can say which stage of the
program the device waited on. With no profiler running it does nothing.

`compiles()` gives the backend compiles of the process (persistent-cache
reads included), the seconds they took and the persistent-cache hits, as
counted by one `jax.monitoring` listener that the first call registers.
Subtract two readings to count what happened between them.
"""
from __future__ import annotations

import dataclasses
import threading

import jax

PREFIX = "auxo:"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span ``auxo:<name>`` in the profiler's trace (a context manager)."""
    return jax.profiler.TraceAnnotation(PREFIX + name)


@dataclasses.dataclass(frozen=True)
class Compiles:
    count: int = 0
    seconds: float = 0.0
    cache_hits: int = 0

    def __sub__(self, other: "Compiles") -> "Compiles":
        return Compiles(self.count - other.count, self.seconds - other.seconds,
                        self.cache_hits - other.cache_hits)


class _Listener:
    def __init__(self):
        self.lock = threading.Lock()
        self.total = Compiles()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == _COMPILE:
            with self.lock:
                t = self.total
                self.total = Compiles(t.count + 1, t.seconds + duration, t.cache_hits)

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            with self.lock:
                t = self.total
                self.total = Compiles(t.count, t.seconds, t.cache_hits + 1)


_listener = None
_registering = threading.Lock()


def compiles() -> Compiles:
    """Compiles counted since the first call of this function in the process."""
    global _listener
    with _registering:
        if _listener is None:
            _listener = _Listener()
    return _listener.total
