"""What the benchmark watches from outside the program: host-clock spans
around calls into its layers, JAX's own compile events, device memory.

Nothing here changes what a run computes.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Optional

import jax

from benchmarks.trace_reduce import SPAN_PREFIX

# Lowering and XLA compilation (or the read from the persistent cache) of one
# module. Tracing is left out: its events nest, jit inside jit, and a sum of
# them can exceed the wall clock.
_XLA_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    _XLA_COMPILE_EVENT,
)



class CompileLog:
    """What JAX reports about compilation while the block runs: seconds spent
    lowering and compiling (or reading the persistent cache), the name of
    every module that reached XLA with the host time it got there, and cache
    hits and misses."""

    def __init__(self):
        self._mu = threading.Lock()
        self.events: list[tuple[float, float]] = []  # (when, seconds), both kinds
        self.modules: list[tuple[float, str, float]] = []  # (when, name, seconds)
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event not in _COMPILE_EVENTS:
            return
        now = time.perf_counter()
        with self._mu:
            self.events.append((now, duration))
            if event == _XLA_COMPILE_EVENT:
                self.modules.append((now, str(kw.get("fun_name")), duration))

    def _on_event(self, event: str, **kw) -> None:
        with self._mu:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def __enter__(self) -> "CompileLog":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def seconds_before(self, t: float) -> float:
        return sum(s for when, s in self.events if when < t)

    def modules_between(self, t0: float, t1: float) -> list[tuple[str, float]]:
        return [(n, s) for when, n, s in self.modules if t0 <= when <= t1]


@dataclass
class Span:
    name: str
    start: float  # time.perf_counter()
    end: float
    meta: dict = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Spans:
    """Host-clock spans, kept in memory. Each is also a ``TraceAnnotation``,
    so a profiler trace that is running holds them on the device's clock."""

    def __init__(self):
        self.all: list[Span] = []

    @contextmanager
    def span(self, name: str, **meta: Any):
        s = Span(name, time.perf_counter(), 0.0, dict(meta))
        try:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                yield s
        finally:
            s.end = time.perf_counter()
            self.all.append(s)

    def named(self, name: str, t0: float = float("-inf"), t1: float = float("inf")):
        """Spans of that name that lie wholly inside [t0, t1]."""
        return [s for s in self.all if s.name == name and s.start >= t0 and s.end <= t1]


def memory_stats() -> dict:
    """Of the fullest local device. A TPU program's temporaries are reserved
    apart from the buffers in use, and both come out of ``bytes_limit``."""
    best: Optional[dict] = None
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if best is None or stats.get("peak_bytes_in_use", 0) > best.get(
            "peak_bytes_in_use", 0
        ):
            best = stats
    return dict(best or {})
