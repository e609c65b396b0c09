#!/usr/bin/env python
"""What utils/tracing.py costs on this host, with no profiler session: the
microseconds of one span (bare, and nested three deep with attributes, as an
epoch's are), the microseconds of one call of a ``jax.monitoring`` listener
(a module's eight: three stages begun and ended, the cache's answer and its
read, with a span open) and the milliseconds of one level's ``[time]`` report
with the recorder nearly empty and full. Touches no device.

    python scripts/span_cost.py
"""

import sys
import time
from pathlib import Path
from statistics import median

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from turboprune_tpu.utils import tracing  # noqa: E402

N, REPS = 20000, 7


def bare():
    for _ in range(N):
        with tracing.span("level/setup"):
            pass


def nested():
    for i in range(N // 3):
        with tracing.span("level", level=i, density=0.5):
            with tracing.span("epoch", epoch=1):
                with tracing.span("epoch/log"):
                    pass


def listeners():
    """What JAX tells the recorder of one module the cache served, N // 8
    modules over: eight calls each, none of them JAX's own work."""
    with tracing.span("epoch/train"):
        for _ in range(N // 8):
            for stage in (tracing._TRACE, tracing._LOWER):
                tracing._on_start(stage, 0.0, fun_name="jit(f)")
                tracing._on_duration(stage, 1e-3, fun_name="jit(f)")
            tracing._on_start(tracing._COMPILE, 0.0, fun_name="jit(f)")
            tracing._on_event("/jax/compilation_cache/cache_hits")
            tracing._on_duration(tracing._CACHE_READ, 1e-3)
            tracing._on_duration(tracing._COMPILE, 2e-3, fun_name="jit(f)")


def per_span_us(fn, spans: int) -> float:
    times = []
    for _ in range(REPS):
        t = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t) / spans * 1e6)
    return median(times)


def report_ms() -> float:
    with tracing.span("level", level=0, density=1.0) as level:
        for name in ("level/load", "level/prune", "level/rewind", "level/train", "level/save"):
            with tracing.span(name):
                with tracing.span("ckpt/read"):
                    pass
    t = time.perf_counter()
    b = tracing.breakdown([level])
    tracing.line("level 0", b), tracing.timing_row(level, b)
    return (time.perf_counter() - t) * 1e3


if __name__ == "__main__":
    tracing._spans.clear()
    print(f"[span_cost] a level's report, {len(tracing._spans)} spans recorded: {report_ms():.3f} ms")
    print(f"[span_cost] bare span: {per_span_us(bare, N):.3f} us (median of {REPS} x {N})")
    print(f"[span_cost] nested span with attributes: {per_span_us(nested, N // 3 * 3):.3f} us")
    print(f"[span_cost] a listener's call: {per_span_us(listeners, N // 8 * 8):.3f} us (a module is eight)")
    print(f"[span_cost] a level's report, {len(tracing._spans)} spans recorded: {report_ms():.3f} ms")
