#!/usr/bin/env python
"""What utils/tracing.py costs on this host, with no profiler session: the
microseconds of one span (bare, and nested three deep with attributes, as an
epoch's are) and the milliseconds of one level's ``[time]`` report with the
recorder nearly empty and full. Touches no device.

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
    print(f"[span_cost] a level's report, {len(tracing._spans)} spans recorded: {report_ms():.3f} ms")
