"""The program's own spans (``turboprune_tpu/utils/tracing.py``, on the same
``perf_counter`` clock as ``obs["window"]``) as the per-layer readers take
them. The recorder is process-wide, so a reader takes only what lies inside
this run's window. A program from before the recorder has nothing to read."""

from __future__ import annotations

import importlib
from collections import defaultdict
from statistics import median
from typing import Optional


def recorded(name: str, t0: float = float("-inf"), t1: float = float("inf")) -> list:
    try:
        tracing = importlib.import_module("turboprune_tpu.utils.tracing")
    except ImportError:
        return []
    return tracing.recorded(name, t0, t1)


def per_level_median(obs: dict, *names: str) -> Optional[float]:
    """Median over the window's levels of the seconds in spans of ``names``
    together; spans of one level share its ``level`` attribute. Nothing where
    the window is not cut in levels, or holds no such span."""
    if obs.get("unit") != "level":
        return None
    by_level: dict = defaultdict(float)
    for name in names:
        for s in recorded(name, *obs["window"]):
            by_level[s.attrs.get("level")] += s.seconds
    return median(by_level.values()) if by_level else None
