#!/usr/bin/env python
"""Device idle time of each profiler session under a ``profile_dir``, by the
innermost ``tp/`` span (turboprune_tpu/utils/tracing.py) the host was in:

    python scripts/trace_gaps.py <profile_dir>

``experiment_params.profile_dir`` leaves two sessions: ``level0_epoch1`` (one
whole epoch-loop iteration) and ``level0_to_1`` (the level boundary). For
each, over the stretch its ``tp/`` spans cover: the first device's busy and
idle seconds, and the idle seconds by span. Interval arithmetic is the
benchmark's (benchmarks/trace_reduce.py). On a v5e an ``XLA Ops`` event's
stats hold its offset and duration only, not the named scope (PERF.md
section 5), so device time by scope is not read here."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.trace_reduce import (  # noqa: E402
    DEVICE_PLANE_PREFIX, OPS_LINE, clip, idle_by_label, read_planes, total, union,
)  # fmt: skip

PREFIX = "tp/"


def main(profile_dir: str) -> int:
    sessions = sorted(Path(profile_dir).glob("**/*.xplane.pb"))
    if not sessions:
        print(f"no .xplane.pb under {profile_dir}")
        return 1
    for xplane in sessions:
        planes = read_planes(xplane)
        spans = [
            (n[len(PREFIX):], s, e)
            for name, lines in planes.items() if name.startswith("/host:")
            for events in lines.values() for n, s, e in events if n.startswith(PREFIX)
        ]  # fmt: skip
        print(f"== {xplane.relative_to(profile_dir)}: {len(spans)} tp/ spans")
        devices = sorted(n for n, ls in planes.items() if n.startswith(DEVICE_PLANE_PREFIX) and ls.get(OPS_LINE))
        if not spans or not devices:
            print("   no tp/ span or no device operation in this session")
            continue
        t0, t1 = min(s for _, s, _ in spans), max(e for _, _, e in spans)
        busy = clip(union((s, e) for _, s, e in planes[devices[0]][OPS_LINE]), t0, t1)
        print(f"   stretch {t1 - t0:.4f} s, device busy {total(busy):.4f} s, idle {t1 - t0 - total(busy):.4f} s; idle by span:")
        for label, seconds in idle_by_label(busy, spans, t0, t1):
            print(f"   {seconds:10.4f} s  {label}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]) if len(sys.argv) == 2 else print(__doc__) or 2)
