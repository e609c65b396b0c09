"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. All
arithmetic is on plain ``(start, end)`` pairs in seconds, so the tests check
it on hand-made intervals.

On a TPU the device is a plane named ``/device:TPU:<n>``. Its ``XLA Ops`` line
holds one event per executed HLO operation (a ``while`` spans its body's
operations, which lie on the same line inside it), and its ``XLA Modules`` line
one event per executed program. The benchmark's own spans are ``TraceMe``
events named ``bench/<span>`` on a thread line of ``/host:CPU``, on the same
clock.
"""

from __future__ import annotations

from collections import defaultdict
from pathlib import Path
from typing import Iterable, Optional, Sequence

Interval = tuple[float, float]
Event = tuple[str, float, float]  # name, start, end

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"
UNLABELLED = "no_span"
TRACED_SPAN = "traced"


def union(intervals: Iterable[Interval]) -> list[Interval]:
    """The same set of instants as disjoint intervals in order."""
    out: list[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], t0: float, t1: float) -> list[Interval]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals if min(e, t1) > max(s, t0)]


def gaps(busy: Sequence[Interval], t0: float, t1: float) -> list[Interval]:
    """What [t0, t1] holds besides ``busy`` (disjoint and in order)."""
    out, at = [], t0
    for s, e in clip(busy, t0, t1):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if t1 > at:
        out.append((at, t1))
    return out


def label_gap(gap: Interval, spans: Sequence[Event]) -> str:
    """The span that covers most of the gap; of spans that cover as much, the
    shortest, which is the innermost."""
    best, best_key = UNLABELLED, (0.0, 0.0)
    for name, s, e in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        key = (cover, -(e - s))
        if cover > 0 and key > best_key:
            best, best_key = name, key
    return best


def idle_by_label(
    busy: Sequence[Interval], spans: Sequence[Event], t0: float, t1: float
) -> list[tuple[str, float]]:
    """Idle seconds of [t0, t1] by the host span that covers each gap,
    largest first."""
    by: dict[str, float] = defaultdict(float)
    for gap in gaps(busy, t0, t1):
        by[label_gap(gap, spans)] += gap[1] - gap[0]
    return sorted(by.items(), key=lambda kv: -kv[1])


def self_seconds(events: Iterable[Event]) -> dict[str, float]:
    """Seconds of each name on one line, less what events nested inside it
    take: a ``while`` is charged only what its body's operations leave."""
    out: dict[str, float] = defaultdict(float)
    stack: list[list] = []  # [name, end, self]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] += own

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return dict(out)


def op_kind(name: str) -> str:
    """``convert_reduce_fusion`` for the HLO text ``%convert_reduce_fusion.304
    = (f32[256]...) fusion(...)`` the trace gives as an operation's name: the
    operation's own name without its number, so that the same kind of work
    adds up."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    stem, _, number = head.rpartition(".")
    return stem if stem and number.isdigit() else head


def _events(line, scale: float = 1e-9) -> list[Event]:
    return [
        (ev.name, ev.start_ns * scale, (ev.start_ns + ev.duration_ns) * scale)
        for ev in line.events
    ]


def find_xplane(trace_dir: str | Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_planes(path: str | Path) -> dict:
    """{plane name: {line name: [events]}} with times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes: dict = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(_events(line))
    return planes


def describe(path: str | Path, top: int = 12) -> str:
    """A trace for reading by hand: planes, lines, and the names that take
    most time on each line."""
    rows = []
    for pname, lines in read_planes(path).items():
        rows.append(f"PLANE {pname}")
        for lname, events in lines.items():
            if not events:
                continue
            t0 = min(e[1] for e in events)
            t1 = max(e[2] for e in events)
            rows.append(
                f"  LINE {lname!r}: {len(events)} events, {t0:.6f}..{t1:.6f} s, "
                f"union {total(union((s, e) for _, s, e in events)):.6f} s"
            )
            by: dict[str, list] = defaultdict(lambda: [0, 0.0])
            for name, s, e in events:
                by[name][0] += 1
                by[name][1] += e - s
            for name, (n, sec) in sorted(by.items(), key=lambda kv: -kv[1][1])[:top]:
                rows.append(f"      {sec:10.6f} s  x{n:<6d} {name[:120]}")
    return "\n".join(rows)


def reduce_trace(path: str | Path, top: int = 10) -> Optional[dict]:
    """What the per-layer metrics need of one trace, or None where no
    operation ran on a device.

    ``window_s`` is the traced stretch: the benchmark's ``traced`` span, which
    it opens once the profiler has started and closes before it stops it (or,
    without one, from the first to the last thing the trace holds).
    ``busy_s`` is the union of operation intervals, averaged over devices.
    """
    planes = read_planes(path)
    devices = {
        name: lines
        for name, lines in planes.items()
        if name.startswith(DEVICE_PLANE_PREFIX) and lines.get(OPS_LINE)
    }
    if not devices:
        return None
    spans: list[Event] = []
    for name, lines in planes.items():
        if name.startswith("/host:"):
            for events in lines.values():
                spans.extend(
                    (n[len(SPAN_PREFIX):], s, e)
                    for n, s, e in events
                    if n.startswith(SPAN_PREFIX)
                )
    ops_all = [ev for lines in devices.values() for ev in lines[OPS_LINE]]
    traced = [(s, e) for n, s, e in spans if n == TRACED_SPAN]
    if traced:
        t0, t1 = traced[0]
    else:
        t0 = min([s for _, s, _ in ops_all] + [s for _, s, _ in spans])
        t1 = max([e for _, _, e in ops_all] + [e for _, _, e in spans])
    busy_by_device = {
        name: clip(union((s, e) for _, s, e in lines[OPS_LINE]), t0, t1)
        for name, lines in devices.items()
    }
    busy_s = sum(total(b) for b in busy_by_device.values()) / len(devices)
    first = sorted(devices)[0]

    def inside(events):
        """Of the first device, what lies wholly inside the stretch."""
        return [ev for ev in events if ev[1] >= t0 and ev[2] <= t1]

    own: dict[str, float] = defaultdict(float)
    for name, seconds in self_seconds(inside(devices[first][OPS_LINE])).items():
        own[op_kind(name)] += seconds
    modules: dict[str, list[float]] = defaultdict(list)
    for name, s, e in inside(devices[first].get(MODULES_LINE, [])):
        modules[name].append(e - s)
    spans = [sp for sp in spans if sp[0] != TRACED_SPAN]
    return {
        "devices": len(devices),
        "window_s": t1 - t0,
        "busy_s": busy_s,
        "device_ops": sorted(own.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": idle_by_label(busy_by_device[first], spans, t0, t1)[:top],
        "modules": dict(modules),
        "spans": spans,
    }


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1]))
