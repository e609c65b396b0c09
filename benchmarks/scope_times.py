"""Device seconds of one traced program by the named scope of its operations.

On a v5e an ``XLA Ops`` event of the profiler's trace carries the HLO text of
its instruction as its name and no scope (PERF.md section 3). The scope is in
the compiled module's own text: every instruction there has
``metadata={op_name="jit(scan_chunk)/.../layers_3/mixer/ssd/dot_general"}``,
the path of ``jax.named_scope``s (and flax module names) it was traced under,
through ``jvp``, ``transpose`` and ``checkpoint`` alike, and a Pallas kernel
is a ``custom-call`` under its own name with the scopes around it. Instruction
names are unique in a module, so the join is by name: event -> instruction ->
``op_name`` -> the first label whose path the ``op_name`` holds.

A fusion carries the ``op_name`` of its root, so an elementwise operation
that XLA fused across a scope's border is charged to the scope of the fusion's
root: the split is of the program as compiled, exact for matrix products and
kernels, approximate by a fusion's breadth at the borders.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Optional, Sequence

from benchmarks import trace_reduce

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"', re.M
)
OTHER = "other"


def instruction_scopes(hlo_text: str) -> dict[str, str]:
    """{instruction name: op_name} of a compiled module's text."""
    return {name: op for name, op in _INSTRUCTION.findall(hlo_text)}


def instruction_name(event_name: str) -> str:
    """``fusion.304`` of the trace's ``%fusion.304 = (f32[256]...) fusion(...)``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def label_of(op_name: Optional[str], labels: Sequence[str]) -> str:
    """The first of ``labels`` (scope paths such as ``mamba/in_proj``) that
    ``op_name`` holds as whole path segments; ``other`` where none."""
    if op_name:
        path = f"/{op_name}/"
        for label in labels:
            if f"/{label}/" in path:
                return label
    return OTHER


def program_seconds(
    xplane: str | Path, program: str, scopes: dict[str, str], labels: Sequence[str]
) -> Optional[dict]:
    """Mean device seconds of one run of ``program`` (a prefix of its name
    on the ``XLA Modules`` line) by label, over its runs that lie wholly
    inside the benchmark's traced stretch; ``runs`` is how many. None where
    the trace holds no such run."""
    planes = trace_reduce.read_planes(xplane)
    device = next(
        (
            lines
            for name, lines in sorted(planes.items())
            if name.startswith(trace_reduce.DEVICE_PLANE_PREFIX) and lines.get(trace_reduce.OPS_LINE)
        ),
        None,
    )
    if device is None:
        return None
    traced = [
        (s, e)
        for name, lines in planes.items()
        if name.startswith("/host:")
        for events in lines.values()
        for n, s, e in events
        if n == trace_reduce.SPAN_PREFIX + trace_reduce.TRACED_SPAN
    ]
    t0, t1 = traced[0] if traced else (float("-inf"), float("inf"))
    runs = [
        (s, e)
        for n, s, e in device.get(trace_reduce.MODULES_LINE, [])
        if n.startswith(program) and s >= t0 and e <= t1
    ]
    if not runs:
        return None
    by: dict[str, float] = defaultdict(float)
    for lo, hi in runs:
        inside = [ev for ev in device[trace_reduce.OPS_LINE] if ev[1] >= lo and ev[2] <= hi]
        for name, seconds in trace_reduce.self_seconds(inside).items():
            by[label_of(scopes.get(instruction_name(name)), labels)] += seconds
    return {"runs": len(runs), "seconds": {k: v / len(runs) for k, v in by.items()}}
