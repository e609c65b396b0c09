"""Spans, gauges and the profiler sessions of the training path: the one
mechanism for driver, harness, checkpoints and step.

``span(name, **attrs)`` times a scope on ``time.perf_counter()`` into a
process-wide bounded recorder and opens a
``jax.profiler.TraceAnnotation("tp/<name>")``, so a running profiler session
holds the same scope on the device trace's clock. ``level`` and ``epoch`` are
inherited from the enclosing span: the spans of one level share its number.
``breakdown`` and ``line`` turn a level's or set-up's spans into the
operator's ``[time]`` line and the ``level_timing.csv`` row.

What JAX does before a program runs is charged, as it ends, to the innermost
span open on the thread that did it, from ``jax.monitoring``'s events:
``trace_s`` (Python to jaxprs), ``lower_s`` (jaxpr to MLIR), ``compiles`` and
``compile_s`` (modules that reached the backend, and their seconds there,
which include a read from the persistent cache), ``cache_hits`` and
``cache_read_s`` (modules the persistent cache served, and the seconds
reading them), ``cache_misses`` and ``miss_compile_s`` (modules that asked
the cache and were compiled and written; a module under the cache's minimum
compile time is neither, warm or cold). The three stages nest, a jit traced
inside a jit and a trace inside a lowering; each is charged its own seconds
less the stages that ended inside it, so on every span ``trace_s + lower_s +
compile_s`` is at most its wall-clock. ``modules(t0, t1)`` is the record by
module, one ``Module`` a backend compile, the newest ``MAX_MODULES``: "which
step recompiled" and "which module missed", by name and by span.

The operator's lines (``driver.py``, the epoch loop): ``[time] set-up`` once
the harness is built, ``[time] start to first epoch`` once the first epoch
of a process closes, ``[time] level N`` when a level ends, and ``[time]
level N (unfinished)`` for the level an exception or a signal ended. Each
ends in ``traced T s, lowered L s, compiled K modules in S s (H read from
the cache in R s; M missed: names)``.

There is no off switch, so what recording costs is in every run: a few
microseconds a span, with a budget of a few dozen spans per level or epoch
and none per step or batch. A span ends where the host already is; nothing
here waits for the device. PERF.md section 3 lists every span and its reader.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import defaultdict, deque
from pathlib import Path
from typing import Any, NamedTuple, Optional, Sequence

import jax

PREFIX = "tp/"
MAX_SPANS = 65536  # a 30-level ladder of 150 epochs records about 32,000
MAX_MODULES = 4096  # a cell's process compiles 76-472, a cold 30-level ladder about 700
INHERITED = ("level", "epoch")
# What a ``[time]`` line names, in its order. The spans between these and the
# root (``level``, ``level/train``, ``epoch``) are containers: their self
# time, a span's duration less what its children cover, is the line's "other".
TERMS = (
    "setup/imports", "setup/config", "setup/distributed", "setup/backend", "harness/init",
    "level/load", "level/prune", "level/rewind", "level/agree", "level/setup",
    "epoch/feed", "epoch/train", "epoch/eval", "epoch/log", "epoch/ckpt",
    "level/finish", "level/save",
)  # fmt: skip
_CKPT = ("ckpt/read", "ckpt/fetch", "ckpt/wait", "ckpt/write", "ckpt/barrier")
# A level save's ``ckpt/write`` runs behind the next level, on the writer's
# thread (utils/checkpoint.py), so it is no span's child: a level's line and
# row name the write that ENDED during the level, beside the level's own time.
BEHIND = "ckpt/write"
# What a span is charged, in the order of a ``[time]`` line's tail.
CHARGED = (
    "trace_s", "lower_s", "compiles", "compile_s",
    "cache_hits", "cache_read_s", "cache_misses", "miss_compile_s",
)  # fmt: skip

_spans: deque = deque(maxlen=MAX_SPANS)  # closed spans, in closing order
_modules: deque = deque(maxlen=MAX_MODULES)  # one a backend compile, in that order
_ids = itertools.count(1)
_local = threading.local()
_mu = threading.Lock()
_gauges: dict[str, float] = {}
_traced: set[str] = set()  # the gauges set while a program was being traced
_profiling = False


def _stack() -> list:
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


class Span:
    """One timed scope, and its own context manager."""

    def __init__(self, name: str, attrs: dict):
        self.id = next(_ids)
        self.parent: Optional[int] = None
        self.name, self.attrs = name, attrs
        self.start = self.end = 0.0
        self.thread = threading.get_ident()
        self.trace_s = self.lower_s = self.compile_s = 0.0
        self.cache_read_s = self.miss_compile_s = 0.0
        self.compiles = self.cache_hits = self.cache_misses = 0

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "Span":
        stack = _stack()
        if stack:
            self.parent = stack[-1].id
            for key in INHERITED:
                if key in stack[-1].attrs:
                    self.attrs.setdefault(key, stack[-1].attrs[key])
        self._annotation = jax.profiler.TraceAnnotation(PREFIX + self.name, **self.attrs)
        self._annotation.__enter__()
        stack.append(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end = time.perf_counter()
        _stack().pop()
        self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        _spans.append(self)


def span(name: str, **attrs: Any) -> Span:
    """``with span("level/prune"): ...``; the ``with`` yields the Span."""
    return Span(name, attrs)


def inherited() -> dict:
    """What a span opened here would inherit (``INHERITED``): for a span that
    opens on another thread on this one's behalf, whose stack holds nothing."""
    stack = _stack()
    return {k: stack[-1].attrs[k] for k in INHERITED if k in stack[-1].attrs} if stack else {}


def note(**attrs: Any) -> None:
    """Add attributes to the innermost span open on this thread, for what is
    known only inside it; nothing where none is open. The recorder's span gets
    them, not its TraceAnnotation, which the profiler took at entry."""
    stack = _stack()
    if stack:
        stack[-1].attrs.update(attrs)


def recorded(
    name: Optional[str] = None, t0: float = float("-inf"), t1: float = float("inf")
) -> list[Span]:
    """Closed spans (of that name) wholly inside [t0, t1], in closing order."""
    return [
        s
        for s in list(_spans)
        if (name is None or s.name == name) and s.start >= t0 and s.end <= t1
    ]


class Module(NamedTuple):
    """One module that reached the backend: ``when`` it left it
    (``perf_counter``), the innermost span open on its thread then (``None``
    outside every span), and whether the persistent cache served it
    (``"hit"``), was asked and then written (``"miss"``) or neither
    (``"none"``: no cache, or a compile under its minimum time)."""

    name: str
    when: float
    span: Optional[int]
    span_name: Optional[str]
    lower_s: float
    compile_s: float
    cache: str


def modules(t0: float = float("-inf"), t1: float = float("inf")) -> list[Module]:
    """The modules that left the backend inside [t0, t1], oldest first."""
    return [m for m in list(_modules) if t0 <= m.when <= t1]


_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_STAGES = {_TRACE: "trace_s", _LOWER: "lower_s", _COMPILE: "compile_s"}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE = {"/jax/compilation_cache/cache_hits": "hit", "/jax/compilation_cache/cache_misses": "miss"}


def _on_start(event: str, _value: float, **_kw) -> None:
    """A stage begins on this thread (JAX records each stage's start as a
    scalar): open a count of the seconds that stages inside it will take."""
    if event in _STAGES:
        if not hasattr(_local, "stages"):
            _local.stages = []
        _local.stages.append(0.0)


def _on_duration(event: str, duration: float, **kw) -> None:
    """A stage ends on this thread: charge what it took itself to the
    innermost open span, and record a module that leaves the backend."""
    field = _STAGES.get(event)
    stack = _stack() if field or event == _CACHE_READ else None  # JAX times other things too
    if field is None:
        if stack:
            stack[-1].cache_read_s += duration
        return
    stages = getattr(_local, "stages", None)
    own = duration - stages.pop() if stages else duration
    if stages:
        stages[-1] += duration
    top = stack[-1] if stack else None
    if top is not None:
        setattr(top, field, getattr(top, field) + own)
    name = str(kw.get("fun_name"))
    if event == _LOWER:
        _local.lowered = (name, own)
    elif event == _COMPILE:
        lowered, cache = getattr(_local, "lowered", None), getattr(_local, "cache", "none")
        _local.lowered, _local.cache = None, "none"
        if top is not None:
            top.compiles += 1
            top.cache_hits += cache == "hit"
            if cache == "miss":
                top.cache_misses += 1
                top.miss_compile_s += own
        _modules.append(
            Module(
                name, time.perf_counter(), top and top.id, top and top.name,
                lowered[1] if lowered and lowered[0] == name else 0.0, own, cache,
            )
        )  # fmt: skip


def _on_event(event: str, **_kw) -> None:
    """The persistent cache answers for the module this thread is compiling,
    before that module's duration event."""
    if event in _CACHE:
        _local.cache = _CACHE[event]


jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def _set(name: str, value: float) -> None:
    _gauges[name] = value
    if getattr(_local, "stages", None):  # a stage is open on this thread
        _traced.add(name)


def gauge(name: str, value: float) -> None:
    """Set a named number of the process (the harness's plan gauges)."""
    with _mu:
        _set(name, value)


def count(name: str) -> None:
    """Add one to a gauge that counts what the process did (``mask_reads``)."""
    with _mu:
        _set(name, _gauges.get(name, 0) + 1)


def gauges() -> dict[str, float]:
    with _mu:
        return dict(_gauges)


def trace_gauges() -> dict[str, float]:
    """The gauges that code set while JAX was tracing it: what a program was
    built as (which form a kernel took, what a backward pass keeps, a loss's
    blocks), whatever module named them."""
    with _mu:
        return {name: _gauges[name] for name in sorted(_traced)}


def breakdown(roots: Sequence[Span], spans: Optional[Sequence[Span]] = None) -> dict:
    """What a ``[time]`` line says of ``roots`` and all recorded under them
    (or, for hand-made spans, found in ``spans``): the seconds of each of
    ``TERMS`` with its children, ``inside`` each term its direct children by
    name, the containers' self time as ``other_s``, and every count of
    ``CHARGED`` summed over all of them, with ``missed``: the ``(name,
    seconds)`` of the modules they compiled that the cache did not hold, the
    longest first. Terms and ``other_s`` sum to ``total_s``. Beside them
    ``behind``: the ``(level, seconds)`` of each write that ended on another
    thread while a root was open."""
    terms: dict = defaultdict(float)
    inside: dict = defaultdict(lambda: defaultdict(float))
    out = {"total_s": sum(r.seconds for r in roots), "other_s": 0.0, **dict.fromkeys(CHARGED, 0)}
    seen = set()
    children: dict = defaultdict(list)
    for s in recorded(t0=min(r.start for r in roots)) if spans is None else spans:
        children[s.parent].append(s)
    todo = deque((root, None, 0) for root in roots)  # span, the term it lies in, depth in it
    while todo:
        s, term, depth = todo.popleft()
        seen.add(s.id)
        for key in CHARGED:
            out[key] += getattr(s, key)
        if term is not None:
            if depth == 1:
                inside[term][s.name] += s.seconds
        elif s.name in TERMS:
            term = s.name
            terms[term] += s.seconds
        else:
            out["other_s"] += s.seconds - sum(c.seconds for c in children[s.id])
        todo.extend((c, term, depth + 1 if term else 0) for c in children[s.id])
    missed = [m for m in list(_modules) if m.cache == "miss" and m.span in seen]
    out["missed"] = [(m.name, m.compile_s) for m in sorted(missed, key=lambda m: -m.compile_s)]
    out["behind"] = [
        (s.attrs.get("level"), s.seconds)
        for s in (recorded() if spans is None else spans)
        if s.name == BEHIND and any(s.thread != r.thread and r.start < s.end <= r.end for r in roots)
    ]
    return {**out, "terms": dict(terms), "inside": {k: dict(v) for k, v in inside.items()}}


def _short(name: str) -> str:
    return name.rsplit("/", 1)[-1]


def line(title: str, b: dict, gauges: Optional[dict] = None) -> str:
    """``[time] level 3: 1.80 s = prune 0.11 + ... + save 0.08 (wait 0.00,
    barrier 0.00, fetch 0.07) + other 0.03; wrote level 2 behind, 0.27 s;
    traced 0.0 s, lowered 0.0 s, compiled 0 modules in 0.0 s (0 read from the
    cache in 0.0 s; 0 missed)``, then ``; name value`` for each of ``gauges``.
    A term is named only where its span ran: ``load`` in a resumed level; of
    the missed modules, the three longest."""
    parts = []
    for name in (n for n in TERMS if n in b["terms"]):
        part = f"{_short(name)} {b['terms'][name]:.2f}"
        if name in b["inside"]:
            split = ", ".join(f"{_short(k)} {v:.2f}" for k, v in b["inside"][name].items())
            part += f" ({split})"
        parts.append(part)
    return (
        f"[time] {title}: {b['total_s']:.2f} s = "
        + " + ".join(parts + [f"other {b['other_s']:.2f}"])
        + "".join(f"; wrote level {level} behind, {s:.2f} s" for level, s in b["behind"])
        + f"; traced {b['trace_s']:.1f} s, lowered {b['lower_s']:.1f} s, compiled "
        f"{b['compiles']} modules in {b['compile_s']:.1f} s ({b['cache_hits']} read from the "
        f"cache in {b['cache_read_s']:.1f} s; {b['cache_misses']} missed"
        + (": " if b["missed"] else "")
        + ", ".join(f"{name} {s:.1f}" for name, s in b["missed"][:3])
        + ")"
        + "".join(f"; {name} {value:g}" for name, value in (gauges or {}).items())
    )


TIMING_COLUMNS = (
    ["level", "density", "total_s"]
    + [f"{_short(n)}_s" for n in TERMS if n.startswith(("level/", "epoch/"))]
    + ["other_s"]
    + [n.replace("/", "_") + "_s" for n in _CKPT]
    + ["compiles", "compile_s", "trace_s", "lower_s"]
    + ["cache_hits", "cache_misses", "cache_read_s", "miss_compile_s"]
)


def timing_row(level: Span, b: dict) -> dict:
    """The ``level_timing.csv`` row of one level, keyed by ``TIMING_COLUMNS``."""
    row = dict.fromkeys(TIMING_COLUMNS, 0.0)
    row.update({k: b[k] for k in ("total_s", "other_s", *CHARGED)})
    row.update(level=level.attrs.get("level"), density=level.attrs.get("density"))
    row.update({f"{_short(n)}_s": s for n, s in b["terms"].items()})
    for split in b["inside"].values():
        for name in set(split) & set(_CKPT):
            row[name.replace("/", "_") + "_s"] += split[name]
    row[BEHIND.replace("/", "_") + "_s"] += sum(s for _, s in b["behind"])
    return row


def setup_roots() -> list[Span]:
    """The newest ``harness/init`` and the ``setup/`` spans that closed
    between the one before it and it."""
    spans = recorded()
    inits = [i for i, s in enumerate(spans) if s.name == "harness/init"]
    lo = inits[-2] + 1 if len(inits) > 1 else 0
    return [s for s in spans[lo : inits[-1]] if s.name.startswith("setup/")] + [spans[inits[-1]]]


def first_epoch_roots() -> Optional[list[Span]]:
    """``setup_roots()``, the first ``level/setup`` and the first ``epoch``
    since the newest ``harness/init``: process start to the end of the first
    epoch, at whatever level it ran. Nothing until that epoch has closed."""
    roots = setup_roots()
    later = recorded(t0=roots[-1].end)
    firsts = [next((s for s in later if s.name == name), None) for name in ("level/setup", "epoch")]
    return None if None in firsts else roots + firsts


def start_profile(directory: str | Path) -> None:
    """Start a profiler session that writes under ``directory``. Python
    frames are left out: the ``tp/`` spans say where the host is, and the
    Python tracer's own cost would fill the gaps it is there to show."""
    global _profiling
    if not _profiling:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(directory), profiler_options=options)
        _profiling = True


def stop_profile() -> None:
    """Stop the session ``start_profile`` started; nothing where none runs."""
    global _profiling
    if _profiling:
        _profiling = False
        jax.profiler.stop_trace()
