"""Seconds of set-up spent tracing Python into jaxprs: the program's own
``trace_s`` (``utils/tracing.py``: each trace charged once, a jit inside a jit
not twice, to the innermost span open on its thread) summed over the spans of
this run that began before the window opened. A span that is still open then
(the ladder's ``level/save``, under which the job warms its prunes) is counted
whole: nothing traces inside a window in which nothing compiles. A program
whose spans carry no such field has nothing to read."""

from benchmarks import program_spans


def read(obs):
    t1 = obs["window"][0]
    spans = [s for s in program_spans.recorded(None, t0=t1 - obs["setup_s"]) if s.start < t1]
    traced = [s.trace_s for s in spans if hasattr(s, "trace_s")]
    return sum(traced) if traced else None
