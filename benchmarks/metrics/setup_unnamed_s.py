"""``setup_s`` less what the program's spans name of it: the union of the
spans of this run that began on the main thread before the window opened,
each clipped at its opening. What is left lies before ``run_experiment.main``
(interpreter start, the benchmark's imports and its ``jax.devices()``) or
between two spans: the number for what the measurement cannot see."""

from benchmarks import program_spans


def read(obs):
    t1 = obs["window"][0]
    spans = [s for s in program_spans.recorded(None, t0=t1 - obs["setup_s"]) if s.start < t1]
    inits = [s for s in spans if s.name == "harness/init"]
    if not inits:
        return None
    named, reached = 0.0, float("-inf")
    for s in sorted((s for s in spans if s.thread == inits[-1].thread), key=lambda s: s.start):
        end = min(s.end, t1)
        if end > reached:
            named += end - max(s.start, reached)
            reached = end
    return obs["setup_s"] - named
