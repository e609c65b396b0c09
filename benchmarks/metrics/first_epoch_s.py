"""The first ``epoch`` span of this run's process: its train and eval programs
traced, lowered, compiled or read from the cache, and run once, with whatever
the job does inside that epoch (the followed epoch's fetches) as the span's
own time."""

from benchmarks import program_spans


def first_epoch(obs):
    """The span, or None where no epoch closed before the window opened."""
    t1 = obs["window"][0]
    epochs = program_spans.recorded("epoch", t1 - obs["setup_s"], t1)
    return min(epochs, key=lambda s: s.start) if epochs else None


def read(obs):
    epoch = first_epoch(obs)
    return None if epoch is None else epoch.seconds
