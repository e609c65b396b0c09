"""Wall-clock from the end of the first ``epoch`` span to the window's
opening: the warm-up epochs or levels that follow it, and the job's own work
before it opens the window (the ladder's prune warm-up)."""

from benchmarks.metrics.first_epoch_s import first_epoch


def read(obs):
    epoch = first_epoch(obs)
    return None if epoch is None else obs["window"][0] - epoch.end
