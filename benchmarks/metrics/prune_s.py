"""Median span of driver.prune_level (prune and rewind) over the levels of
the window. Nothing to read in a window that prunes nothing."""

from statistics import median


def read(obs):
    spans = obs["spans"].named("prune_level", *obs["window"])
    return median(s.seconds for s in spans) if spans else None
