"""Median wall-clock of the whole levels inside the window, from the previous
level's save to this one's. Host clock; only where the window is cut in
levels."""

from statistics import median


def read(obs):
    if obs.get("unit") != "level":
        return None
    b = obs["boundaries"]
    return median(b[i + 1] - b[i] for i in range(len(b) - 1))
