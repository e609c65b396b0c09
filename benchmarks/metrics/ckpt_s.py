"""Median over the window's levels of the seconds in load_level and
save_level together. Nothing to read where the window is not cut in levels."""

from statistics import median


def read(obs):
    if obs.get("unit") != "level":
        return None
    b = obs["boundaries"]
    per_level = []
    for lo, hi in zip(b, b[1:]):
        per_level.append(
            sum(
                s.seconds
                for name in ("load_level", "save_level")
                for s in obs["spans"].named(name, lo, hi + 1e-3)
            )
        )
    return median(per_level) if per_level else None
