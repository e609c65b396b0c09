"""Median over the window's levels of the program's ``level/setup`` span: the
optimizer's re-initialisation and replication, the optimizer rewind, the
level's console panel and the plan entry, before the first epoch."""

from benchmarks import program_spans


def read(obs):
    seconds = program_spans.per_level_median(obs, "level/setup")
    return None if seconds is None else 1e3 * seconds
