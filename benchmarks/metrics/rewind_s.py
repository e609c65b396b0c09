"""Median over the window's levels of the program's ``level/rewind`` span:
``reset_weights`` after the prune, which for IMP reads ``model_init`` back."""

from benchmarks import program_spans


def read(obs):
    return program_spans.per_level_median(obs, "level/rewind")
