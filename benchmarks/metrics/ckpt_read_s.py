"""Median over the window's levels of the seconds in the program's
``ckpt/read`` spans (Orbax restores): the level's load and the rewind's read
together."""

from benchmarks import program_spans


def read(obs):
    return program_spans.per_level_median(obs, "ckpt/read")
