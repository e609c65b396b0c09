"""Median over the window's levels of the seconds in the program's
``ckpt/fetch`` (device to host, mask packing) and ``ckpt/write`` (Orbax save
and its wait) spans together."""

from benchmarks import program_spans


def read(obs):
    return program_spans.per_level_median(obs, "ckpt/fetch", "ckpt/write")
