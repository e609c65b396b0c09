"""Median over the window's epochs of the program's ``epoch/log`` span: the
sparsity read, the metrics row, wandb and the console line after each eval."""

from statistics import median

from benchmarks import program_spans


def read(obs):
    spans = program_spans.recorded("epoch/log", *obs["window"])
    return 1e3 * median(s.seconds for s in spans) if spans else None
