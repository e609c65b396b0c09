"""The program's ``harness/init`` span: mesh, model, loaders (synthetic data
is made here), the initial state and the eval wrappers. It lies before the
window; of several in one process, the last that closed before it opened."""

from benchmarks import program_spans


def read(obs):
    spans = program_spans.recorded("harness/init", t1=obs["window"][0])
    return spans[-1].seconds if spans else None
