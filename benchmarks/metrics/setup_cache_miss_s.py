"""Seconds of set-up spent compiling modules that the persistent cache was
asked for and did not hold: the program's record by module
(``utils/tracing.py::modules``), those that left the backend before the window
opened with ``cache == "miss"``. 0 on a warm machine, so a ledger line says
whether ``setup_s`` moved with the cache or with the code. A program without
the record has nothing to read."""

import importlib


def read(obs):
    try:
        tracing = importlib.import_module("turboprune_tpu.utils.tracing")
    except ImportError:
        return None
    if not hasattr(tracing, "modules"):
        return None
    t1 = obs["window"][0]
    return sum(m.compile_s for m in tracing.modules(t1 - obs["setup_s"], t1) if m.cache == "miss")
