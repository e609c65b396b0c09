"""Where XLA's persistent compilation cache lives.

A cold ResNet50 train step is tens of seconds of compile per executable and
an IMP run builds several; the cache turns a restart's compiles into reads.
Placed by the entry points (``main()`` of run_experiment.py,
run_cyclic_training_experiment.py, run_server.py, chip_smoke.py),
never at import of the package, so a library user keeps their own setting.
"""

from __future__ import annotations

import os
from pathlib import Path

_CHECKOUT = Path(__file__).resolve().parents[2]


def place_compile_cache() -> str:
    """Point JAX at the persistent compile cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` in the environment wins and nothing is set
    in code (JAX reads the variable itself). Otherwise the cache is
    ``<checkout>/.jax_cache``: a fixed path, because the next process only
    hits what it can find again.
    """
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
