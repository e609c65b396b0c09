"""The benchmark's own tests run on the CPU, in seconds each:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Nothing here yields a device number.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

# What an earlier run left on disk must not decide a test.
jax.config.update("jax_enable_compilation_cache", False)
