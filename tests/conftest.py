"""Test harness: run everything on a virtual 8-device CPU mesh.

This is the distributed-test strategy SURVEY.md §4 prescribes (the reference
had no tests at all): ``xla_force_host_platform_device_count`` simulates an
8-device mesh on CPU, covering SPMD data-parallel semantics (sharding, psum,
replicated-prune determinism) without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (import after env setup)

# The persistent compilation cache stays off in tests: the entry points a
# test calls place one (utils/compile_cache.py), and a suite whose results
# depend on what an earlier run left on disk cannot be trusted either way.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    return jax.devices()

# graftsan: opt-in runtime concurrency sanitizer fixture (asserts zero
# observed lock-order cycles at teardown). Re-exported here so test files
# get it without a root-level pytest_plugins declaration.
from turboprune_tpu.analysis.pytest_plugin import graftsan  # noqa: E402, F401
