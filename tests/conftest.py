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


# The driver runs the suite on six workers with ``--dist loadfile``, which hands
# whole files out in collection order; in name order the long files that sort
# late (test_sdar, test_tpu_compile) started last and were the tail the run
# waited for, two to four minutes past an even share (ROADMAP D9). So the files
# over about 100 s of their own go first, longest first (seconds of the builder's
# whole run at PR 45, 559 s down to 51); the rest follow in name order, and a file keeps
# its order.
LONG_FILES = (
    "test_harness", "test_nemotron_h", "test_sdar", "test_level_resume", "test_integration_extra",
    "test_models_masking", "test_mid_level_resume", "test_multiprocess", "test_granite", "test_sparse",
    "test_tpu_compile", "test_chip_smoke", "test_plan", "test_lfm2", "test_nm", "test_checkpoint",
    "test_compact_train", "test_ring", "test_moe_groups", "test_granite_ladder", "test_brumby",
    "test_scan_epoch", "test_flash_causal", "test_ssd", "test_flash_blockdiff", "test_loss_blocks",
    "test_tracing", "test_serve", "test_fleet",
)  # fmt: skip


def pytest_configure(config):
    # xdist's loadfile scheduler sorts the files by how many tests each holds, most first, unless
    # told not to (its ``--no-loadscope-reorder``, which the driver's command does not pass): that
    # undid the order above, and the files of few long tests (test_integration_extra's 4 in 330 s,
    # test_mid_level_resume's 6 in 300 s, test_chip_smoke, test_multiprocess) started last, 1,075 s
    # into a run of 1,427 s, while four workers sat idle (the builder's timed run at PR 45).
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    rank = {name: at for at, name in enumerate(LONG_FILES)}
    items.sort(key=lambda item: rank.get(item.path.stem, len(rank)))
