"""The block-diffusion sparse-expert transformer on the normal path: a
two-level IMP ladder through ``run_experiment.main`` on the tiny preset, as
one chip of two holds it. It trains on noised batches, prunes every expert's
every kernel with the projections, rewinds, and its epochs' rows carry the
step counters. A file of its own, so that it gets a worker of the tier-1 run
to itself."""

from unittest import mock

import jax
import numpy as np
import pandas as pd
import pytest
from test_sdar import TINY

from benchmarks import correct
from turboprune_tpu.ops import masking


@pytest.fixture(scope="module")
def ladder(tmp_path_factory):
    import run_experiment
    from turboprune_tpu import driver
    from turboprune_tpu.harness import PruningHarness

    held = {}

    class Kept(PruningHarness):
        def __init__(self, *a, **k):
            held["harness"] = self
            super().__init__(*a, **k)

    base = tmp_path_factory.mktemp("sdar")
    argv = [
        "--config-name=sdar_30b_a3b_imp", f"experiment_params.base_dir={base}", *TINY,
        "experiment_params.epochs_per_level=2", "pruning_params.target_sparsity=0.2",
        "optimizer_params.lr=0.05",  # the entry config's rate is sized for a 2,048-wide head
    ]  # fmt: skip
    with mock.patch.object(driver, "PruningHarness", Kept):
        assert run_experiment.main(argv) == 0
    return held


def test_the_ladder_trains_prunes_and_rewinds(ladder):
    h = ladder["harness"]
    summary = pd.read_csv(next(h.metrics.expt_dir.glob("metrics/*_summary.csv")))
    assert list(summary["level"]) == [0, 1]
    assert summary["sparsity"].iloc[1] == pytest.approx(20.0, abs=0.01)
    rows = pd.read_csv(next(h.metrics.expt_dir.glob("metrics/level_wise_metrics/level_1_metrics.csv")))
    assert len(rows) == 2 and set(h.model.counters) <= set(rows.columns)
    # 128 tokens a step are 256 rows, each choosing 4 of 16 experts, 8 held, 2 layers, 4 steps.
    assert (rows["moe_dropped_pairs"] == 0).all() and (rows["moe_rounds"] == 2 * 4).all()
    assert rows["moe_pairs"].between(4 * 2 * 256, 4 * 2 * 768).all()
    assert rows["masked_targets"].between(4 * 32, 4 * 96).all()  # a half of 128 tokens a step, about
    assert h.data_gauges["rows_per_step"] == 256 and h.data_gauges["block_length"] == 4
    assert np.isfinite(rows["train_loss"]).all() and np.isfinite(rows["test_loss"]).all()


def test_the_level_1_mask_is_the_numpy_magnitude_oracles(ladder):
    h = ladder["harness"]
    before = h.ckpts.load_level(0, h.state)
    want = correct.magnitude_oracle(before["params"], before["masks"], 0.8)
    got = correct.flat_masks(h.state.masks)
    assert got.size == want.size and int((~want).sum()) == int((1.0 - 0.8) * want.size)
    np.testing.assert_array_equal(got, want)
    mlp = h.state.masks["layers_0"]["mlp"]
    assert mlp["router"] == {"weight": None} and h.state.masks["embedding"] is None
    assert mlp["experts"]["kernel_gate"].shape == (8, 32, 24) and not bool(mlp["experts"]["kernel_gate"].all())
    table = masking.layerwise_sparsity(h.state.masks)
    assert len(table) == 2 * (4 + 3 * 8) + 1


def test_the_weights_rewound_to_init_and_trained_on(ladder):
    h = ladder["harness"]
    init = h.ckpts.load_model("model_init", h.state)["params"]
    moved = {
        masking.path_name(p): float(np.abs(np.asarray(a) - np.asarray(b)).max())
        for (p, a), b in zip(jax.tree_util.tree_leaves_with_path(h.state.params), jax.tree.leaves(init))
    }
    assert all(v > 0 for v in moved.values()), {k for k, v in moved.items() if not v > 0}
