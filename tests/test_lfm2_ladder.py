"""The convolution-hybrid sparse-expert decoder on the normal path: a
two-level IMP ladder through ``run_experiment.main`` on the tiny preset, as
one chip of two holds it. It trains on next-token batches, prunes the dense
layer's 2-D kernels and every expert's every stacked kernel in one ranking,
rewinds, and its epochs' rows carry the step counters. A file of its own, so
that it gets a worker of the tier-1 run to itself."""

from unittest import mock

import jax
import numpy as np
import pandas as pd
import pytest
from test_lfm2 import TINY

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

    base = tmp_path_factory.mktemp("lfm2")
    argv = [
        "--config-name=lfm2_8b_a1b_imp", f"experiment_params.base_dir={base}", *TINY,
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
    # 128 tokens a step, each choosing 4 of 16 experts, 8 held, 2 routed layers, 4 steps.
    assert (rows["moe_dropped_pairs"] == 0).all() and (rows["moe_rounds"] == 2 * 4).all()
    assert rows["moe_pairs"].between(4 * 2 * 128, 4 * 2 * 384).all()
    assert np.isfinite(rows["train_loss"]).all() and np.isfinite(rows["test_loss"]).all()


def test_the_level_1_mask_is_the_numpy_magnitude_oracles(ladder):
    h = ladder["harness"]
    before = h.ckpts.load_level(0, h.state)
    want = correct.magnitude_oracle(before["params"], before["masks"], 0.8)
    got = correct.flat_masks(h.state.masks)
    assert got.size == want.size and int((~want).sum()) == int((1.0 - 0.8) * want.size)
    np.testing.assert_array_equal(got, want)
    dense, routed = h.state.masks["layers_0"]["mlp"], h.state.masks["layers_1"]["mlp"]
    assert routed["router"] == {"weight": None, "bias": None} and h.state.masks["embedding"] is None
    assert h.state.masks["layers_0"]["mixer"]["conv_taps"] is None
    # One ranking over 2-D and stacked kernels: both kinds lost weights.
    assert dense["in_proj"]["kernel"].shape == (32, 96) and not bool(dense["in_proj"]["kernel"].all())
    assert routed["experts"]["kernel_gate"].shape == (8, 32, 24) and not bool(routed["experts"]["kernel_gate"].all())
    table = masking.layerwise_sparsity(h.state.masks)
    assert len(table) == (2 + 2) + (4 + 3 * 8) + (2 + 3 * 8)  # no head of its own: it is the embedding


def test_the_weights_rewound_to_init_and_trained_on(ladder):
    h = ladder["harness"]
    init = h.ckpts.load_model("model_init", h.state)["params"]
    moved = {
        masking.path_name(p): float(np.abs(np.asarray(a) - np.asarray(b)).max())
        for (p, a), b in zip(jax.tree_util.tree_leaves_with_path(h.state.params), jax.tree.leaves(init))
    }
    still = {k for k, v in moved.items() if not v > 0}
    # The selection bias gets no gradient and no decay: it stays where it was.
    assert still == {"layers_1/mlp/router/bias", "layers_2/mlp/router/bias"}, still
