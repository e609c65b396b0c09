"""The sparse-expert hybrid on the normal path: a two-level IMP ladder through
``run_experiment.main`` on the tiny preset, as one chip of two holds it. Its
level-1 mask is the numpy magnitude oracle's over stacked kernels and plain
ones alike, the router stays unmasked, the epochs' rows carry the step
counters, and an epoch reads the masks' count no more often than before.
A file of its own, so that it gets a worker of the tier-1 run to itself."""

from unittest import mock

import jax
import numpy as np
import pandas as pd
import pytest
from test_nemotron_h import TINY

from benchmarks import correct
from turboprune_tpu.ops import masking, moe

LADDER = [
    *TINY,
    "experiment_params.epochs_per_level=2",
    "pruning_params.target_sparsity=0.2",  # levels 0 and 1
    "optimizer_params.lr=0.05",  # the entry config's rate is sized for a 4,096-wide head
]


@pytest.fixture(scope="module")
def ladder(tmp_path_factory):
    import run_experiment
    from turboprune_tpu import driver
    from turboprune_tpu.harness import PruningHarness
    from turboprune_tpu.utils import tracing

    held = {}

    class Kept(PruningHarness):
        def __init__(self, *a, **k):
            held["harness"] = self
            super().__init__(*a, **k)

        def train_epoch(self):
            before = tracing.gauges().get("mask_reads", 0)
            out = super().train_epoch()
            held.setdefault("epoch_mask_reads", []).append(tracing.gauges().get("mask_reads", 0) - before)
            return out

    base = tmp_path_factory.mktemp("moe")
    argv = ["--config-name=nemotron3_super_imp", f"experiment_params.base_dir={base}", *LADDER]
    with mock.patch.object(driver, "PruningHarness", Kept):
        assert run_experiment.main(argv) == 0
    return held


def test_the_ladder_trains_prunes_and_rewinds(ladder):
    h = ladder["harness"]
    summary = pd.read_csv(next(h.metrics.expt_dir.glob("metrics/*_summary.csv")))
    assert list(summary["level"]) == [0, 1]
    assert summary["sparsity"].iloc[1] == pytest.approx(20.0, abs=0.01)
    assert h.model.share.of(h.model.cfg)["experts_here"] == 8  # rank 1 of 2: experts 8-15
    rows = pd.read_csv(next(h.metrics.expt_dir.glob("metrics/level_wise_metrics/level_1_metrics.csv")))
    assert len(rows) == 2 and set(moe.COUNTERS) <= set(rows.columns)
    # 128 tokens a step choose 4 of 16 experts; 8 are held: about 256 pairs a step, 4 steps.
    assert (rows["moe_dropped_pairs"] == 0).all() and (rows["moe_pairs"].between(4 * 128, 4 * 384)).all()
    assert (rows["moe_load_max"] >= rows["moe_pairs"] / 8).all()
    assert ladder["epoch_mask_reads"] == [0, 0, 0, 0]  # the counters ride the epoch's one fetch


def test_the_level_1_mask_is_the_numpy_magnitude_oracles(ladder):
    h = ladder["harness"]
    before = h.ckpts.load_level(0, h.state)
    want = correct.magnitude_oracle(before["params"], before["masks"], 0.8)
    got = correct.flat_masks(h.state.masks)
    assert got.size == want.size and int((~want).sum()) == int((1.0 - 0.8) * want.size)
    np.testing.assert_array_equal(got, want)
    mixer = h.state.masks["layers_0"]["mixer"]
    assert mixer["router"] == {"weight": None, "bias": None} and h.state.masks["embedding"] is None
    assert mixer["experts"]["kernel_up"].shape == (8, 32, 48) and not bool(mixer["experts"]["kernel_up"].all())
    table = masking.layerwise_sparsity(h.state.masks)
    assert {f"layers_0/mixer/experts/kernel_down[{e}]" for e in range(8)} <= set(table)
    assert len(table) == 2 * 8 + 11 and sum(masking.kept_counts(h.state.masks)) == int(want.sum())


def test_the_weights_rewound_to_init_and_trained_on(ladder):
    h = ladder["harness"]
    init = h.ckpts.load_model("model_init", h.state)["params"]
    moved = {
        masking.path_name(p): float(np.abs(np.asarray(a) - np.asarray(b)).max())
        for (p, a), b in zip(jax.tree_util.tree_leaves_with_path(h.state.params), jax.tree.leaves(init))
    }
    assert moved.pop("layers_0/mixer/router/bias") == 0.0  # no gradient reaches the selection bias
    # Two heads' decay and step bias get gradients of 1e-5 through an out_proj
    # that starts small: eight steps move them by less than float32 holds.
    still = {k for k, v in moved.items() if not v > 0}
    assert still <= {"layers_1/mixer/A_log", "layers_1/mixer/dt_bias"}, still
    assert moved["layers_0/mixer/router/weight"] > 0  # trained, though never masked
