"""The hybrid language model on the normal path: a two-level IMP ladder
through ``run_experiment.main`` on the tiny preset. Its level-1 mask is the
numpy magnitude oracle's, and a run killed after level 0 and resumed ends
where the continuous run ends, reading from disk once what a continuous run
never reads (what test_level_resume.py asks of the ResNet). A file of its
own, so that it gets a worker of the tier-1 run to itself."""

from unittest import mock

import jax
import numpy as np
import pytest
import test_harness
from test_granite import TINY
from test_harness import _killed_and_resumed, _named, _traced_run

from benchmarks import correct
from turboprune_tpu.config import compose

LADDER = [
    *TINY,
    "experiment_params.epochs_per_level=2",
    "pruning_params.target_sparsity=0.2",  # levels 0 and 1
]


def _cfg(tmp_path, *extra):
    return compose(
        "granite_h_micro_imp", [f"experiment_params.base_dir={tmp_path}", *LADDER, *extra]
    )


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    import run_experiment
    from turboprune_tpu import driver

    # The continuous run goes through the entry point itself; the harness it
    # builds is kept by handing _traced_run a ``run`` that is ``main``.
    def through_main(cfg, harness_cls=None):
        argv = ["--config-name=granite_h_micro_imp", f"experiment_params.base_dir={cfg.experiment_params.base_dir}", *LADDER]
        with mock.patch.object(driver, "PruningHarness", harness_cls):
            assert run_experiment.main(argv) == 0
        return None, None

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(test_harness, "_cfg", _cfg)
        with mock.patch.object(test_harness, "run", through_main):
            whole = _traced_run(_cfg(tmp_path_factory.mktemp("lm")))
        killed, resumed = _killed_and_resumed(tmp_path_factory.mktemp("lm_cut"), 0)
    return {"whole": whole, "killed": killed, "resumed": resumed}


def test_the_ladder_trains_prunes_and_rewinds(runs):
    whole = runs["whole"]
    assert [s.attrs["level"] for s in _named(whole, "level")] == [0, 1]
    assert [s.attrs["source"] for s in _named(whole, "level/rewind")] == ["resident"]
    assert not _named(whole, "ckpt/read")
    rows = whole["timing"]
    assert list(rows["level"]) == [0, 1] and (rows["tokens_per_step"] == 128).all()
    assert (rows["target_tokens_per_step"] == whole["harness"].data_gauges["target_tokens_per_step"]).all()
    assert whole["harness"].data_gauges["docs_per_sequence"] > 1


def test_the_level_1_mask_is_the_numpy_magnitude_oracles(runs):
    h = runs["whole"]["harness"]
    before = h.ckpts.load_level(0, h.state)
    want = correct.magnitude_oracle(before["params"], before["masks"], 0.8)
    got = correct.flat_masks(h.state.masks)
    assert got.size == want.size and int((~want).sum()) == int(0.2 * want.size)
    np.testing.assert_array_equal(got, want)
    # The embedding, the convolutions and the norms carry no mask.
    assert jax.tree.structure(h.state.masks) != jax.tree.structure(h.state.params)


def test_the_weights_rewound_to_init_and_trained_on(runs):
    h = runs["whole"]["harness"]
    init = h.ckpts.load_model("model_init", h.state)["params"]
    moved = [
        float(np.abs(np.asarray(a) - np.asarray(b)).max())
        for a, b in zip(jax.tree.leaves(h.state.params), jax.tree.leaves(init))
    ]
    assert max(moved) > 0 and np.isfinite(moved).all()


def test_killed_and_resumed_ends_where_the_continuous_run_ends(runs):
    killed, resumed = runs["killed"], runs["resumed"]
    assert [s.attrs["level"] for s in _named(killed, "level")] == [0]
    assert [s.attrs["level"] for s in _named(resumed, "level")] == [1]
    assert [s.attrs["level"] for s in _named(resumed, "level/load")] == [1]
    assert [s.attrs["source"] for s in _named(resumed, "level/rewind")] == ["disk"]
    assert resumed["fingerprint"] == runs["whole"]["fingerprint"]
    assert killed["fingerprint"] != runs["whole"]["fingerprint"]
    assert "checkpoints/model_level_1" in resumed["written"]
    assert resumed["written"] == runs["whole"]["written"]
