"""Level-granular resume of a run that goes on for more than one level: the
resumed process reads from disk once what a continuous run never reads, and
from then on behaves as the process that wrote it. A file of its own beside
``test_harness.py`` (whose helpers it runs with), so that the two spread over
two workers of the tier-1 run."""

import jax
import numpy as np
import pytest
from test_harness import (
    _cfg,
    _counted_on_disk,
    _killed_and_resumed,
    _mask_reads,
    _named,
    _reports_the_masks_on_disk,
    _traced_run,
)

from turboprune_tpu.harness import CyclicPruningHarness


class TestResumedRunRewindsAgain:
    """A run killed after level 0 and resumed at level 1 rewinds twice: the
    first rewind reads the target from disk, where Orbax restores it onto the
    devices of the state it is shown; the second takes what the process kept.
    That has to be a host tree: a device tree is aliased by the level's
    ``replicate`` and deleted by the step that donates its state.
    ``test_harness.py::TestLevelHandOff`` has the plain ``imp`` run; here
    ``wr_opt`` rewinds the momentum the same way, and ``cyclic`` splits a
    level's 3 epochs into cycles of 1 + 1, so its loader is not where 3 a
    level would put it."""

    FLAVOURS = {
        "wr_opt": (
            (
                "pruning_params.training_type=wr",
                "pruning_params.rewind_epoch=0",
                "pruning_params.rewind_optimizer=true",
            ),
            None,
            3,  # reads: model_level_0, model_rewind, optimizer_rewind
        ),
        "cyclic": (
            (
                "cyclic_training.num_cycles=2",
                "cyclic_training.strategy=constant",
                "experiment_params.epochs_per_level=3",
            ),
            "cyclic",
            2,  # model_level_0, model_init
        ),
    }

    @pytest.fixture(scope="class", params=list(FLAVOURS))
    def runs(self, request, tmp_path_factory):
        extra, harness, reads = self.FLAVOURS[request.param]
        harness_cls = CyclicPruningHarness if harness else None
        whole = _traced_run(_cfg(tmp_path_factory.mktemp(request.param), *extra), harness_cls)
        killed, resumed = _killed_and_resumed(
            tmp_path_factory.mktemp(request.param + "_cut"), 0, *extra, harness_cls=harness_cls
        )
        return {"whole": whole, "killed": killed, "resumed": resumed, "reads": reads}

    def test_the_target_comes_from_disk_once_and_then_from_memory(self, runs):
        killed, resumed = runs["killed"], runs["resumed"]
        assert [s.attrs["level"] for s in _named(killed, "level")] == [0]
        assert not _named(killed, "level/rewind") and not _named(killed, "ckpt/read")
        assert [s.attrs["level"] for s in _named(resumed, "level")] == [1, 2]
        assert [s.attrs["level"] for s in _named(resumed, "level/load")] == [1]
        rewinds = _named(resumed, "level/rewind")
        assert [(s.attrs["level"], s.attrs["source"]) for s in rewinds] == [
            (1, "disk"),
            (2, "resident"),
        ]
        # Every read lies in the resumed level; level 2 reads nothing back.
        assert [s.attrs["level"] for s in _named(resumed, "ckpt/read")] == [1] * runs["reads"]

    def test_killed_and_resumed_ends_where_the_continuous_run_ends(self, runs):
        assert runs["resumed"]["fingerprint"] == runs["whole"]["fingerprint"]
        assert runs["killed"]["fingerprint"] != runs["whole"]["fingerprint"]

    def test_every_checkpoint_holds_what_the_continuous_run_wrote(self, runs):
        whole, cut = runs["whole"]["written"], runs["resumed"]["written"]
        assert "checkpoints/model_level_2" in whole and whole == cut

    def test_what_the_resumed_process_keeps_is_on_the_host(self, runs):
        held = runs["resumed"]["harness"].ckpts._resident
        assert len(held) == runs["reads"] - 1  # all it read but model_level_0
        assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(held))

    @pytest.mark.parametrize("level", [0, 1, 2])
    def test_every_reported_sparsity_is_that_of_the_checkpointed_masks(self, runs, level):
        """The carried count (``PruningHarness.mask_count``) in every epoch
        row, ``final_sparsity`` and ``achieved_density``: of the continuous
        run, cyclic or not, and of the resumed one, whose first level holds
        no carried count and reads the masks it loaded."""
        sparsity = _reports_the_masks_on_disk(runs["whole"], level)
        if level:
            assert _reports_the_masks_on_disk(runs["resumed"], level) == sparsity
        else:  # the killed run returned no summaries
            assert {r["sparsity"] for r in runs["killed"]["rows"]} == {sparsity} == {0.0}

    @pytest.mark.parametrize(
        "which, level, reads",
        [
            ("whole", 0, (1, 1, 0)),  # the masks it was built with, in level 0's set-up
            ("whole", 1, (1, 0, 0)),  # the prune's ``after``; ``before`` is carried
            ("whole", 2, (1, 0, 0)),
            ("resumed", 1, (2, 0, 0)),  # ``level/load`` wrote masks: ``before`` is a read
            ("resumed", 2, (1, 0, 0)),
        ],
    )
    def test_a_level_reads_the_masks_once_and_no_epoch_or_cycle_reads_them(self, runs, which, level, reads):
        assert _mask_reads(runs[which], level) == reads
        assert _counted_on_disk(runs[which], level)[0] == pytest.approx([0.0, 20.0, 36.0][level], abs=0.05)
