"""Epoch-granular resume inside a level (``checkpoint_every_epochs``). Moved
out of ``test_harness.py``, whose helpers it runs with, so that the tier-1
run's longest file is not its longest pole: the two spread over two workers."""

import pandas as pd
import pytest
from test_harness import _cfg, _mask_reads, _reports_the_masks_on_disk, _traced_run

from turboprune_tpu.config.compose import compose
from turboprune_tpu.driver import run


class TestMidLevelResume:
    """Epoch-granular checkpointing (beyond-reference): a run preempted
    mid-level must resume at the saved epoch and finish BIT-IDENTICAL to an
    uninterrupted run — params, masks, batch_stats and opt_state all match,
    which also proves the loader's shuffle stream was restored."""

    def _cfg(self, base, *extra):
        return compose(
            "cifar10_imp",
            overrides=[
                f"experiment_params.base_dir={base}",
                "dataset_params.dataloader_type=synthetic",
                # Two scanned steps of batch 8 an epoch on two devices: a
                # scanned ResNet18 step of batch 16 takes XLA's CPU backend
                # 4-5 s, and this test trains 45 of them over its three runs.
                "dataset_params.total_batch_size=8",
                "dataset_params.synthetic_num_train=16",
                "dataset_params.synthetic_num_test=8",
                "experiment_params.num_devices=2",
                "experiment_params.epochs_per_level=5",
                "experiment_params.checkpoint_every_epochs=2",
                # target SPARSITY 0.2 -> density ladder [1.0, 0.8]: exactly
                # two levels (0.8 would mean a density floor of 0.2 = EIGHT
                # levels at prune_rate 0.2).
                "pruning_params.target_sparsity=0.2",
                "model_params.model_name=resnet18",
                *extra,
            ],
        )

    @staticmethod
    def _fingerprint(harness):
        from turboprune_tpu.parallel.multihost import tree_fingerprint

        s = harness.state
        return tree_fingerprint(
            {
                "params": s.params,
                "masks": s.masks,
                "batch_stats": s.batch_stats,
                "opt_state": s.opt_state,
            }
        )

    @pytest.fixture(scope="class")
    def runs(self, tmp_path_factory):
        """Three runs of one seed: uninterrupted; preempted right after the
        level-1 epoch-1 mid save; resumed through the production path."""
        from turboprune_tpu.harness import PruningHarness

        tmp = tmp_path_factory.mktemp("mid")
        whole = _traced_run(self._cfg(tmp / "a"))

        class Preempted(PruningHarness):
            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                orig = self.ckpts.save_mid_level

                def dying(level, epoch, state, meta):
                    orig(level, epoch, state, meta)
                    if (level, epoch) == (1, 1):
                        raise KeyboardInterrupt("simulated preemption")

                self.ckpts.save_mid_level = dying

        killed = _traced_run(self._cfg(tmp / "b"), Preempted)
        assert killed["summaries"] is None  # the interrupt ended it
        slot = killed["harness"].ckpts.peek_mid_level()
        resumed = _traced_run(
            self._cfg(
                tmp / "b",
                "experiment_params.resume_experiment=true",
                "experiment_params.resume_experiment_stuff.resume_expt_name="
                + killed["dir"].name,
                "experiment_params.resume_experiment_stuff.resume_level=1",
            )
        )
        return {"whole": whole, "killed": killed, "slot": slot, "resumed": resumed}

    def test_bit_identical_resume_after_preemption(self, runs):
        want = self._fingerprint(runs["whole"]["harness"])
        assert (runs["slot"]["level"], runs["slot"]["epoch"]) == (1, 1)
        resumed = runs["resumed"]
        assert resumed["dir"] == runs["killed"]["dir"]
        summaries = resumed["summaries"]
        assert len(summaries) == 1
        got = self._fingerprint(resumed["harness"])
        assert got == want  # bit-identical to the uninterrupted run

        # The level CSV and summary must cover the WHOLE level: the
        # pre-preemption epoch rows ride in the mid-save header, so the
        # resumed run's finish_level sees epochs 0..4, not just 2..4.
        lv = pd.read_csv(
            resumed["dir"] / "metrics" / "level_wise_metrics" / "level_1_metrics.csv"
        )
        assert list(lv["epoch"]) == [0, 1, 2, 3, 4]
        assert summaries[0]["max_test_acc"] == pytest.approx(
            float(lv["test_acc"].max())
        )

    def test_a_re_entered_level_reports_the_sparsity_of_its_checkpointed_masks(self, runs):
        """Rows of before the preemption ride in the slot's header, those
        after it take the count the re-entry read: all five are the count of
        ``model_level_1``'s masks, as the uninterrupted run's are."""
        resumed = runs["resumed"]
        sparsity = _reports_the_masks_on_disk(resumed, 1)
        assert sparsity == _reports_the_masks_on_disk(runs["whole"], 1)
        assert [r["epoch"] for r in resumed["rows"]] == [2, 3, 4]
        lv = pd.read_csv(
            resumed["dir"] / "metrics" / "level_wise_metrics" / "level_1_metrics.csv",
            float_precision="round_trip",
        )
        assert list(lv["sparsity"]) == [sparsity] * 5

    @pytest.mark.parametrize(
        "which, level, reads",
        [
            ("whole", 0, (1, 1, 0)),
            ("whole", 1, (1, 0, 0)),
            # ``level/load``, the prune and the slot's restore each wrote
            # masks: one read after each, the last in ``level/setup``.
            ("resumed", 1, (3, 1, 0)),
        ],
    )
    def test_each_write_of_the_masks_is_read_once_and_no_epoch_reads_them(self, runs, which, level, reads):
        assert _mask_reads(runs[which], level) == reads

    def test_no_mid_checkpoint_when_disabled(self, tmp_path):
        cfg = _cfg(tmp_path)  # checkpoint_every_epochs defaults to 0
        from pathlib import Path

        expt_dir, _ = run(cfg)
        assert not (Path(expt_dir) / "checkpoints" / "mid_level").exists()
