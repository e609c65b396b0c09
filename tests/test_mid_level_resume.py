"""Epoch-granular resume inside a level (``checkpoint_every_epochs``). Moved
out of ``test_harness.py``, whose helpers it runs with, so that the tier-1
run's longest file is not its longest pole: the two spread over two workers."""

import pandas as pd
import pytest
from test_harness import _cfg

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

    def test_bit_identical_resume_after_preemption(self, tmp_path):
        from pathlib import Path

        from turboprune_tpu.harness import PruningHarness

        captured = {}

        class Capturing(PruningHarness):
            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                captured["h"] = self

        # Uninterrupted reference run.
        expt_a, _ = run(self._cfg(tmp_path / "a"), harness_cls=Capturing)
        want = self._fingerprint(captured["h"])

        # Interrupted run: die right after the level-1 epoch-1 mid save.
        class Preempted(Capturing):
            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                orig = self.ckpts.save_mid_level

                def dying(level, epoch, state, meta):
                    orig(level, epoch, state, meta)
                    if (level, epoch) == (1, 1):
                        raise KeyboardInterrupt("simulated preemption")

                self.ckpts.save_mid_level = dying

        cfg_b = self._cfg(tmp_path / "b")
        with pytest.raises(KeyboardInterrupt):
            run(cfg_b, harness_cls=Preempted)
        expt_b = captured["h"].expt_dir
        meta = captured["h"].ckpts.peek_mid_level()
        assert meta["level"] == 1 and meta["epoch"] == 1

        # Resume through the production path (resume_experiment config).
        cfg_r = self._cfg(
            tmp_path / "b",
            "experiment_params.resume_experiment=true",
            "experiment_params.resume_experiment_stuff.resume_expt_name="
            + Path(expt_b).name,
            "experiment_params.resume_experiment_stuff.resume_level=1",
        )
        expt_r, summaries = run(cfg_r, harness_cls=Capturing)
        assert expt_r == expt_b
        assert len(summaries) == 1
        got = self._fingerprint(captured["h"])
        assert got == want  # bit-identical to the uninterrupted run

        # The level CSV and summary must cover the WHOLE level: the
        # pre-preemption epoch rows ride in the mid-save header, so the
        # resumed run's finish_level sees epochs 0..4, not just 2..4.
        lv = pd.read_csv(
            Path(expt_b) / "metrics" / "level_wise_metrics" / "level_1_metrics.csv"
        )
        assert list(lv["epoch"]) == [0, 1, 2, 3, 4]
        assert summaries[0]["max_test_acc"] == pytest.approx(
            float(lv["test_acc"].max())
        )

    def test_no_mid_checkpoint_when_disabled(self, tmp_path):
        cfg = _cfg(tmp_path)  # checkpoint_every_epochs defaults to 0
        from pathlib import Path

        expt_dir, _ = run(cfg)
        assert not (Path(expt_dir) / "checkpoints" / "mid_level").exists()
