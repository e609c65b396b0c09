"""Driver-level integration tests for the paths BASELINE.json names but the
core suite didn't execute end-to-end (VERDICT r3 items 2/5): the native tpk
loader selected from config, VGG16+SNIP, and DeiT+ERK."""

import numpy as np
import pandas as pd
import pytest

from turboprune_tpu.config.compose import compose
from turboprune_tpu.driver import run


def _overrides(base_dir, *extra):
    return [
        f"experiment_params.base_dir={base_dir}",
        "dataset_params.total_batch_size=16",
        "experiment_params.epochs_per_level=1",
        *extra,
    ]


class TestTpkEndToEnd:
    """Pack synthetic JPEGs into .tpk via the config auto-pack knob and run a
    full driver IMP ladder on it — the reference's FFCV-as-default-path bar
    (/root/reference/utils/dataset.py:409-430)."""

    @pytest.fixture(scope="class")
    def image_root(self, tmp_path_factory):
        from PIL import Image

        root = tmp_path_factory.mktemp("tpkdata")
        rng = np.random.default_rng(0)
        # Class-conditional means so the data is learnable, like
        # data/synthetic.py.
        means = rng.uniform(40, 215, size=(2, 1, 1, 3))
        for split, per_class in (("train", 16), ("val", 8)):
            for c, cls in enumerate(("class_a", "class_b")):
                d = root / split / cls
                d.mkdir(parents=True)
                for i in range(per_class):
                    arr = np.clip(
                        means[c] + rng.normal(0, 25, size=(40, 40, 3)), 0, 255
                    ).astype(np.uint8)
                    Image.fromarray(arr).save(d / f"{i}.jpeg", quality=95)
        return root

    def test_driver_imp_on_tpk(self, image_root, tmp_path):
        cfg = compose(
            "cifar10_imp",
            overrides=_overrides(
                tmp_path,
                "dataset_params.dataloader_type=tpk",
                f"dataset_params.data_root_dir={image_root}",
                "dataset_params.tpk_auto_pack=true",
                "pruning_params.target_sparsity=0.2",
            ),
        )
        expt_dir, summaries = run(cfg)
        # auto-pack wrote the .tpk files next to the ImageFolder splits
        assert (image_root / "train.tpk").exists()
        assert (image_root / "val.tpk").exists()
        assert len(summaries) == 2
        np.testing.assert_allclose(
            [s["density"] for s in summaries], [1.0, 0.8], atol=1e-6
        )
        np.testing.assert_allclose(summaries[1]["achieved_density"], 0.8, atol=5e-4)
        # 32 train images / batch 16 = 2 steps; metrics flowed through
        from pathlib import Path

        lv = pd.read_csv(
            Path(expt_dir) / "metrics" / "level_wise_metrics" / "level_0_metrics.csv"
        )
        assert len(lv) == 1 and np.isfinite(lv["train_loss"]).all()

    def test_missing_tpk_fails_loudly(self, tmp_path):
        cfg = compose(
            "cifar10_imp",
            overrides=_overrides(
                tmp_path,
                "dataset_params.dataloader_type=tpk",
                f"dataset_params.data_root_dir={tmp_path}/nothing_here",
            ),
        )
        with pytest.raises(FileNotFoundError, match="tpk file not found"):
            run(cfg)


class TestVggSnip:
    """BASELINE.json config 3: VGG16 + SNIP one-shot PaI, end to end."""

    def test_vgg16_bn_snip_level(self, tmp_path):
        cfg = compose(
            "cifar10_imp",
            overrides=_overrides(
                tmp_path,
                "dataset_params.dataloader_type=synthetic",
                "dataset_params.synthetic_num_train=32",
                "dataset_params.synthetic_num_test=16",
                "experiment_params.max_steps_per_epoch=2",
                # One device, not the mesh's eight: every device of a
                # replicated state runs SNIP's threshold over VGG16's 134M
                # scores (425 s of this test's 461 on eight). What stays is
                # one lax.top_k of 67M of them, 250 s on a CPU core.
                "experiment_params.num_devices=1",
                "model_params.model_name=vgg16_bn",
                "pruning_params.prune_method=snip",
                "pruning_params.training_type=at_init",
                "pruning_params.target_sparsity=0.5",
            ),
        )
        _, summaries = run(cfg)
        assert len(summaries) == 1
        assert abs(summaries[0]["achieved_density"] - 0.5) < 5e-3
        assert np.isfinite(summaries[0]["train_loss"])


class TestDeitErk:
    """BASELINE.json config 5: DeiT + ERK pruning, end to end."""

    def test_deit_tiny_er_erk_level(self, tmp_path):
        cfg = compose(
            "cifar10_imp",
            overrides=_overrides(
                tmp_path,
                "dataset_params.dataloader_type=synthetic",
                "dataset_params.synthetic_num_train=32",
                "dataset_params.synthetic_num_test=16",
                "experiment_params.max_steps_per_epoch=2",
                "model_params.model_name=deit_tiny_patch16_224",
                "model_params.mask_layer_type=LinearMask",
                "pruning_params.prune_method=er_erk",
                "pruning_params.training_type=at_init",
                "pruning_params.target_sparsity=0.5",
            ),
        )
        _, summaries = run(cfg)
        assert len(summaries) == 1
        # ER/ERK allocations clamp at density 1 without redistribution, so
        # achieved density only approximates the target (Bernoulli draws).
        assert 0.4 < summaries[0]["achieved_density"] < 0.65
        assert np.isfinite(summaries[0]["train_loss"])
