"""Data-layer tests: augmentation semantics, loader contract, grain
pipeline on a tiny fake ImageFolder (SURVEY.md §4 — the reference has no
tests; these pin the airbench/FFCV-equivalent behaviors)."""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from turboprune_tpu.data import (
    DeviceCifarLoader,
    SyntheticLoaders,
    synthetic_arrays,
)
from turboprune_tpu.data.augment import (
    augment_epoch,
    batch_cutout,
    batch_flip_lr,
    batch_translate_crop,
    normalize_uint8,
    pad_reflect,
)


def _images(n=8, s=8, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 256, size=(n, s, s, 3), dtype=np.uint8))


class TestAugment:
    def test_normalize_uint8_range(self):
        x = normalize_uint8(_images(), (0.5, 0.5, 0.5), (0.25, 0.25, 0.25))
        assert x.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(x))) <= 2.0 + 1e-6

    def test_flip_is_mirror_or_identity_per_image(self):
        x = normalize_uint8(_images(), (0, 0, 0), (1, 1, 1))
        y = batch_flip_lr(x, jax.random.PRNGKey(0))
        for i in range(x.shape[0]):
            same = bool(jnp.allclose(y[i], x[i]))
            flipped = bool(jnp.allclose(y[i], x[i, :, ::-1, :]))
            assert same or flipped

    def test_translate_crop_content_comes_from_padded(self):
        x = normalize_uint8(_images(n=4, s=8), (0, 0, 0), (1, 1, 1))
        padded = pad_reflect(x, 2)
        out = batch_translate_crop(padded, jax.random.PRNGKey(1), 8)
        assert out.shape == x.shape
        # each output must equal SOME (sy, sx) window of its padded input
        for i in range(4):
            found = any(
                bool(jnp.allclose(out[i], padded[i, sy : sy + 8, sx : sx + 8, :]))
                for sy in range(5)
                for sx in range(5)
            )
            assert found

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("translate", [1, 2, 4])
    def test_translate_crop_equals_per_image_reference(self, translate, dtype):
        """Bit for bit the per-image crop at the shifts the same key draws;
        37 images, so no block or tile size divides N."""
        n, c = 37, 8
        x = normalize_uint8(_images(n=n, s=c, seed=translate), (0, 0, 0), (1, 1, 1))
        padded = pad_reflect(x, translate).astype(dtype)
        key = jax.random.PRNGKey(1)  # draws every shift of 1, 2 and 4 on both axes
        out = batch_translate_crop(padded, key, c)
        assert out.dtype == dtype and out.shape == (n, c, c, 3)

        # graftlint: disable=rng-key-reuse -- deliberate: the reference redraws the crop's own shifts from the same key
        ky, kx = jax.random.split(key)
        sy = np.asarray(jax.random.randint(ky, (n,), 0, 2 * translate + 1))
        sx = np.asarray(jax.random.randint(kx, (n,), 0, 2 * translate + 1))
        assert len(set(sy)) == len(set(sx)) == 2 * translate + 1  # every shift drawn
        host = np.asarray(padded.astype(jnp.float32))
        want = np.stack(
            [host[i, sy[i] : sy[i] + c, sx[i] : sx[i] + c] for i in range(n)]
        )
        np.testing.assert_array_equal(np.asarray(out.astype(jnp.float32)), want)

    def test_augment_epoch_lowers_to_dense_ops_only(self):
        """The guard that the dense crop is what runs: the program lowered
        for the TPU (PRNG unrolled there; the CPU rolls threefry into a
        ``while`` of its own) indexes nothing by data and loops over
        nothing. The parent's vmap(dynamic_slice) lowered to a gather,
        which the TPU ran as a ``while`` of N iterations (PERF.md, PR 25)."""
        x = jnp.zeros((37, 12, 12, 3), jnp.float32)
        text = (
            augment_epoch.trace(
                x, jax.random.PRNGKey(0), jnp.asarray(1),
                crop_size=8, flip=True, translate=2, altflip=True,
            )
            .lower(lowering_platforms=("tpu",))
            .as_text()
        )
        assert "stablehlo.select" in text and "stablehlo.slice" in text
        for op in ("gather", "dynamic_slice", "dynamic_update_slice", "while", "scatter"):
            assert f"stablehlo.{op}" not in text, op

    def test_cutout_zeroes_exactly_one_square(self):
        x = jnp.ones((4, 8, 8, 3), jnp.float32)
        out = batch_cutout(x, jax.random.PRNGKey(2), 3)
        for i in range(4):
            zeros = int(jnp.sum(out[i] == 0.0))
            assert zeros == 3 * 3 * 3

    def test_altflip_flips_whole_set_on_odd_epochs(self):
        x = normalize_uint8(_images(n=4, s=8), (0, 0, 0), (1, 1, 1))
        k = jax.random.PRNGKey(3)
        even = augment_epoch(
            x, k, jnp.asarray(0), crop_size=8, flip=True, translate=0, altflip=True
        )
        # graftlint: disable=rng-key-reuse -- deliberate: same key on both calls proves the odd-epoch output is exactly the flipped even-epoch output
        odd = augment_epoch(
            x, k, jnp.asarray(1), crop_size=8, flip=True, translate=0, altflip=True
        )
        assert bool(jnp.allclose(odd, even[:, :, ::-1, :]))


class TestDeviceLoader:
    def _loader(self, train=True, n=64, bs=16, **kw):
        x, y = synthetic_arrays(n, 8, 4, seed=0)
        aug = {"flip": True, "translate": 2} if train else None
        return DeviceCifarLoader(
            x, y, bs, train=train, aug=aug, seed=0, **kw
        )

    def test_train_epoch_shapes_and_count(self):
        loader = self._loader(n=70, bs=16)
        batches = list(loader)
        assert len(batches) == len(loader) == 70 // 16
        for imgs, labels in batches:
            assert imgs.shape == (16, 8, 8, 3)
            assert labels.shape == (16,)
            assert labels.dtype == jnp.int32

    def test_test_loader_pads_last_batch_and_keeps_order(self):
        loader = self._loader(train=False, n=70, bs=16)
        batches = list(loader)
        assert len(batches) == 5  # ceil(70/16)
        # final batch padded to full size with sentinel label -1
        assert batches[-1][0].shape[0] == 16
        last_labels = np.asarray(batches[-1][1])
        assert (last_labels[70 - 4 * 16 :] == -1).all()
        # no shuffle: valid labels concatenate back to the original order
        x, y = synthetic_arrays(70, 8, 4, seed=0)
        got = np.concatenate([np.asarray(b[1]) for b in batches])
        np.testing.assert_array_equal(got[got >= 0], y)

    def test_shuffle_differs_across_epochs_but_same_multiset(self):
        loader = self._loader(n=64, bs=64)
        (imgs1, labels1), = list(loader)
        (imgs2, labels2), = list(loader)
        assert not bool(jnp.array_equal(labels1, labels2))
        np.testing.assert_array_equal(
            np.sort(np.asarray(labels1)), np.sort(np.asarray(labels2))
        )

    @pytest.mark.parametrize("epoch", [0, 1])
    def test_epoch_arrays_stream_is_pinned(self, epoch):
        """The seeded stream of a train loader, epoch by epoch, against the
        crop as it was before PR 25 (vmap of a per-image dynamic_slice, kept
        here) under the loader's key discipline: fold_in(epoch), split into
        augmentation and permutation, the crop's key first of three."""
        loader = self._loader(n=70, bs=16)
        loader.epoch = epoch  # the counter is the loader's whole RNG state
        images, labels = loader.epoch_arrays()

        def old_crop(padded, key, crop_size):
            n, h, _, c = padded.shape
            ky, kx = jax.random.split(key)
            sy = jax.random.randint(ky, (n,), 0, h - crop_size + 1)
            sx = jax.random.randint(kx, (n,), 0, h - crop_size + 1)
            return jax.vmap(
                lambda img, y, x: jax.lax.dynamic_slice(
                    img, (y, x, 0), (crop_size, crop_size, c)
                )
            )(padded, sy, sx)

        k_aug, k_perm = jax.random.split(
            jax.random.fold_in(loader._epoch_key, epoch)
        )
        want = old_crop(loader._base, jax.random.split(k_aug, 3)[0], 8)
        if epoch % 2 == 1:
            want = want[:, :, ::-1, :]
        perm = jax.random.permutation(k_perm, 70)
        np.testing.assert_array_equal(
            np.asarray(images), np.asarray(want[perm][:64]).reshape(4, 16, 8, 8, 3)
        )
        np.testing.assert_array_equal(
            np.asarray(labels), np.asarray(loader.labels[perm][:64]).reshape(4, 16)
        )

    def test_unknown_aug_key_rejected(self):
        x, y = synthetic_arrays(8, 8, 2, seed=0)
        with pytest.raises(ValueError, match="Unrecognized"):
            DeviceCifarLoader(x, y, 4, train=True, aug={"mixup": 1})


class TestSyntheticLoaders:
    def test_contract(self):
        loaders = SyntheticLoaders(
            "CIFAR10", batch_size=32, image_size=8, num_classes=10,
            num_train=128, num_test=64, seed=0,
        )
        assert loaders.num_classes == 10
        imgs, labels = next(iter(loaders.train_loader))
        assert imgs.shape == (32, 8, 8, 3)
        assert int(labels.min()) >= 0 and int(labels.max()) < 10

    def test_deterministic_given_seed(self):
        a = synthetic_arrays(16, 8, 4, seed=7)
        b = synthetic_arrays(16, 8, 4, seed=7)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])

    @staticmethod
    def _spectral_oracle(xs, num_classes, image_size):
        """Bayes-ish classifier for the hard task: |complex projection| of
        each image onto every (class, variant) grating signature (unknown
        phase handled by the magnitude), max over variants, argmax class."""
        from turboprune_tpu.data.synthetic import _grating_signatures

        freqs, colors = _grating_signatures(num_classes, 4, image_size, 12345)
        x = xs.astype(np.float32) - 128.0
        xx, yy = np.meshgrid(
            np.arange(image_size), np.arange(image_size), indexing="ij"
        )
        s = np.zeros((len(xs), num_classes, 4))
        for c in range(num_classes):
            for v in range(4):
                fx, fy = freqs[c, v]
                basis = np.exp(-2j * np.pi * (fx * xx + fy * yy) / image_size)
                proj = np.einsum("nhwc,c->nhw", x, colors[c, v])
                s[:, c, v] = np.abs(np.einsum("nhw,hw->n", proj, basis))
        return s.max(2).argmax(1)

    def test_hard_synthetic_oracle_band(self):
        """The hard task must be learnable-but-not-trivial: the spectral
        oracle should land well below 100% but far above chance at the
        default snr — the band that makes accuracy curves discriminate
        between training types (VERDICT r4 missing #2)."""
        xs, ys = synthetic_arrays(512, 32, 10, seed=7, task="hard", snr=1.5)
        acc = (self._spectral_oracle(xs, 10, 32) == ys).mean()
        assert 0.85 < acc < 0.995, acc  # snr=1.5 calibration band

    def test_hard_synthetic_shares_structure_across_splits(self):
        """Different sample seeds (train/test) must share signatures: the
        SAME signature bank classifies both splits — at snr=5 near-perfectly
        — so class structure is split-invariant."""
        a_x, a_y = synthetic_arrays(64, 16, 3, seed=1, task="hard", snr=5.0)
        b_x, b_y = synthetic_arrays(64, 16, 3, seed=2, task="hard", snr=5.0)
        assert (self._spectral_oracle(a_x, 3, 16) == a_y).mean() > 0.95
        assert (self._spectral_oracle(b_x, 3, 16) == b_y).mean() > 0.95


class TestGrainImageNet:
    @pytest.fixture(scope="class")
    def fake_imagefolder(self, tmp_path_factory):
        from PIL import Image

        root = tmp_path_factory.mktemp("imagenet")
        rng = np.random.default_rng(0)
        for split, per_class in (("train", 6), ("val", 3)):
            for cls in ("n01", "n02"):
                d = root / split / cls
                d.mkdir(parents=True)
                for i in range(per_class):
                    arr = rng.integers(0, 256, size=(40, 52, 3), dtype=np.uint8)
                    Image.fromarray(arr).save(d / f"img_{i}.jpeg")
        return root

    def test_pipeline_shapes_and_labels(self, fake_imagefolder):
        from turboprune_tpu.data.imagenet import ImageNetLoaders

        loaders = ImageNetLoaders(
            str(fake_imagefolder), total_batch_size=4, num_workers=0, seed=0
        )
        assert loaders.num_classes == 2
        imgs, labels = next(iter(loaders.train_loader))
        assert imgs.shape == (4, 224, 224, 3)
        assert imgs.dtype == jnp.float32
        assert set(np.asarray(labels)) <= {0, 1}
        # val: sequential, final batch padded with label -1
        val_batches = list(loaders.test_loader)
        for imgs, labels in val_batches:
            assert imgs.shape[0] == 4
        total = sum(int((np.asarray(b[1]) >= 0).sum()) for b in val_batches)
        assert total == 6

    def test_eval_center_crop_deterministic(self, fake_imagefolder):
        from turboprune_tpu.data.imagenet import GrainImageLoader

        loader = GrainImageLoader(
            str(fake_imagefolder / "val"), 2, train=False, num_workers=0, seed=0
        )
        a = [np.asarray(b[0]) for b in loader]
        b = [np.asarray(x[0]) for x in loader]
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    def test_train_stream_state_resumes_exact_order(self, fake_imagefolder):
        """The stream-state protocol (mid-level resume): a fresh loader
        restored from get_stream_state() must replay the original stream's
        NEXT epoch exactly — position, shuffle pass, and augmentation
        stream all ride in grain's checkpointable iterator state."""
        from turboprune_tpu.data.imagenet import GrainImageLoader

        def make():
            return GrainImageLoader(
                str(fake_imagefolder / "train"), 2, train=True,
                num_workers=0, seed=0,
            )

        first = make()
        assert first.get_stream_state() is None  # no stream yet
        _ = list(first)  # epoch 1 consumed
        state = first.get_stream_state()
        assert isinstance(state, bytes)
        want = [(np.asarray(i), np.asarray(l)) for i, l in first]  # epoch 2

        resumed = make()
        resumed.set_stream_state(state)
        got = [(np.asarray(i), np.asarray(l)) for i, l in resumed]
        assert len(got) == len(want)
        for (gi, gl), (wi, wl) in zip(got, want):
            np.testing.assert_array_equal(gi, wi)
            np.testing.assert_array_equal(gl, wl)
