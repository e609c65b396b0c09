"""Device-resident CIFAR loaders (airbench-equivalent).

The reference's CIFAR path loads the whole dataset onto the GPU once and
does all augmentation there in batch (/root/reference/utils/dataset.py:
101-256, "Using Airbench CIFAR Loader"). The TPU-native version keeps the
whole set in HBM as device arrays, preprocesses once (normalize + pre-flip +
reflect-pad), and augments the ENTIRE epoch in one jitted call
(``augment.augment_epoch``); batches are then plain device-array slices —
the per-step path does no host work at all. That call is dense over the
set (selects over static slices, a reverse; no per-image indexing, which
the TPU ran as a loop of N iterations: 1.0 s an epoch, now 12.5 ms; see
``augment.py``). What still indexes by data is the shuffle, one ``take``
of whole images along N (25 ms for CIFAR's 50,000 on a v5e).

Raw data sources (no torchvision in this environment): a cached
``cifar10.npz``/``cifar100.npz`` under ``data_root_dir``, or the standard
python pickle batches (``cifar-10-batches-py`` / ``cifar-100-python``) if a
pre-downloaded copy exists. Use ``dataloader_type: synthetic`` when neither
is on disk.
"""

from __future__ import annotations

import pickle
from pathlib import Path
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .augment import (
    CIFAR10_MEAN,
    CIFAR10_STD,
    CIFAR100_MEAN,
    CIFAR100_STD,
    augment_epoch,
    batch_flip_lr,
    normalize_uint8,
    pad_reflect,
)
from .padding import pad_eval_batch

Batch = tuple[jax.Array, jax.Array]


def _load_pickle_batches(root: Path, dataset: str) -> Optional[tuple]:
    """Read the standard CIFAR python-pickle layout if present."""
    if dataset == "CIFAR10":
        d = root / "cifar-10-batches-py"
        if not d.exists():
            return None
        train_files = [d / f"data_batch_{i}" for i in range(1, 6)]
        test_files = [d / "test_batch"]
        label_key = b"labels"
    else:
        d = root / "cifar-100-python"
        if not d.exists():
            return None
        train_files = [d / "train"]
        test_files = [d / "test"]
        label_key = b"fine_labels"

    def read(files):
        xs, ys = [], []
        for f in files:
            with open(f, "rb") as fh:
                entry = pickle.load(fh, encoding="bytes")
            xs.append(
                np.asarray(entry[b"data"], np.uint8)
                .reshape(-1, 3, 32, 32)
                .transpose(0, 2, 3, 1)  # -> NHWC
            )
            ys.append(np.asarray(entry[label_key], np.int32))
        return np.concatenate(xs), np.concatenate(ys)

    return read(train_files), read(test_files)


def load_cifar_arrays(
    data_root_dir: str, dataset_name: str = "CIFAR10"
) -> tuple[tuple[np.ndarray, np.ndarray], tuple[np.ndarray, np.ndarray]]:
    """((train_x, train_y), (test_x, test_y)) as uint8 NHWC / int32.

    Checks the npz cache first (written by ``cache_cifar_npz``), then the
    pickle layout (the reference caches a preprocessed ``.pt`` the same way,
    dataset.py:121-149)."""
    root = Path(data_root_dir)
    npz = root / f"{dataset_name.lower()}.npz"
    if npz.exists():
        z = np.load(npz)
        return (z["train_x"], z["train_y"]), (z["test_x"], z["test_y"])
    loaded = _load_pickle_batches(root, dataset_name)
    if loaded is not None:
        return loaded
    raise FileNotFoundError(
        f"No {dataset_name} data under {root} (expected {npz.name} or the "
        f"python pickle batches). This environment has no network access — "
        f"pre-stage the data or use dataloader_type: synthetic."
    )


def cache_cifar_npz(
    data_root_dir: str,
    dataset_name: str,
    train: tuple[np.ndarray, np.ndarray],
    test: tuple[np.ndarray, np.ndarray],
) -> Path:
    root = Path(data_root_dir)
    root.mkdir(parents=True, exist_ok=True)
    out = root / f"{dataset_name.lower()}.npz"
    np.savez(
        out,
        train_x=train[0],
        train_y=train[1],
        test_x=test[0],
        test_y=test[1],
    )
    return out


class DeviceCifarLoader:
    """Epoch iterator over device-resident, whole-epoch-augmented CIFAR.

    Mirrors the reference CifarLoader's contract (dataset.py:101-256):
    train => shuffle + drop_last + aug {flip, translate=2, altflip};
    test => in-order, no aug, keep last partial batch.

    ``batch_scope = "global"``: the whole dataset is resident on every host
    (CIFAR is single-host in the reference too, run_experiment.py:24-42), so
    each yielded batch is the full global batch."""

    batch_scope = "global"

    def __init__(
        self,
        images: np.ndarray,  # uint8 NHWC
        labels: np.ndarray,
        batch_size: int,
        train: bool,
        dataset_name: str = "CIFAR10",
        aug: Optional[dict] = None,
        altflip: bool = True,
        seed: int = 0,
    ):
        mean, std = (
            (CIFAR10_MEAN, CIFAR10_STD)
            if dataset_name == "CIFAR10"
            else (CIFAR100_MEAN, CIFAR100_STD)
        )
        self.batch_size = batch_size
        self.train = train
        self.drop_last = train
        self.shuffle = train
        self.altflip = altflip
        self.aug = dict(aug or {})
        unknown = set(self.aug) - {"flip", "translate", "cutout"}
        if unknown:
            raise ValueError(f"Unrecognized aug keys: {sorted(unknown)}")
        self.epoch = 0
        self._key = jax.random.PRNGKey(seed)

        self.labels = jnp.asarray(labels, jnp.int32)
        self.image_size = images.shape[1]
        # One-time preprocessing (reference epoch-0 branch, dataset.py:
        # 191-201): normalize; pre-flip once if flipping; reflect-pad if
        # translating. The cached tensor lives in HBM.
        base = normalize_uint8(jnp.asarray(images), mean, std)
        if self.aug.get("flip"):
            self._key, k = jax.random.split(self._key)
            base = batch_flip_lr(base, k)
        if self.aug.get("translate", 0) > 0:
            base = pad_reflect(base, int(self.aug["translate"]))
        self._base = jax.device_put(base)
        # Per-epoch keys are derived STATELESSLY from this base key +
        # the epoch counter (fold_in), never from a chained split: the
        # counter is then the loader's entire RNG state, so mid-level
        # resume (harness) restores the exact augmentation/shuffle stream
        # by restoring one int. The tpk loader uses the same seed+epoch
        # discipline; grain does NOT (persistent stream position — it
        # declares resumable_epochs = False instead).
        self._epoch_key = self._key

    def __len__(self) -> int:
        n = self.labels.shape[0]
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    @property
    def num_samples(self) -> int:
        return int(self.labels.shape[0])

    def _epoch_data(self) -> Batch:
        """Augmented + shuffled arrays for one epoch (advances epoch/PRNG
        state)."""
        epoch = self.epoch
        self.epoch += 1
        k_aug, k_perm = jax.random.split(
            jax.random.fold_in(self._epoch_key, epoch)
        )

        if self.aug:
            images = augment_epoch(
                self._base,
                k_aug,
                jnp.asarray(epoch),
                crop_size=self.image_size,
                flip=bool(self.aug.get("flip", False)),
                translate=int(self.aug.get("translate", 0)),
                cutout=int(self.aug.get("cutout", 0)),
                altflip=self.altflip,
            )
        else:
            images = self._base

        if self.shuffle:
            n = self.labels.shape[0]
            perm = jax.random.permutation(k_perm, n)
            images = jnp.take(images, perm, axis=0)
            labels = jnp.take(self.labels, perm, axis=0)
        else:
            labels = self.labels
        return images, labels

    def epoch_arrays(self) -> Batch:
        """The whole epoch stacked on a step axis: images [S, B, H, W, C],
        labels [S, B] — input for the lax.scan epoch runner
        (train/steps.py make_scan_chunk): one dispatch per EPOCH instead of
        per step. Train-mode only (needs drop_last's uniform batches)."""
        if not self.drop_last:
            raise ValueError("epoch_arrays requires drop_last (train mode)")
        images, labels = self._epoch_data()
        s = len(self)
        used = s * self.batch_size
        return (
            images[:used].reshape((s, self.batch_size) + images.shape[1:]),
            labels[:used].reshape(s, self.batch_size),
        )

    def eval_epoch_arrays(self) -> Batch:
        """The static eval set stacked on a step axis: images [S, B, ...],
        labels [S, B], final batch padded with sentinel label -1 (masked by
        the eval step) — input for the lax.scan eval runner
        (train/steps.py make_scan_eval). Eval-mode only. NOT cached here:
        the harness keeps the one device-resident copy (sharded for its
        mesh); a loader-side cache would pin a duplicate in HBM for the
        whole run. Building the stack is a cheap pad+reshape of ``_base``."""
        if self.drop_last:
            raise ValueError("eval_epoch_arrays is for eval mode")
        s = len(self)
        images, labels = pad_eval_batch(
            self._base, self.labels, s * self.batch_size
        )
        return (
            images.reshape((s, self.batch_size) + images.shape[1:]),
            labels.reshape(s, self.batch_size),
        )

    def __iter__(self) -> Iterator[Batch]:
        images, labels = self._epoch_data()
        n = self.labels.shape[0]
        for i in range(len(self)):
            lo = i * self.batch_size
            hi = min(lo + self.batch_size, n)
            if hi - lo < self.batch_size:
                # Final eval batch: pad to full size, sentinel label -1
                # (masked by the eval step — see data/padding.py).
                yield pad_eval_batch(images[lo:hi], labels[lo:hi], self.batch_size)
            else:
                yield images[lo:hi], labels[lo:hi]


class CifarLoaders:
    """Train/test pair with the reference AirbenchLoaders recipe
    (dataset.py:229-256: train aug = flip + translate 2, altflip on)."""

    def __init__(
        self,
        data_root_dir: str,
        dataset_name: str,
        batch_size: int,
        seed: int = 0,
    ):
        (train_x, train_y), (test_x, test_y) = load_cifar_arrays(
            data_root_dir, dataset_name
        )
        self.num_classes = 10 if dataset_name == "CIFAR10" else 100
        self.train_loader = DeviceCifarLoader(
            train_x,
            train_y,
            batch_size,
            train=True,
            dataset_name=dataset_name,
            aug={"flip": True, "translate": 2},
            altflip=True,
            seed=seed,
        )
        self.test_loader = DeviceCifarLoader(
            test_x,
            test_y,
            batch_size,
            train=False,
            dataset_name=dataset_name,
            seed=seed + 1,
        )
