"""Input pipelines (reference layer: /root/reference/utils/dataset.py).

Three loader families behind one factory, selected by
``dataset_params.dataloader_type`` (the reference hardcodes
airbench-for-CIFAR / FFCV-for-ImageNet in the harness,
standard_pruning_harness.py:145-157):

  device    whole dataset in HBM, whole-epoch jitted augmentation (CIFAR)
  grain     multi-process host decode + per-host sharding + device prefetch
            (ImageNet; the FFCV replacement)
  tpk       first-party native loader: mmap'd packed file + multithreaded
            C++ decode/crop (native/tpkdata.cpp) — FFCV's actual
            architecture (compiled pipeline + os_cache mmap)
  synthetic deterministic generated data (zero-egress tests/benches);
            for a token dataset (config TOKEN_DATASETS) packed token
            sequences, resident like ``device`` (data/tokens.py)

All loaders share one contract: ``.train_loader`` / ``.test_loader``
iterables yielding device-resident ``(images NHWC float, labels int32)``,
``len(loader)`` = batches per epoch, ``.num_classes``. A token dataset's
batch is ``(tokens [B, 2, T] int32, targets [B, T] int32)`` under the same
contract.
"""

from __future__ import annotations

from typing import Any

from .augment import (
    CIFAR10_MEAN,
    CIFAR10_STD,
    CIFAR100_MEAN,
    CIFAR100_STD,
    IMAGENET_MEAN,
    IMAGENET_STD,
    augment_epoch,
    batch_cutout,
    batch_flip_lr,
    batch_translate_crop,
    normalize_uint8,
    pad_reflect,
)
from .cifar import CifarLoaders, DeviceCifarLoader, cache_cifar_npz, load_cifar_arrays
from .imagenet import GrainImageLoader, ImageFolderSource, ImageNetLoaders
from .pipeline import PrefetchEngine, stream_batches
from .synthetic import SyntheticLoaders, synthetic_arrays
from .tokens import PackedTokenLoader, SyntheticTokenLoaders


def create_loaders(cfg) -> Any:
    """Loader factory from a MainConfig (reference _setup_dataloaders,
    standard_pruning_harness.py:145-157)."""
    dp = cfg.dataset_params
    seed = cfg.experiment_params.seed
    if dp.is_tokens:
        return SyntheticTokenLoaders(
            vocab_size=dp.num_classes,
            seq_len=dp.seq_len,
            batch_size=dp.total_batch_size,
            num_train=dp.synthetic_num_train,
            num_test=dp.synthetic_num_test,
            doc_len_mu=dp.doc_len_mu,
            doc_len_sigma=dp.doc_len_sigma,
            doc_len_min=dp.doc_len_min,
            layout_seed=dp.layout_seed,
            seed=seed,
            token_skew=dp.token_skew,
            block_length=dp.block_length,
        )
    if dp.dataloader_type == "synthetic":
        return SyntheticLoaders(
            dataset_name=dp.dataset_name,
            batch_size=dp.total_batch_size,
            image_size=dp.image_size,
            num_classes=dp.num_classes,
            num_train=dp.synthetic_num_train,
            num_test=dp.synthetic_num_test,
            seed=seed,
            task=dp.synthetic_task,
            snr=dp.synthetic_snr,
        )
    if dp.dataloader_type == "device":
        if dp.dataset_name not in ("CIFAR10", "CIFAR100"):
            raise ValueError(
                "dataloader_type=device is for CIFAR; use grain for ImageNet"
            )
        return CifarLoaders(
            data_root_dir=dp.data_root_dir,
            dataset_name=dp.dataset_name,
            batch_size=dp.total_batch_size,
            seed=seed,
        )
    if dp.dataloader_type == "grain":
        return ImageNetLoaders(
            data_root_dir=dp.data_root_dir,
            total_batch_size=dp.total_batch_size,
            num_workers=dp.num_workers,
            seed=seed,
            image_size=dp.image_size,
            prefetch_depth=dp.prefetch_depth,
        )
    if dp.dataloader_type == "tpk":
        from .native import TpkLoaders

        return TpkLoaders(
            data_root_dir=dp.data_root_dir,
            total_batch_size=dp.total_batch_size,
            num_classes=dp.num_classes,
            image_size=dp.image_size,
            seed=seed,
            nthreads=dp.tpk_nthreads,
            prefetch_depth=dp.prefetch_depth,
            decode_workers=dp.decode_workers,
            train_path=dp.tpk_train_path,
            val_path=dp.tpk_val_path,
            auto_pack=dp.tpk_auto_pack,
        )
    raise ValueError(f"Unknown dataloader_type: {dp.dataloader_type}")


__all__ = [
    "create_loaders",
    "CifarLoaders",
    "DeviceCifarLoader",
    "SyntheticLoaders",
    "SyntheticTokenLoaders",
    "PackedTokenLoader",
    "ImageNetLoaders",
    "GrainImageLoader",
    "ImageFolderSource",
    "load_cifar_arrays",
    "cache_cifar_npz",
    "synthetic_arrays",
    "augment_epoch",
    "PrefetchEngine",
    "stream_batches",
]
