"""Native packed-dataset loader (ctypes binding for native/tpkdata.cpp).

The first-party replacement for the role FFCV plays in the reference
(/root/reference/utils/dataset.py:347-430): a memory-mapped packed file
(.tpk) holding either fixed-size raw uint8 samples (mode 0 — CIFAR-style)
or JPEG blobs with an offset table (mode 1 — ImageNet-style), read by a C++
library that does multithreaded decode, torchvision-policy
RandomResizedCrop / ratio center-crop, bilinear resize, and hflip entirely
outside Python. The grain pipeline (imagenet.py) remains the
multi-process-worker option; this is the low-overhead single-process path —
FFCV's actual architecture (compiled pipeline + os_cache mmap).

Python owns: file writing (``write_tpk_raw`` / ``write_tpk_jpegs`` /
``pack_imagefolder``), epoch shuffling, per-host sharding, and handing
batches to the device.
"""

from __future__ import annotations

import ctypes
import os
import struct
import subprocess
from pathlib import Path
from typing import Iterator, Optional, Sequence

import jax
import numpy as np

from .padding import pad_eval_batch

_MAGIC = 0x444B5054  # "TPKD"
_HEADER = struct.Struct("<IIQIIII")  # magic, version, n, mode, h, w, c
_NATIVE_DIR = Path(__file__).resolve().parents[2] / "native"
_LIB_PATH = _NATIVE_DIR / "libtpkdata.so"

_lib: Optional[ctypes.CDLL] = None


def ensure_built() -> Path:
    """Bring libtpkdata.so up to date with tpkdata.cpp on first use. make
    decides by timestamp: the library is ignored by git, so one left on
    disk can be older than the source beside it."""
    subprocess.run(
        ["make", "-C", str(_NATIVE_DIR)], check=True, capture_output=True
    )
    return _LIB_PATH


def _load_lib() -> ctypes.CDLL:
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(str(ensure_built()))
        lib.tpk_open.restype = ctypes.c_void_p
        lib.tpk_open.argtypes = [ctypes.c_char_p]
        lib.tpk_close.argtypes = [ctypes.c_void_p]
        lib.tpk_num_samples.restype = ctypes.c_int64
        lib.tpk_num_samples.argtypes = [ctypes.c_void_p]
        for f in (lib.tpk_mode, lib.tpk_height, lib.tpk_width, lib.tpk_channels):
            f.restype = ctypes.c_int32
            f.argtypes = [ctypes.c_void_p]
        lib.tpk_read_raw_batch.restype = ctypes.c_int
        lib.tpk_read_raw_batch.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int,
        ]
        lib.tpk_decode_batch.restype = ctypes.c_int
        lib.tpk_decode_batch.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_uint64,
            ctypes.c_double,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int,
        ]
        _lib = lib
    return _lib


# --------------------------------------------------------------- writers
def write_tpk_raw(path: str | Path, images: np.ndarray, labels: np.ndarray) -> Path:
    """Fixed-size uint8 NHWC samples (mode 0)."""
    images = np.ascontiguousarray(images, np.uint8)
    labels = np.ascontiguousarray(labels, np.int32)
    n, h, w, c = images.shape
    path = Path(path)
    with open(path, "wb") as f:
        f.write(_HEADER.pack(_MAGIC, 1, n, 0, h, w, c))
        f.write(labels.tobytes())
        f.write(images.tobytes())
    return path


def write_tpk_jpegs(
    path: str | Path, blobs: Sequence[bytes], labels: np.ndarray
) -> Path:
    """Variable-size JPEG blobs with an offset table (mode 1)."""
    labels = np.ascontiguousarray(labels, np.int32)
    n = len(blobs)
    assert labels.shape == (n,)
    offsets = np.zeros(n + 1, np.uint64)
    offsets[1:] = np.cumsum([len(b) for b in blobs])
    path = Path(path)
    with open(path, "wb") as f:
        f.write(_HEADER.pack(_MAGIC, 1, n, 1, 0, 0, 0))
        f.write(labels.tobytes())
        f.write(offsets.tobytes())
        for b in blobs:
            f.write(b)
    return path


def pack_imagefolder(split_dir: str | Path, out_path: str | Path) -> Path:
    """Pack an ImageFolder split's JPEGs into a .tpk (the analog of FFCV's
    dataset-writing step that produces .beton files)."""
    from .imagenet import _index_image_folder

    paths, labels, _classes = _index_image_folder(Path(split_dir))
    blobs = []
    for p in paths:
        with open(p, "rb") as f:
            blobs.append(f.read())
    return write_tpk_jpegs(out_path, blobs, np.asarray(labels, np.int32))


# ---------------------------------------------------------------- reader
class TpkFile:
    def __init__(self, path: str | Path):
        self._lib = _load_lib()
        self._handle = self._lib.tpk_open(str(path).encode())
        if not self._handle:
            raise OSError(f"cannot open tpk file: {path}")
        self.num_samples = int(self._lib.tpk_num_samples(self._handle))
        self.mode = int(self._lib.tpk_mode(self._handle))
        self.height = int(self._lib.tpk_height(self._handle))
        self.width = int(self._lib.tpk_width(self._handle))
        self.channels = int(self._lib.tpk_channels(self._handle))

    def close(self) -> None:
        if self._handle:
            self._lib.tpk_close(self._handle)
            self._handle = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except (AttributeError, TypeError, OSError):
            # Interpreter shutdown: the ctypes lib / globals may already be
            # torn down. Anything else (double-free, bad handle) should not
            # be silenced — it means the reader itself is broken.
            pass

    def read_raw(
        self, indices: np.ndarray, nthreads: int = 0
    ) -> tuple[np.ndarray, np.ndarray]:
        """``nthreads=0`` = auto (min(16, cpu_count)); loaders pass the
        configured ``dataset_params.tpk_nthreads`` through instead of
        relying on a hardcoded default."""
        nthreads = _resolve_nthreads(nthreads)
        indices = np.ascontiguousarray(indices, np.int64)
        n = len(indices)
        images = np.empty((n, self.height, self.width, self.channels), np.uint8)
        labels = np.empty(n, np.int32)
        rc = self._lib.tpk_read_raw_batch(
            self._handle,
            indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            n,
            images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            nthreads,
        )
        if rc:
            raise RuntimeError(f"tpk_read_raw_batch failed (rc={rc})")
        return images, labels

    def decode(
        self,
        indices: np.ndarray,
        out_size: int,
        train: bool,
        seed: int = 0,
        center_crop_ratio: float = 224 / 256,
        nthreads: int = 0,
    ) -> tuple[np.ndarray, np.ndarray]:
        nthreads = _resolve_nthreads(nthreads)
        indices = np.ascontiguousarray(indices, np.int64)
        n = len(indices)
        images = np.empty((n, out_size, out_size, 3), np.uint8)
        labels = np.empty(n, np.int32)
        rc = self._lib.tpk_decode_batch(
            self._handle,
            indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            n,
            out_size,
            1 if train else 0,
            ctypes.c_uint64(seed),
            center_crop_ratio,
            images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            nthreads,
        )
        if rc:
            raise RuntimeError(f"tpk_decode_batch failed (rc={rc})")
        return images, labels


def _resolve_nthreads(nthreads: int) -> int:
    return nthreads or min(16, os.cpu_count() or 1)


def make_shard(n: int, pid: int, nproc: int) -> np.ndarray:
    """Strided per-host shard (host p takes samples p, p+nproc, ...).

    The sharding contract (FFCV ``distributed=True`` analog,
    /root/reference/utils/dataset.py:411-418): every sample belongs to
    exactly one host's shard — strided assignment covers the ``n % nproc``
    remainder that a contiguous ``n // nproc`` split would permanently drop
    (r4 weak #4). Shard sizes differ by at most one; lockstep is restored by
    the loader's globally-agreed step count (train) or eval padding."""
    return np.arange(pid, n, nproc, dtype=np.int64)


class TpkImageLoader:
    """Epoch iterator over a .tpk: native decode, per-host sharding, device
    normalize — the FFCV ``Loader`` contract (dataset.py:409-430): train =
    shuffled + drop_last, eval = sequential + keep last.
    ``batch_scope = "host"``: yields THIS host's slice of the global batch.

    Sharding contract (both splits strided, see ``make_shard``):
      train: all hosts run ``(n // nproc) // batch_size`` steps — identical
        on every host by construction, so SPMD steps stay in lockstep even
        when shard sizes differ by one. Up to ``batch_size - 1 + (1 if the
        shard has the extra sample)`` samples per host per epoch fall off
        the drop-last tail, but the per-epoch shuffle rotates WHICH samples,
        so none is permanently excluded (unlike the pre-r5 contiguous split,
        which silently never visited the last ``n % nproc`` samples at all).
      eval: every sample visited exactly once; short final/odd-shard batches
        are padded with sentinel labels (data/padding.py) and all hosts run
        the same global ceil step count."""

    batch_scope = "host"

    def __init__(
        self,
        path: str | Path,
        total_batch_size: int,
        train: bool,
        image_size: int = 224,
        seed: int = 0,
        nthreads: int = 0,
        prefetch_depth: int = 4,
        decode_workers: int = 2,
    ):
        self.file = TpkFile(path)
        nproc = jax.process_count()
        if total_batch_size % nproc:
            raise ValueError("total_batch_size not divisible by process_count")
        self.batch_size = total_batch_size // nproc
        self.train = train
        self.image_size = image_size
        self.seed = seed
        self.nthreads = _resolve_nthreads(nthreads)
        self.prefetch_depth = prefetch_depth
        self.decode_workers = decode_workers
        self.epoch = 0
        self.last_pipeline_stats: Optional[dict] = None
        self._nproc = nproc
        self._shard = make_shard(self.file.num_samples, jax.process_index(), nproc)

    def __len__(self) -> int:
        if self.train:
            # GLOBAL train step count — floor(n/nproc)//bs is identical on
            # every host (shard sizes differ by one; see class docstring).
            return (self.file.num_samples // self._nproc) // self.batch_size
        # GLOBAL eval batch count (largest shard, ceil) — identical on every
        # host so lockstep SPMD eval steps line up; short shards pad.
        max_shard = -(-self.file.num_samples // self._nproc)
        return -(-max_shard // self.batch_size)

    def _decode_batch(self, order: np.ndarray, b: int, epoch: int):
        idx = order[b * self.batch_size : (b + 1) * self.batch_size]
        if self.file.mode == 1:
            images, labels = self.file.decode(
                idx,
                self.image_size,
                self.train,
                seed=self.seed * 1_000_003 + epoch,
                nthreads=self.nthreads,
            )
        else:
            images, labels = self.file.read_raw(idx, nthreads=self.nthreads)
        if not self.train:
            images, labels = pad_eval_batch(images, labels, self.batch_size)
        return images, labels

    def _epoch_tasks(self, max_batches: Optional[int] = None):
        """(decode-task iterator, n) for one epoch; advances the epoch
        counter (the per-epoch shuffle/augment PRNG stream) exactly like the
        pre-engine iterator did — on first consumption, since callers wrap
        this in a generator."""
        epoch = self.epoch
        self.epoch += 1
        order = self._shard
        if self.train:
            rng = np.random.default_rng(self.seed + epoch)
            order = rng.permutation(order)
        n = len(self)
        if max_batches is not None:
            n = min(n, max_batches)

        def tasks():
            from functools import partial

            for b in range(n):
                yield partial(self._decode_batch, order, b, epoch)

        return tasks(), n

    def _set_stats(self, stats: dict) -> None:
        self.last_pipeline_stats = stats

    def __iter__(self) -> Iterator[tuple[jax.Array, jax.Array]]:
        """Device batches for one epoch through the shared prefetch engine
        (data/pipeline.py): ``decode_workers`` concurrent C++ decode calls
        (each ``nthreads``-threaded, GIL released) feed a transfer stage, so
        decode, H2D transfer and device compute all overlap — FFCV's
        pipelined-decode architecture, shared with the grain loader."""
        from .pipeline import stream_batches

        task_iter, n = self._epoch_tasks()
        if n == 0:
            return
        yield from stream_batches(
            task_iter,
            depth=self.prefetch_depth,
            workers=self.decode_workers,
            name="tpk",
            stats_sink=self._set_stats,
        )

    def iter_chunks(
        self, chunk: int, max_batches: Optional[int] = None
    ) -> Iterator[tuple[jax.Array, jax.Array]]:
        """Chunked epoch for the scan-chunk train path: yields stacked
        [K, B, ...] device chunks (K = ``chunk``); a tail of fewer than K
        batches comes out as plain [B, ...] batches so the consumer sees at
        most two shapes (one scan program + one per-step program)."""
        from .pipeline import stream_batches

        task_iter, n = self._epoch_tasks(max_batches)
        if n == 0:
            return
        yield from stream_batches(
            task_iter,
            depth=max(self.prefetch_depth, chunk),
            workers=self.decode_workers,
            chunk=chunk,
            name="tpk",
            stats_sink=self._set_stats,
        )


class TpkLoaders:
    """Train/val pair over packed .tpk files — the config-selectable
    first-party native path (``dataset_params.dataloader_type: tpk``),
    filling the role FFCV's Loader pair plays in the reference
    (/root/reference/utils/dataset.py:409-430). ``auto_pack`` writes missing
    .tpk files from ImageFolder splits under ``data_root_dir`` on first use
    (FFCV's .beton-writing step, done primary-host-only)."""

    def __init__(
        self,
        data_root_dir: str,
        total_batch_size: int,
        num_classes: int,
        image_size: int = 224,
        seed: int = 0,
        nthreads: int = 0,
        prefetch_depth: int = 4,
        decode_workers: int = 2,
        train_path: str = "",
        val_path: str = "",
        auto_pack: bool = False,
    ):
        root = Path(data_root_dir)
        train_tpk = Path(train_path) if train_path else root / "train.tpk"
        val_tpk = Path(val_path) if val_path else root / "val.tpk"
        if auto_pack:
            self._maybe_pack(root / "train", train_tpk)
            self._maybe_pack(root / "val", val_tpk)
        for p in (train_tpk, val_tpk):
            if not p.exists():
                raise FileNotFoundError(
                    f"tpk file not found: {p} — set dataset_params.tpk_*_path "
                    "or tpk_auto_pack: true with ImageFolder splits under "
                    "data_root_dir"
                )
        self.train_loader = TpkImageLoader(
            train_tpk,
            total_batch_size,
            train=True,
            image_size=image_size,
            seed=seed,
            nthreads=nthreads,
            prefetch_depth=prefetch_depth,
            decode_workers=decode_workers,
        )
        self.test_loader = TpkImageLoader(
            val_tpk,
            total_batch_size,
            train=False,
            image_size=image_size,
            seed=seed,
            nthreads=nthreads,
            prefetch_depth=prefetch_depth,
            decode_workers=decode_workers,
        )
        self.num_classes = num_classes

    @staticmethod
    def _maybe_pack(split_dir: Path, tpk_path: Path) -> None:
        from ..parallel.multihost import is_primary, sync_hosts

        # EVERY host reaches the barrier unconditionally — gating it on
        # per-host filesystem state (file already packed on one host, split
        # dir staged only on the primary) would leave hosts in different
        # collectives and hang the job.
        if is_primary() and not tpk_path.exists() and split_dir.is_dir():
            tmp = tpk_path.with_suffix(".tpk.tmp")
            pack_imagefolder(split_dir, tmp)
            os.replace(tmp, tpk_path)
        sync_hosts(f"tpk_pack:{tpk_path.name}")
