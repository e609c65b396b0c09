"""Shared streaming input pipeline: one instrumented prefetch engine for
every host-fed loader (tpk, grain).

Before this module each streaming loader carried its own ad-hoc overlap
trick — TpkImageLoader ran a 1-deep ``ThreadPoolExecutor(max_workers=1)``
prefetch and GrainImageLoader an inline list-queue — neither propagated
worker exceptions promptly, neither could be shut down deterministically,
and neither could say WHERE an epoch's wall time went. ``PrefetchEngine``
replaces both with one three-stage pipeline (the FFCV architecture the
reference gets its headline number from: decode, transfer and compute all
in flight at once):

  decode    N pool workers execute zero-arg decode tasks; at most ``depth``
            tasks are in flight (a bounded ring — memory stays bounded no
            matter how far the consumer falls behind)
  transfer  one thread consumes decoded host batches IN SUBMIT ORDER,
            groups them (``group`` consecutive batches per call — the
            chunked-scan path stacks K batches into one [K, B, ...] device
            put), applies the caller's ``transfer`` function (device_put +
            on-device normalize), and feeds a bounded output queue
  consumer  the training loop pulls device-resident batches off the queue

Contract:
  * results come out in task-submission order, whatever the worker count
  * a task (or transfer) exception is re-raised to the consumer on its
    next pull, with the worker's original traceback attached
  * ``close()`` is idempotent, joins the transfer thread, cancels pending
    decode tasks, and never deadlocks — even when the consumer abandons
    the iterator mid-epoch
  * ``stats()`` reports per-stage wall time so a reader of the loader's
    ``last_pipeline_stats`` (tests/test_pipeline.py today) can say whether
    an epoch was decode-bound (``decode_wait_s``), transfer-bound
    (``transfer_wait_s``) or compute-bound (``consumer_wait_s``)

Bounded-memory guarantee: decoded-but-unconsumed batches never exceed
``depth`` (futures ring) + ``depth`` (output queue) + ``group`` (held by
the transfer stage while assembling one call) — tests pin this bound.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Iterable, Iterator, Optional

import jax.numpy as jnp
import numpy as np

DecodeTask = Callable[[], Any]
TransferFn = Callable[[list], list]

_DONE = object()


class _Failure:
    """A worker/transfer exception crossing the thread boundary."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class PrefetchEngine:
    """Bounded multi-stage prefetch (see module docstring).

    ``tasks``     iterable of zero-arg callables returning one host batch.
                  Executed on ``workers`` pool threads, at most ``depth``
                  in flight; results are consumed in submission order.
    ``transfer``  called on the transfer thread with a list of ``group``
                  consecutive decoded batches (the final group may be
                  shorter); returns a LIST of items to emit downstream.
    """

    def __init__(
        self,
        tasks: Iterable[DecodeTask],
        transfer: TransferFn,
        *,
        depth: int = 4,
        workers: int = 1,
        group: int = 1,
        name: str = "pipeline",
    ):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if workers < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        if group < 1:
            raise ValueError(f"group must be >= 1, got {group}")
        self._tasks = iter(tasks)
        self._transfer = transfer
        self._depth = depth
        self._group = group
        self._out: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._closed = False  # guarded-by: _lock
        self._finished = False  # guarded-by: _lock
        self._lock = threading.Lock()
        self._stats = {  # guarded-by: _lock
            "batches_decoded": 0,
            "items_emitted": 0,
            "decode_wait_s": 0.0,
            "transfer_wait_s": 0.0,
            "backpressure_s": 0.0,
            "consumer_wait_s": 0.0,
        }
        self._meta = {"depth": depth, "workers": workers, "group": group}
        self._pool = ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix=f"{name}-decode"
        )
        self._ring: deque = deque()
        self._fill_ring()
        self._thread = threading.Thread(
            target=self._run_transfer, name=f"{name}-transfer", daemon=True
        )
        self._thread.start()

    # --------------------------------------------------------------- decode
    def _fill_ring(self) -> None:
        """Keep up to ``depth`` decode tasks in flight."""
        while len(self._ring) < self._depth:
            try:
                task = next(self._tasks)
            except StopIteration:
                return
            self._ring.append(self._pool.submit(task))

    # ------------------------------------------------------------- transfer
    def _run_transfer(self) -> None:
        try:
            while not self._stop.is_set():
                batches = []
                while len(batches) < self._group and self._ring:
                    fut = self._ring.popleft()
                    self._fill_ring()  # refill BEFORE blocking on fut
                    t0 = time.perf_counter()
                    batches.append(fut.result())
                    self._bump("decode_wait_s", time.perf_counter() - t0)
                    self._bump("batches_decoded", 1)
                    if self._stop.is_set():
                        return
                if not batches:
                    break  # tasks exhausted
                t0 = time.perf_counter()
                items = self._transfer(batches)
                self._bump("transfer_wait_s", time.perf_counter() - t0)
                for item in items:
                    if not self._put(item):
                        return
                    self._bump("items_emitted", 1)
            if not self._stop.is_set():
                self._put(_DONE)
        # graftlint: disable=broad-except -- thread boundary: ANY decode/transfer failure must cross to the consumer thread and re-raise there with its original traceback, not die silently in a daemon thread
        except BaseException as e:
            for fut in self._ring:
                fut.cancel()
            self._put(_Failure(e))

    def _put(self, item) -> bool:
        """Queue.put that stays responsive to close(); returns False when
        the engine was stopped while waiting (consumer gone)."""
        t0 = time.perf_counter()
        while not self._stop.is_set():
            try:
                self._out.put(item, timeout=0.05)
                self._bump("backpressure_s", time.perf_counter() - t0)
                return True
            except queue.Full:
                continue
        return False

    def _bump(self, key: str, delta) -> None:
        with self._lock:
            self._stats[key] += delta

    # ------------------------------------------------------------- consumer
    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        with self._lock:
            finished = self._finished
        if finished:
            raise StopIteration
        t0 = time.perf_counter()
        while True:
            try:
                item = self._out.get(timeout=1.0)
                break
            except queue.Empty:
                if not self._thread.is_alive() and self._out.empty():
                    # The transfer thread always enqueues _DONE or _Failure
                    # before exiting; reaching here means it was killed
                    # abnormally (interpreter teardown) — fail loudly
                    # rather than block forever.
                    with self._lock:
                        self._finished = True
                    raise RuntimeError(
                        "prefetch pipeline transfer thread died without "
                        "signalling completion"
                    ) from None
        self._bump("consumer_wait_s", time.perf_counter() - t0)
        if item is _DONE:
            with self._lock:
                self._finished = True
            raise StopIteration
        if isinstance(item, _Failure):
            with self._lock:
                self._finished = True
            self.close()
            if isinstance(item.exc, StopIteration):
                # A StopIteration raised inside __next__ would silently end
                # the epoch early — surface it as a hard error instead.
                raise RuntimeError(
                    "decode task raised StopIteration"
                ) from item.exc
            raise item.exc  # original worker traceback rides on the exc
        return item

    # ------------------------------------------------------------ lifecycle
    def close(self) -> None:
        """Stop the pipeline and join its threads. Idempotent; safe to call
        with the transfer thread blocked on a full output queue or on an
        in-flight decode (pending tasks are cancelled, running ones are
        waited out)."""
        # Check-then-act under the lock: the consumer's failure path, the
        # generator's finally, and __del__ can all race into close(); only
        # one of them may run the join/shutdown sequence.
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._finished = True
        self._stop.set()
        # Unblock a transfer thread stuck in _put (bounded queue full).
        while True:
            try:
                self._out.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=60.0)
        for fut in self._ring:
            fut.cancel()
        self._pool.shutdown(wait=True, cancel_futures=True)

    def __enter__(self) -> "PrefetchEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # pragma: no cover — GC backstop only
        try:
            self.close()
        # graftlint: disable=broad-except -- interpreter-teardown backstop: close() during GC may find modules already torn down; the deterministic path is the explicit close() in stream_batches
        except Exception:
            pass

    # ----------------------------------------------------------------- stats
    def stats(self) -> dict:
        """Per-stage wall-time snapshot (see module docstring for the
        stage semantics)."""
        with self._lock:
            out = dict(self._stats)
        out.update(self._meta)
        return out


# ------------------------------------------------------------ transfer fns
def _to_device(images: np.ndarray, labels: np.ndarray) -> tuple:
    """Host uint8 batch (stacked or single) -> normalized device arrays.
    ``normalize_uint8`` is elementwise, so the same jitted program shape-
    specializes for [B, H, W, C] and stacked [K, B, H, W, C] alike."""
    from .imagenet import _normalize_device  # lazy: avoid import cycle

    return _normalize_device(jnp.asarray(images)), jnp.asarray(labels, jnp.int32)


def make_batch_transfer() -> TransferFn:
    """Per-batch transfer: each decoded host batch becomes one device batch."""

    def transfer(batches: list) -> list:
        return [_to_device(images, labels) for images, labels in batches]

    return transfer


def make_chunk_transfer(chunk_steps: int) -> TransferFn:
    """Chunked transfer: ``chunk_steps`` host batches are stacked into ONE
    [K, B, ...] device put (collapsing K H2D transfers into one) for the
    chunked-scan train path. A short tail group (epoch length not divisible
    by K) degrades to per-batch items so the consumer never sees a second
    stacked shape — the scan executable compiles exactly once."""

    def transfer(batches: list) -> list:
        if len(batches) == chunk_steps and chunk_steps > 1:
            images = np.stack([b[0] for b in batches])
            labels = np.stack([b[1] for b in batches])
            return [_to_device(images, labels)]
        return [_to_device(images, labels) for images, labels in batches]

    return transfer


def stream_batches(
    tasks: Iterable[DecodeTask],
    *,
    depth: int,
    workers: int,
    chunk: int = 1,
    name: str = "pipeline",
    stats_sink: Optional[Callable[[dict], None]] = None,
):
    """Generator driving a PrefetchEngine for one epoch: yields device
    batches (stacked [K, B, ...] chunks when ``chunk > 1``), guarantees the
    engine is closed when the consumer stops early (generator ``close()``
    lands in the ``finally``), and hands the final stage-time stats to
    ``stats_sink``."""
    transfer = make_chunk_transfer(chunk) if chunk > 1 else make_batch_transfer()
    engine = PrefetchEngine(
        tasks, transfer, depth=depth, workers=workers, group=chunk, name=name
    )
    try:
        yield from engine
    finally:
        engine.close()
        if stats_sink is not None:
            stats_sink(engine.stats())
