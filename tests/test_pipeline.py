"""Streaming input pipeline (data/pipeline.py) + chunked-scan train path.

Covers the engine contract the loaders now depend on (ordering, bounded
depth, exception propagation with the worker's traceback, deterministic
shutdown, no deadlock on early consumer exit), loader-level equivalence of
the chunked iterator, BIT-EXACT parity of ``make_scan_chunk(K)`` with K
sequential train steps, and the end-to-end streamed chunked harness path
on synthetic .tpk data (dispatch count reduced by K×).
"""

import threading
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from turboprune_tpu.data.pipeline import (
    PrefetchEngine,
    make_chunk_transfer,
    stream_batches,
)

_IDENTITY = lambda batches: list(batches)  # noqa: E731 — per-batch passthrough


def _tasks(values, delay=0.0, counter=None, lock=None):
    def make(v):
        def task():
            if counter is not None:
                with lock:
                    counter[0] += 1
            if delay:
                time.sleep(delay)
            return v

        return task

    return [make(v) for v in values]


class TestPrefetchEngine:
    def test_ordering_preserved_with_parallel_workers(self):
        """Results must come out in submission order even when later tasks
        finish first (4 workers, reverse-staggered sleeps)."""
        n = 24

        def make(i):
            def task():
                time.sleep(0.001 * ((n - i) % 5))
                return i

            return task

        engine = PrefetchEngine(
            [make(i) for i in range(n)], _IDENTITY, depth=6, workers=4
        )
        try:
            assert list(engine) == list(range(n))
        finally:
            engine.close()

    def test_bounded_depth(self):
        """With the consumer stalled, the pipeline must stop decoding at
        the documented bound: depth (futures ring) + depth (output queue)
        + group (held by the transfer stage) — never the whole epoch."""
        counter, lock = [0], threading.Lock()
        depth = 2
        engine = PrefetchEngine(
            _tasks(range(100), counter=counter, lock=lock),
            _IDENTITY,
            depth=depth,
            workers=2,
        )
        try:
            time.sleep(0.5)  # consumer never pulls
            assert counter[0] <= 2 * depth + 1, counter[0]
            # ...and the pipeline still completes once consumption starts.
            assert list(engine) == list(range(100))
        finally:
            engine.close()

    def test_worker_exception_propagates_with_traceback(self):
        def exploding_decode():
            raise ValueError("decode exploded mid-epoch")

        tasks = _tasks([0, 1]) + [exploding_decode] + _tasks([3, 4])
        engine = PrefetchEngine(tasks, _IDENTITY, depth=2, workers=2)
        got = []
        with pytest.raises(ValueError, match="decode exploded") as excinfo:
            for item in engine:
                got.append(item)
        assert got == [0, 1]  # everything before the failure arrives intact
        # The ORIGINAL worker traceback rides on the exception.
        exc = excinfo.value
        tb = "".join(
            traceback.format_exception(type(exc), exc, exc.__traceback__)
        )
        assert "exploding_decode" in tb
        engine.close()

    def test_transfer_exception_propagates(self):
        def bad_transfer(batches):
            raise RuntimeError("transfer stage failed")

        engine = PrefetchEngine(_tasks(range(4)), bad_transfer, depth=2)
        with pytest.raises(RuntimeError, match="transfer stage failed"):
            list(engine)
        engine.close()

    def test_close_joins_workers_and_is_idempotent(self):
        engine = PrefetchEngine(
            _tasks(range(50), delay=0.005), _IDENTITY, depth=4, workers=2
        )
        assert next(engine) == 0
        engine.close()
        engine.close()  # idempotent
        assert not engine._thread.is_alive()
        # Executor refuses new work after shutdown — pool really closed.
        with pytest.raises(RuntimeError):
            engine._pool.submit(lambda: None)

    def test_early_consumer_exit_no_deadlock(self):
        """Abandoning the iterator with the output queue full and decode
        tasks in flight must not hang close() (the transfer thread is
        blocked in put; pending futures are cancelled)."""
        engine = PrefetchEngine(
            _tasks(range(200), delay=0.002), _IDENTITY, depth=2, workers=2
        )
        got = [next(engine), next(engine)]
        t0 = time.perf_counter()
        engine.close()
        assert time.perf_counter() - t0 < 10.0
        assert got == [0, 1]
        assert not engine._thread.is_alive()

    def test_generator_wrapper_closes_on_break(self):
        """stream_batches must close its engine when the consumer breaks
        out of the loop (generator finally), hand stats to the sink, and
        run batches through the device transfer (uint8 -> normalized)."""
        stats_box = []

        def make(i):
            def task():
                time.sleep(0.002)
                return (
                    np.full((2, 4, 4, 3), i, np.uint8),
                    np.full((2,), i, np.int32),
                )

            return task

        gen = stream_batches(
            [make(i) for i in range(50)],
            depth=2,
            workers=1,
            stats_sink=stats_box.append,
        )
        images, labels = next(gen)
        gen.close()
        assert len(stats_box) == 1
        assert stats_box[0]["items_emitted"] >= 1
        assert images.dtype == jnp.float32  # normalized on device
        np.testing.assert_array_equal(np.asarray(labels), [0, 0])

    def test_grouping_and_short_tail(self):
        """group=K hands the transfer stage K consecutive batches and a
        short tail; make_chunk_transfer-style contracts see exactly one
        full-group call per chunk."""
        seen = []

        def transfer(batches):
            seen.append(len(batches))
            return [tuple(batches)]

        engine = PrefetchEngine(
            _tasks(range(10)), transfer, depth=4, workers=3, group=4
        )
        try:
            out = list(engine)
        finally:
            engine.close()
        assert out == [(0, 1, 2, 3), (4, 5, 6, 7), (8, 9)]
        assert seen == [4, 4, 2]

    def test_stats_keys_and_accounting(self):
        engine = PrefetchEngine(
            _tasks(range(8), delay=0.002), _IDENTITY, depth=2, workers=2
        )
        try:
            assert len(list(engine)) == 8
        finally:
            engine.close()
        stats = engine.stats()
        assert stats["batches_decoded"] == 8
        assert stats["items_emitted"] == 8
        for key in (
            "decode_wait_s",
            "transfer_wait_s",
            "consumer_wait_s",
            "backpressure_s",
        ):
            assert stats[key] >= 0.0
        assert (stats["depth"], stats["workers"], stats["group"]) == (2, 2, 1)


class TestChunkTransfer:
    def test_full_chunk_stacks_short_tail_degrades(self):
        transfer = make_chunk_transfer(3)
        batches = [
            (np.full((2, 4, 4, 3), i, np.uint8), np.full((2,), i, np.int32))
            for i in range(3)
        ]
        (images, labels), = transfer(batches)
        assert images.shape == (3, 2, 4, 4, 3)
        assert labels.shape == (3, 2)
        np.testing.assert_array_equal(np.asarray(labels)[:, 0], [0, 1, 2])
        tail = transfer(batches[:2])
        assert len(tail) == 2  # degraded to per-batch items
        assert tail[0][0].ndim == 4


@pytest.fixture(scope="module")
def tpk_train(tmp_path_factory):
    from turboprune_tpu.data.native import write_tpk_raw

    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, size=(48, 8, 8, 3), dtype=np.uint8)
    labels = rng.integers(0, 4, size=(48,)).astype(np.int32)
    path = tmp_path_factory.mktemp("pipeline_tpk") / "train.tpk"
    write_tpk_raw(path, images, labels)
    return path


class TestLoaderChunks:
    def test_tpk_iter_chunks_matches_per_batch_iter(self, tpk_train):
        """iter_chunks(K) must yield exactly the per-batch epoch, stacked —
        same shuffle order, same pixels, bitwise-identical normalization
        (the normalize op is elementwise, so 4D and stacked 5D agree)."""
        from turboprune_tpu.data.native import TpkImageLoader

        mk = lambda: TpkImageLoader(  # noqa: E731
            tpk_train, total_batch_size=8, train=True, image_size=8, seed=3
        )
        flat = list(mk())  # epoch 0, per-batch path
        chunks = list(mk().iter_chunks(2))  # epoch 0, chunked path
        assert len(flat) == 6 and len(chunks) == 3
        unstacked = [
            (np.asarray(ci)[k], np.asarray(cl)[k])
            for ci, cl in chunks
            for k in range(np.asarray(ci).shape[0])
        ]
        for (fi, fl), (ci, cl) in zip(flat, unstacked):
            np.testing.assert_array_equal(np.asarray(fi), ci)
            np.testing.assert_array_equal(np.asarray(fl), cl)

    def test_tpk_iter_chunks_tail_and_max_batches(self, tpk_train):
        from turboprune_tpu.data.native import TpkImageLoader

        loader = TpkImageLoader(
            tpk_train, total_batch_size=8, train=True, image_size=8
        )
        items = list(loader.iter_chunks(4))  # 6 batches -> [4-chunk, 2 tail]
        assert np.asarray(items[0][0]).ndim == 5
        assert [np.asarray(i[0]).ndim for i in items[1:]] == [4, 4]
        capped = list(loader.iter_chunks(2, max_batches=3))
        ndims = [np.asarray(i[0]).ndim for i in capped]
        assert ndims == [5, 4]  # 3 batches -> one 2-chunk + one single

    def test_loader_records_pipeline_stats(self, tpk_train):
        from turboprune_tpu.data.native import TpkImageLoader

        loader = TpkImageLoader(
            tpk_train, total_batch_size=8, train=True, image_size=8
        )
        assert loader.last_pipeline_stats is None
        list(loader)
        stats = loader.last_pipeline_stats
        assert stats["batches_decoded"] == 6
        assert stats["items_emitted"] == 6


def _tiny_mlp():
    """Conv-free model: XLA compiles the per-step program and the scanned
    body to the SAME elementwise/matmul arithmetic, so scan-vs-loop parity
    is BIT-EXACT (conv/BN models reassociate reductions between programs —
    see tests/test_scan_epoch.py's documented ~1e-7 noise)."""
    import flax.linen as nn

    class TinyMLP(nn.Module):
        num_classes: int = 4

        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(16)(x)
            x = nn.relu(x)
            return nn.Dense(self.num_classes)(x)

    return TinyMLP()


class TestScanChunk:
    def test_scan_chunk_bit_exact_vs_sequential_steps(self):
        """make_scan_chunk(K) over K stacked batches == K sequential
        train_step calls on the same state: params, opt_state, step counter
        and metric sums all BITWISE identical."""
        from turboprune_tpu.train import (
            create_optimizer,
            create_train_state,
            make_scan_chunk,
            make_train_step,
        )

        model = _tiny_mlp()
        tx = create_optimizer("SGD", 0.1, momentum=0.9, weight_decay=5e-4)
        state0 = create_train_state(
            model, tx, jax.random.PRNGKey(0), (1, 8, 8, 3)
        )
        raw = make_train_step(model, tx, None)
        K = 4
        rng = np.random.default_rng(0)
        images = jnp.asarray(
            rng.normal(size=(K, 16, 8, 8, 3)).astype(np.float32)
        )
        labels = jnp.asarray(rng.integers(0, 4, size=(K, 16)), jnp.int32)

        step = jax.jit(raw)
        s_loop = state0
        sums = None
        for i in range(K):
            s_loop, m = step(s_loop, (images[i], labels[i]))
            sums = m if sums is None else jax.tree.map(jnp.add, sums, m)

        scan = jax.jit(make_scan_chunk(raw))
        s_scan, scan_sums = scan(state0, (images, labels))

        assert int(s_scan.step) == int(s_loop.step) == K
        for a, b in zip(
            jax.tree.leaves((s_scan.params, s_scan.opt_state)),
            jax.tree.leaves((s_loop.params, s_loop.opt_state)),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for key in ("correct", "count"):  # integer-valued: exact
            np.testing.assert_array_equal(
                np.asarray(scan_sums[key]), np.asarray(sums[key])
            )
        # loss_sum alone is reduced K-ways inside the scan program vs
        # sequential host adds in the loop — the pairing differs, so the
        # last float bit can too (~1e-7); the bit-exact claim is the STATE.
        np.testing.assert_allclose(
            float(scan_sums["loss_sum"]), float(sums["loss_sum"]), rtol=1e-6
        )


@pytest.mark.usefixtures("tpk_train")
class TestStreamedChunkedHarness:
    def test_harness_chunked_epoch_dispatch_count_and_metrics(
        self, tpk_train, tmp_path
    ):
        """End-to-end streamed chunked path on synthetic .tpk data (the
        scripts/check.sh fast-tier smoke): one train epoch through
        PruningHarness with scan_chunk_steps=3 must run ceil(6/3)=2 scan
        dispatches and ZERO per-step dispatches — a 3x (=K) dispatch
        reduction — and produce exact sample accounting."""
        from turboprune_tpu.config.compose import compose
        from turboprune_tpu.data.native import write_tpk_raw
        from turboprune_tpu.harness.pruning_harness import PruningHarness

        rng = np.random.default_rng(1)
        val = tmp_path / "val.tpk"
        write_tpk_raw(
            val,
            rng.integers(0, 256, size=(16, 8, 8, 3), dtype=np.uint8),
            rng.integers(0, 4, size=(16,)).astype(np.int32),
        )
        cfg = compose(
            "cifar10_imp",
            overrides=[
                f"experiment_params.base_dir={tmp_path}",
                "dataset_params.dataloader_type=tpk",
                f"dataset_params.tpk_train_path={tpk_train}",
                f"dataset_params.tpk_val_path={val}",
                "dataset_params.total_batch_size=8",
                "dataset_params.image_size=8",
                "dataset_params.num_classes=4",
                "dataset_params.scan_chunk_steps=3",
                "experiment_params.epochs_per_level=1",
                "experiment_params.training_precision=float32",
                "optimizer_params.lr=0.01",
                "model_params.model_name=resnet18",
            ],
        )
        harness = PruningHarness(cfg, ("smoke", str(tmp_path / "expt")))
        harness.setup_level(1)
        calls = {"scan": 0, "step": 0}
        orig_scan = harness._steps.scan_chunk
        orig_step = harness._steps.train_step

        def counting_scan(*a):
            calls["scan"] += 1
            return orig_scan(*a)

        def counting_step(*a):
            calls["step"] += 1
            return orig_step(*a)

        harness._steps = harness._steps._replace(
            scan_chunk=counting_scan, train_step=counting_step
        )
        row = harness.train_epoch()
        # 48 samples / batch 8 = 6 batches; K=3 -> 2 scans, no tail steps.
        assert calls == {"scan": 2, "step": 0}
        assert np.isfinite(row["train_loss"])
        stats = harness.loaders.train_loader.last_pipeline_stats
        assert stats["batches_decoded"] == 6
        assert stats["items_emitted"] == 2  # K batches per emitted chunk
