"""Serving subsystem tests (turboprune_tpu/serve/).

Covers the ISSUE-1 acceptance criteria on the CPU backend:
  - InferenceEngine logits on a pruned (density < 1) checkpoint are
    BIT-IDENTICAL to the harness evaluate forward on the same inputs
  - bucket padding never changes valid-row results; oversized batches chunk
  - batcher flushes on max-batch AND on deadline; bounded-queue backpressure
  - end-to-end HTTP round-trip (/predict, /healthz, /metrics) against a
    synthetic-data experiment checkpoint
  - a burst of mixed-size requests causes ZERO steady-state recompiles
    (compile-cache hit stats asserted)

One module-scope engine (warmed once) backs both the direct-engine tests
and the HTTP server: compiles are the wall-clock cost on this 1-core
container (no persistent compile cache — see conftest.py), so every test
that can reuse an already-compiled bucket does.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from turboprune_tpu.config.compose import compose
from turboprune_tpu.config.schema import ConfigError, ServeConfig, config_from_dict
from turboprune_tpu.driver import run
from turboprune_tpu.serve import (
    DynamicBatcher,
    InferenceEngine,
    InferenceServer,
    QueueFullError,
    ServeMetrics,
    build_server,
)

BUCKETS = (2, 4, 8)


@pytest.fixture(scope="module")
def expt(tmp_path_factory):
    """A tiny finished experiment: 2 levels (densities 1.0, 0.8), synthetic
    CIFAR-shape data — the checkpoint the whole module serves."""
    base = tmp_path_factory.mktemp("serve_expt")
    cfg = compose(
        "cifar10_imp",
        overrides=[
            f"experiment_params.base_dir={base}",
            "dataset_params.dataloader_type=synthetic",
            "dataset_params.total_batch_size=16",
            "dataset_params.synthetic_num_train=64",
            "dataset_params.synthetic_num_test=32",
            "experiment_params.epochs_per_level=1",
            "experiment_params.max_steps_per_epoch=2",
            "pruning_params.target_sparsity=0.2",  # ladder [1.0, 0.8]
            "model_params.model_name=resnet18",
        ],
    )
    expt_dir, summaries = run(cfg)
    assert len(summaries) == 2
    return cfg, expt_dir


@pytest.fixture(scope="module")
def engine(expt):
    """The shared serving engine: highest level (pruned), warmed buckets."""
    _, expt_dir = expt
    eng = InferenceEngine.from_experiment(
        expt_dir, buckets=BUCKETS, metrics=ServeMetrics()
    )
    eng.warmup()
    return eng


def _reference_forward(expt_dir: str, images: np.ndarray) -> np.ndarray:
    """The harness evaluate forward, reconstructed verbatim: eval_step
    (train/steps.py make_eval_step) builds
    ``{"params": apply_masks(params, masks), "batch_stats": ...}`` and runs
    ``model.apply(..., train=False)`` inside jit — same expression here, on
    the level checkpoint restored independently of the engine."""
    from turboprune_tpu.harness.pruning_harness import PRECISION_DTYPES
    from turboprune_tpu.models import create_model
    from turboprune_tpu.ops.masking import apply_masks, make_masks
    from turboprune_tpu.train.state import init_variables
    from turboprune_tpu.utils.checkpoint import (
        ExperimentCheckpoints,
        restore_model_tree,
    )

    cfg = config_from_dict(
        yaml.safe_load(open(f"{expt_dir}/expt_config.yaml"))
    )
    dp = cfg.dataset_params
    model = create_model(
        cfg.model_params.model_name,
        num_classes=dp.num_classes,
        dataset_name=dp.dataset_name,
        compute_dtype=PRECISION_DTYPES[
            cfg.experiment_params.training_precision
        ],
    )
    variables = init_variables(
        model, jax.random.PRNGKey(0), (1, dp.image_size, dp.image_size, 3)
    )
    ckpts = ExperimentCheckpoints(expt_dir)
    level = ckpts.saved_levels()[-1]
    restored = restore_model_tree(
        ckpts.level_path(level),
        {
            "params": variables["params"],
            "masks": make_masks(variables["params"]),
            "batch_stats": variables.get("batch_stats", {}),
        },
    )

    def fwd(v, x):
        var = {"params": apply_masks(v["params"], v["masks"])}
        if v["batch_stats"]:
            var["batch_stats"] = v["batch_stats"]
        return model.apply(var, x, train=False)

    logits = jax.jit(fwd)(restored, jnp.asarray(images, jnp.float32))
    return np.asarray(jax.device_get(logits), np.float32)


class TestEngine:
    def test_pruned_logits_bit_identical_to_evaluate_forward(
        self, expt, engine
    ):
        _, expt_dir = expt
        assert engine.level == 1
        assert engine.density < 1.0  # genuinely pruned checkpoint
        rng = np.random.default_rng(0)
        images = rng.standard_normal((4, 32, 32, 3)).astype(np.float32)
        got = engine.predict(images)  # 4 = exact bucket, no padding
        want = _reference_forward(expt_dir, images)
        assert got.shape == (4, 10)
        assert np.array_equal(got, want)  # bit-identical, not just close

    def test_bucket_padding_never_changes_valid_rows(self, expt, engine):
        _, expt_dir = expt
        rng = np.random.default_rng(1)
        images = rng.standard_normal((3, 32, 32, 3)).astype(np.float32)
        got = engine.predict(images)  # 3 -> padded to bucket 4
        want = _reference_forward(expt_dir, images)  # unpadded shape 3
        assert got.shape == (3, 10)
        assert np.array_equal(got, want)

    def test_oversized_batch_chunks_at_largest_bucket(self, engine):
        rng = np.random.default_rng(2)
        images = rng.standard_normal((11, 32, 32, 3)).astype(np.float32)
        got = engine.predict(images)  # chunks: 8 + 3(->bucket 4)
        # Chunk-stitching must agree with the per-chunk forwards (whose
        # bit-identity to the evaluate forward the tests above establish).
        want = np.concatenate(
            [engine.predict(images[:8]), engine.predict(images[8:])]
        )
        assert got.shape == (11, 10)
        assert np.array_equal(got, want)

    def test_compile_cache_zero_steady_state_recompiles(self, engine):
        metrics = engine.metrics
        misses_before = metrics.counter("compile_cache_misses_total")
        assert misses_before == len(BUCKETS)  # warmup compiled every bucket
        assert engine.compiled_buckets == BUCKETS
        hits_before = metrics.counter("compile_cache_hits_total")
        rng = np.random.default_rng(3)
        for n in (1, 3, 8, 2, 5, 7, 4, 6, 1, 8):  # mixed-size burst
            engine.predict(
                rng.standard_normal((n, 32, 32, 3)).astype(np.float32)
            )
        # Steady state: every request hit a warm bucket — zero new traces.
        assert metrics.counter("compile_cache_misses_total") == misses_before
        assert metrics.counter("compile_cache_hits_total") >= hits_before + 10

    def test_compact_load_path_matches_dense_engine(self, expt, engine):
        """serve.compact: the engine slices dead channels, AOT-compiles the
        smaller model, and serves logits equivalent to the mask-folded
        path (identical here: this mag-pruned checkpoint has scattered
        zeros, no dead fan-out slices, so compaction is the identity —
        which the report must say honestly)."""
        _, expt_dir = expt
        metrics = ServeMetrics()
        eng = InferenceEngine.from_experiment(
            expt_dir, buckets=(4,), metrics=metrics, compact=True
        )
        assert eng.density < 1.0
        info = eng.info()["compaction"]
        assert info["params_after"] <= info["params_before"]
        assert metrics.snapshot()["plan_params_compacted"] == info[
            "params_after"
        ]
        rng = np.random.default_rng(7)
        images = rng.standard_normal((4, 32, 32, 3)).astype(np.float32)
        got = eng.predict(images)
        want = engine.predict(images)
        # Identity compaction -> same program modulo recompilation; bound
        # covers fp reassociation for the general (sliced) case too.
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    def test_role_checkpoint_and_bad_shapes(self, expt):
        _, expt_dir = expt
        eng = InferenceEngine.from_experiment(
            expt_dir, role="model_init", buckets=(2,), metrics=ServeMetrics()
        )
        assert eng.level is None
        assert eng.density == 1.0  # init checkpoint is dense
        # Shape validation fires before any compile/execution.
        with pytest.raises(ValueError):
            eng.predict(np.zeros((2, 16, 16, 3), np.float32))
        with pytest.raises(ValueError):
            eng.predict(np.zeros((0, 32, 32, 3), np.float32))


class _FakeEngine:
    """Deterministic row-wise 'model' so batcher tests skip jax entirely."""

    input_shape = (4, 4, 3)

    def __init__(self):
        rng = np.random.default_rng(0)
        self._w = rng.standard_normal((4 * 4 * 3, 5)).astype(np.float32)

    def predict(self, images: np.ndarray) -> np.ndarray:
        # Row-at-a-time on purpose: one big (n, d) @ (d, k) matmul takes
        # batch-size-dependent BLAS paths whose accumulation order differs
        # in the last bit, and the scatter tests compare the batched run
        # bit-exactly against per-request runs.
        return np.stack([row.reshape(-1) @ self._w for row in images])


def _fake_images(rng, n):
    return rng.standard_normal((n, 4, 4, 3)).astype(np.float32)


class TestBatcher:
    def test_flush_on_max_batch(self):
        metrics = ServeMetrics()
        engine = _FakeEngine()
        batcher = DynamicBatcher(
            engine, max_batch=4, max_wait_ms=5000.0, queue_depth=16,
            metrics=metrics,
        ).start()
        rng = np.random.default_rng(0)
        imgs = [_fake_images(rng, 1) for _ in range(4)]
        t0 = time.perf_counter()
        futures = [batcher.submit(x) for x in imgs]
        results = [f.result(timeout=10) for f in futures]
        elapsed = time.perf_counter() - t0
        batcher.close()
        # 4 rows == max_batch: flushed by SIZE, far before the 5s deadline.
        assert elapsed < 3.0
        for x, r in zip(imgs, results):
            assert np.array_equal(r, engine.predict(x))
        assert metrics.counter("batches_total") == 1
        assert metrics.counter("images_total") == 4

    def test_flush_on_deadline(self):
        metrics = ServeMetrics()
        engine = _FakeEngine()
        batcher = DynamicBatcher(
            engine, max_batch=64, max_wait_ms=300.0, queue_depth=16,
            metrics=metrics,
        ).start()
        rng = np.random.default_rng(1)
        imgs = [_fake_images(rng, k) for k in (1, 2, 3)]
        t0 = time.perf_counter()
        futures = [batcher.submit(x) for x in imgs]
        results = [f.result(timeout=10) for f in futures]
        elapsed = time.perf_counter() - t0
        batcher.close()
        # 6 rows < max_batch: only the DEADLINE can have flushed this.
        assert elapsed >= 0.2
        assert metrics.counter("batches_total") == 1
        assert metrics.counter("images_total") == 6
        for x, r in zip(imgs, results):  # scatter returned each caller's rows
            assert np.array_equal(r, engine.predict(x))

    def test_bounded_queue_backpressure(self):
        metrics = ServeMetrics()
        batcher = DynamicBatcher(  # worker NOT started: queue only fills
            _FakeEngine(), max_batch=4, max_wait_ms=10.0, queue_depth=2,
            metrics=metrics,
        )
        rng = np.random.default_rng(2)
        batcher.submit(_fake_images(rng, 1))
        batcher.submit(_fake_images(rng, 1))
        with pytest.raises(QueueFullError):
            batcher.submit(_fake_images(rng, 1))
        assert metrics.counter("rejected_total") == 1
        batcher.close()

    def test_engine_error_propagates_and_batcher_survives(self):
        class Exploding(_FakeEngine):
            def __init__(self):
                super().__init__()
                self.fail_next = True

            def predict(self, images):
                if self.fail_next:
                    self.fail_next = False
                    raise RuntimeError("boom")
                return super().predict(images)

        engine = Exploding()
        batcher = DynamicBatcher(
            engine, max_batch=2, max_wait_ms=10.0, queue_depth=16,
            metrics=ServeMetrics(),
        ).start()
        rng = np.random.default_rng(3)
        with pytest.raises(RuntimeError, match="boom"):
            batcher.predict(_fake_images(rng, 1), timeout=10)
        ok = batcher.predict(_fake_images(rng, 1), timeout=10)  # still alive
        assert ok.shape == (1, 5)
        batcher.close()


@pytest.fixture(scope="module")
def server(engine):
    srv = InferenceServer(
        engine,
        host="127.0.0.1",
        port=0,  # ephemeral
        max_batch=8,
        max_wait_ms=10.0,
        queue_depth=64,
        metrics=engine.metrics,
    ).start_background()
    yield srv
    srv.close()


def _get(srv, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{srv.port}{path}", timeout=30
    ) as r:
        return r.status, r.read()


def _post_predict(srv, instances):
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/predict",
        data=json.dumps({"instances": instances}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.status, json.loads(r.read())


class TestHTTP:
    def test_healthz(self, server):
        status, body = _get(server, "/healthz")
        health = json.loads(body)
        assert status == 200
        assert health["status"] == "ok"
        assert health["level"] == 1
        assert health["density"] < 1.0
        assert health["buckets"] == list(BUCKETS)
        assert health["compiled_buckets"] == list(BUCKETS)  # warmed up

    def test_predict_round_trip_matches_engine(self, server, engine):
        rng = np.random.default_rng(4)
        images = rng.standard_normal((2, 32, 32, 3)).astype(np.float32)
        status, resp = _post_predict(server, images.tolist())
        assert status == 200
        got = np.asarray(resp["logits"], np.float32)
        want = engine.predict(images)
        assert np.array_equal(got, want)  # JSON round-trip is exact for f32
        assert resp["classes"] == np.argmax(want, axis=-1).tolist()
        assert resp["model_level"] == 1

    def test_single_unbatched_image(self, server):
        rng = np.random.default_rng(5)
        status, resp = _post_predict(
            server, rng.standard_normal((32, 32, 3)).astype(np.float32).tolist()
        )
        assert status == 200
        assert len(resp["logits"]) == 1

    def test_mixed_burst_zero_steady_state_recompiles(self, server):
        misses_before = server.metrics.counter("compile_cache_misses_total")
        assert misses_before == len(BUCKETS)  # warmup compiled everything
        rng = np.random.default_rng(6)

        def client(cid):
            for n in (1, 3, 5, 2):
                _post_predict(
                    server,
                    rng.standard_normal((n, 32, 32, 3))
                    .astype(np.float32)
                    .tolist(),
                )

        threads = [
            threading.Thread(target=client, args=(c,)) for c in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert (
            server.metrics.counter("compile_cache_misses_total")
            == misses_before
        )  # ZERO recompiles at steady state
        assert server.metrics.counter("requests_total") >= 12

    def test_metrics_endpoint_prometheus_text(self, server):
        status, body = _get(server, "/metrics")
        text = body.decode()
        assert status == 200
        assert (
            f"turboprune_serve_compile_cache_misses_total {len(BUCKETS)}"
            in text
        )
        assert "turboprune_serve_requests_total" in text
        assert 'turboprune_serve_request_latency_ms_bucket{le="+Inf"}' in text
        assert "turboprune_serve_request_latency_ms_sum" in text
        assert "turboprune_serve_request_latency_p99_ms" in text
        assert "turboprune_serve_queue_depth" in text

    def test_bad_requests(self, server):
        with pytest.raises(urllib.error.HTTPError) as e:
            _post_predict(server, [[1.0, 2.0]])  # wrong rank/shape
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(server, "/nope")
        assert e.value.code == 404


class TestServeConfig:
    def test_compose_serve_group(self):
        cfg = compose("serve", ["serve.port=9999", "serve.max_batch=16"])
        assert cfg.serve.port == 9999
        assert cfg.serve.max_batch == 16
        assert cfg.serve.batch_buckets == [1, 8, 32, 128]

    def test_serve_group_appends_to_training_config(self):
        cfg = compose("cifar10_imp", ["+serve=default"])
        assert cfg.serve is not None
        assert cfg.serve.warmup is True

    def test_training_configs_carry_no_serve_group(self):
        assert compose("cifar10_imp", []).serve is None

    def test_validation(self):
        with pytest.raises(ConfigError):
            ServeConfig(batch_buckets=[8, 2]).validate()  # not increasing
        with pytest.raises(ConfigError):
            ServeConfig(batch_buckets=[]).validate()
        with pytest.raises(ConfigError):
            ServeConfig(max_batch=0).validate()
        with pytest.raises(ConfigError):
            ServeConfig(port=70000).validate()
        ServeConfig().validate()  # defaults are valid

    def test_build_server_from_config(self, expt):
        _, expt_dir = expt
        cfg = compose(
            "serve",
            [
                "serve.port=0",
                f"serve.expt_dir={expt_dir}",
                "serve.batch_buckets=[2, 4, 8]",
                "serve.warmup=false",  # no compiles: construction-only test
            ],
        )
        srv = build_server(cfg)
        try:
            assert srv.engine.level == 1
            assert srv.engine.buckets == (2, 4, 8)
        finally:
            srv.close()

    def test_build_server_requires_serve_group_and_dir(self):
        with pytest.raises(ConfigError):
            build_server(compose("cifar10_imp", []))
        with pytest.raises(ConfigError):
            build_server(compose("serve", []))  # no expt dir anywhere


class TestSatellites:
    def test_cyclic_rejects_mid_level_checkpointing(self, tmp_path):
        """checkpoint_every_epochs is a silent no-op under the cyclic loop —
        it must fail loudly instead (ADVICE r5)."""
        from turboprune_tpu.driver import run_cyclic

        cfg = compose(
            "cifar10_imp",
            overrides=[
                f"experiment_params.base_dir={tmp_path}",
                "dataset_params.dataloader_type=synthetic",
                "dataset_params.total_batch_size=16",
                "dataset_params.synthetic_num_train=64",
                "dataset_params.synthetic_num_test=32",
                "experiment_params.epochs_per_level=2",
                "experiment_params.checkpoint_every_epochs=1",
                "cyclic_training.num_cycles=2",
            ],
        )
        with pytest.raises(ConfigError, match="cyclic"):
            run_cyclic(cfg)
