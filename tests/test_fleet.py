"""Fleet serving tests (turboprune_tpu/serve/fleet/ + loadgen).

Covers the ISSUE-11 acceptance criteria on the CPU backend:
  - one process serves >= 3 checkpoints of one IMP run — masked-dense,
    dead-channel-compacted, and N:M-gathered — routed on the request's
    "model" field, with per-model logits parity <= 1e-6 against
    single-model engines
  - zero steady-state recompiles per model (per-model compile counters)
  - the on-disk AOT executable cache: miss -> store -> hit, version
    mismatch -> bypass (never a wrong-executable hit), corrupt entry ->
    quarantine, and a warm cache makes engine re-construction COMPILE-FREE
    (xla_compiles_total == 0 asserted)
  - LRU weight paging under max_resident_models, with metrics surviving
    eviction/re-page-in
  - metrics-registry collision fix: two models' identically-named series
    render as distinct labelled samples under one # TYPE line
  - graceful drain: in-flight requests answered, post-drain submits shed
  - open-loop load generator: p50/p99/p99.9 vs offered load with the
    saturation knee detected at the overloaded point
  - serve.fleet config schema: compose-time rejection of unknown keys and
    out-of-set choice values (the graftlint conf-* literal sets)

The checkpoint fixture is built WITHOUT training: a dense init plus
hand-constructed mask trees (dense / channel-structured / 2:4-projected)
saved through the real checkpoint writer — the engines under test cannot
tell the difference, and the module avoids minutes of IMP on this 1-core
container. Compiles are the wall-clock cost here (no persistent XLA cache,
see conftest.py), so the module uses one bucket and shares one AOT cache
dir fleet-wide: later engines load serialized executables instead of
invoking XLA.
"""

import json
import shutil
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from turboprune_tpu.config.compose import compose
from turboprune_tpu.config.schema import ConfigError, FleetConfig
from turboprune_tpu.serve import (
    AOTExecutableCache,
    DynamicBatcher,
    FleetEngine,
    InferenceEngine,
    InferenceServer,
    MetricsHub,
    ModelRegistry,
    QueueFullError,
    ServeMetrics,
    UnknownModelError,
    detect_knee,
    open_cache,
    run_open_loop,
    sweep_offered_load,
)
from turboprune_tpu.utils.checkpoint import ExperimentCheckpoints

BUCKETS = (2,)  # one bucket: every compile in this module is deliberate


# --------------------------------------------------------------- fixtures
def _channel_structured_masks(params, graph, kill_frac):
    """Kill the smallest-L2 fan-out slices per compactable space — the
    structure dead-channel compaction rewards."""
    from turboprune_tpu.ops import masking

    masks = jax.tree.map(
        lambda m: None if m is None else np.array(m),
        masking.make_masks(params),
        is_leaf=lambda v: v is None,
    )
    for sp in graph.spaces.values():
        node = masks
        leaf = params
        for k in sp.producer.kernel[:-1]:
            node = node[k]
            leaf = leaf[k]
        kernel = np.asarray(
            jax.device_get(leaf[sp.producer.kernel[-1]]), np.float32
        )
        norms = np.sqrt(
            (kernel.reshape(-1, kernel.shape[-1]) ** 2).sum(axis=0)
        )
        order = np.argsort(norms)
        m = node[sp.producer.kernel[-1]]
        m[..., order[: int(len(order) * kill_frac)]] = False
    return jax.tree.map(
        lambda m: None if m is None else jnp.asarray(m),
        masks,
        is_leaf=lambda v: v is None,
    )


@pytest.fixture(scope="module")
def fleet_expt(tmp_path_factory):
    """A 3-level experiment dir: level_0 dense, level_1 channel-structured
    (compactable), level_2 transposable-2:4-projected (nm-routable)."""
    from turboprune_tpu.models import create_model
    from turboprune_tpu.ops import masking
    from turboprune_tpu.sparse import build_graph
    from turboprune_tpu.sparse.nm import project_masks
    from turboprune_tpu.train.state import init_variables
    from turboprune_tpu.utils.checkpoint import save_model_tree
    from turboprune_tpu.utils.experiment import save_config

    base = tmp_path_factory.mktemp("fleet")
    expt_dir = base / "fleet_expt"
    expt_dir.mkdir()
    cfg = compose(
        "cifar10_imp",
        overrides=[
            f"experiment_params.base_dir={base}",
            "experiment_params.training_precision=float32",
            "dataset_params.dataloader_type=synthetic",
            "dataset_params.total_batch_size=16",
            "model_params.model_name=resnet18",
        ],
    )
    save_config(str(expt_dir), cfg)
    model = create_model("resnet18", 10, "CIFAR10", jnp.float32)
    variables = init_variables(model, jax.random.PRNGKey(0), (1, 32, 32, 3))
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    dense = masking.make_masks(params)
    graph = build_graph(model, params)
    channel = _channel_structured_masks(params, graph, 0.5)
    nm_masks, _ = project_masks(params, dense, 2, 4, transposable=True)
    ckpts = ExperimentCheckpoints(expt_dir)
    ckpts.checkpoints_dir.mkdir(parents=True, exist_ok=True)
    for lvl, masks in enumerate((dense, channel, nm_masks)):
        save_model_tree(
            ckpts.level_path(lvl),
            {"params": params, "masks": masks, "batch_stats": batch_stats},
        )
    return expt_dir


@pytest.fixture(scope="module")
def aot_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("aot")


@pytest.fixture(scope="module")
def fleet(fleet_expt, aot_dir):
    """The shared fleet: all 3 levels, auto backend, shared AOT cache."""
    eng = FleetEngine(
        ModelRegistry([fleet_expt]),
        buckets=BUCKETS,
        max_resident_models=4,
        aot_cache=AOTExecutableCache(aot_dir),
        max_batch=8,
        max_wait_ms=5.0,
        queue_depth=64,
    )
    yield eng
    eng.close()


def _images(seed, n):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, 32, 32, 3)).astype(np.float32)


# ---------------------------------------------------------------- registry
class TestRegistry:
    def test_scan_ids_and_default_routes(self, fleet_expt):
        reg = ModelRegistry([fleet_expt])
        assert reg.ids() == ["level_0", "level_1", "level_2"]
        assert len(reg) == 3
        assert reg.default_id("latest") == "level_2"
        assert reg.default_id("dense") == "level_0"
        assert reg.default_id("pinned", "level_1") == "level_1"
        assert reg.resolve(None, default_route="latest").level == 2
        assert reg.resolve("level_1").model_id == "level_1"

    def test_unknown_model_lists_known_ids(self, fleet_expt):
        reg = ModelRegistry([fleet_expt])
        with pytest.raises(UnknownModelError) as e:
            reg.get("level_99")
        assert "level_0" in str(e.value) and "level_99" in str(e.value)
        with pytest.raises(UnknownModelError):
            reg.default_id("pinned", "")  # pinned route needs a real id

    def test_multi_dir_prefixes_and_duplicate_basename(
        self, fleet_expt, tmp_path
    ):
        second = tmp_path / "fleet_b"
        second.mkdir()
        shutil.copy(fleet_expt / "expt_config.yaml", second)
        ckpts = ExperimentCheckpoints(second)
        ckpts.checkpoints_dir.mkdir(parents=True, exist_ok=True)
        shutil.copytree(
            ExperimentCheckpoints(fleet_expt).level_path(0),
            ckpts.level_path(0),
        )
        reg = ModelRegistry([fleet_expt, second])
        assert f"{fleet_expt.name}/level_0" in reg.ids()
        assert "fleet_b/level_0" in reg.ids()
        # latest still resolves within the FIRST experiment
        assert reg.default_id("latest") == f"{fleet_expt.name}/level_2"
        with pytest.raises(ValueError, match="duplicate model id"):
            ModelRegistry([fleet_expt, fleet_expt])

    def test_not_an_experiment_dir(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            ModelRegistry([tmp_path])


# --------------------------------------------------------------- AOT cache
@pytest.fixture()
def tiny_lowered():
    spec = jax.ShapeDtypeStruct((4,), jnp.float32)
    return jax.jit(lambda x: x * 2.0 + 1.0).lower(spec)


class TestAOTCache:
    def test_miss_store_hit_round_trip(self, tmp_path, tiny_lowered):
        cache = AOTExecutableCache(tmp_path)
        key = cache.make_key(
            hlo_fingerprint=cache.fingerprint(tiny_lowered), bucket=4
        )
        fn, status = cache.load(key)
        assert fn is None and status == "miss"
        assert cache.store(key, tiny_lowered.compile())
        fn, status = cache.load(key)
        assert status == "hit"
        out = fn(jnp.arange(4, dtype=jnp.float32))
        np.testing.assert_array_equal(
            np.asarray(out), np.arange(4, dtype=np.float32) * 2 + 1
        )
        stats = cache.stats()
        assert stats["entries"] == 1
        assert stats == {**stats, "hit": 1, "miss": 1, "stores": 1}

    def test_version_mismatch_bypasses_then_overwrites(
        self, tmp_path, tiny_lowered
    ):
        import pickle

        cache = AOTExecutableCache(tmp_path)
        key = cache.make_key(
            hlo_fingerprint=cache.fingerprint(tiny_lowered), bucket=4
        )
        cache.store(key, tiny_lowered.compile())
        path = cache._path(key)
        entry = pickle.loads(path.read_bytes())
        entry["meta"]["jax"] = "0.0.0"  # a different toolchain's build
        path.write_bytes(pickle.dumps(entry))
        fn, status = cache.load(key)
        assert fn is None and status == "bypass"
        assert path.exists()  # bypass ignores, never destroys
        # ...and the current environment's store wins the slot back.
        cache.store(key, tiny_lowered.compile())
        _, status = cache.load(key)
        assert status == "hit"

    def test_corrupt_entry_quarantined(self, tmp_path, tiny_lowered):
        cache = AOTExecutableCache(tmp_path)
        key = cache.make_key(
            hlo_fingerprint=cache.fingerprint(tiny_lowered), bucket=4
        )
        cache._path(key).write_bytes(b"\x80not a pickle")
        fn, status = cache.load(key)
        assert fn is None and status == "corrupt"
        assert not cache._path(key).exists()  # renamed out of the way
        assert cache.stats()["quarantined"] == 1
        _, status = cache.load(key)  # slot is clean again
        assert status == "miss"

    def test_key_covers_plan_and_bucket(self, tmp_path):
        cache = AOTExecutableCache(tmp_path)
        k = lambda plan, b: cache.make_key(  # noqa: E731
            hlo_fingerprint="f" * 64, plan_signature=plan, bucket=b
        )
        assert k(("masked",), 2) != k(("masked",), 4)
        assert k(("masked",), 2) != k(("compact", (("fc", 10),)), 2)

    def test_open_cache_disabled_by_empty(self, tmp_path):
        assert open_cache("") is None
        assert open_cache(None) is None
        assert isinstance(open_cache(tmp_path), AOTExecutableCache)


# ------------------------------------------------------------ fleet engine
class TestFleetEngine:
    def test_serves_three_backends_with_parity(self, fleet_expt, fleet):
        """The acceptance core: >= 3 checkpoints, one process, auto picks
        masked/compact/nm per checkpoint, and every routed answer matches
        the single-model masked engine within 1e-6."""
        images = _images(0, 2)
        want_backend = {"level_0": "masked", "level_1": "compact",
                        "level_2": "nm"}
        for model_id, backend in want_backend.items():
            got = fleet.predict(images, model=model_id, timeout=120)
            eng = InferenceEngine.from_experiment(
                fleet_expt,
                level=int(model_id.split("_")[1]),
                buckets=BUCKETS,
                backend="masked",
                metrics=ServeMetrics(),
                aot_cache=fleet.aot_cache,  # same arch -> reuses entries
            )
            want = eng.predict(images)
            assert np.abs(got - want).max() <= 1e-6, model_id
            info = fleet.info()["models"][model_id]
            assert info["backend"] == backend
            assert info["resident"] is True
        assert fleet.info()["models"]["level_1"]["compaction"][
            "params_after"
        ] < fleet.info()["models"]["level_1"]["compaction"]["params_before"]
        assert fleet.info()["models"]["level_2"]["nm"]["routed_layers"] >= 1

    def test_default_route_is_latest(self, fleet):
        assert fleet.default_model == "level_2"
        future, resident = fleet.submit(_images(1, 2))
        future.result(timeout=60)
        assert resident.spec.model_id == "level_2"

    def test_zero_steady_state_recompiles_per_model(self, fleet):
        """After first contact, traffic to every model causes ZERO new
        traces — asserted per model on the hub's labelled counters."""
        for model_id in ("level_0", "level_1", "level_2"):
            fleet.predict(_images(2, 2), model=model_id, timeout=60)
        before = {
            m: fleet.hub.counter("compile_cache_misses_total", m)
            for m in ("level_0", "level_1", "level_2")
        }
        assert all(v == len(BUCKETS) for v in before.values())
        for i in range(4):
            for model_id in ("level_0", "level_1", "level_2"):
                fleet.predict(_images(3 + i, 1), model=model_id, timeout=60)
        for model_id, misses in before.items():
            assert (
                fleet.hub.counter("compile_cache_misses_total", model_id)
                == misses
            ), model_id
            assert (
                fleet.hub.counter("compile_cache_hits_total", model_id) >= 4
            )

    def test_warm_aot_cache_makes_reconstruction_compile_free(
        self, fleet_expt, fleet
    ):
        """Cold-start acceptance: with the cache warmed by the fleet above,
        building a brand-new fleet compiles NOTHING — every bucket comes
        off disk (xla_compiles_total stays 0 on the fresh hub)."""
        for model_id in ("level_0", "level_1", "level_2"):
            fleet.predict(_images(9, 2), model=model_id, timeout=60)
        hub = MetricsHub()
        fresh = FleetEngine(
            ModelRegistry([fleet_expt]),
            buckets=BUCKETS,
            aot_cache=AOTExecutableCache(fleet.aot_cache.dir),
            hub=hub,
            warmup=False,
        )
        try:
            for model_id in ("level_0", "level_1", "level_2"):
                fresh.predict(_images(10, 2), model=model_id, timeout=60)
                assert hub.counter("xla_compiles_total", model_id) == 0, (
                    model_id
                )
                assert (
                    hub.counter("aot_cache_hit_total", model_id)
                    == len(BUCKETS)
                )
        finally:
            fresh.close()

    def test_aot_keys_are_manifest_covered(self, fleet):
        """Exec-manifest closure over the persistent cache: every *.aotx
        the fleet wrote was minted through make_key (the key ledger — no
        anonymous executables on disk), every ledger plan kind is one the
        static manifest enumerates, every ledger bucket is one this fleet
        declared, and the production bucket union itself is covers()-ed."""
        from pathlib import Path

        from turboprune_tpu.analysis.exec_manifest import (
            build_manifest,
            covers,
        )

        for model_id in ("level_0", "level_1", "level_2"):
            fleet.predict(_images(20, 2), model=model_id, timeout=60)
        manifest = build_manifest()
        ledger = fleet.aot_cache.key_meta()
        on_disk = {
            p.stem for p in Path(fleet.aot_cache.dir).glob("*.aotx")
        }
        assert on_disk, "warm fleet should have persisted executables"
        assert on_disk <= set(ledger), "key(s) on disk the ledger never minted"
        kinds = {meta["plan_kind"] for meta in ledger.values()}
        assert kinds == {"masked", "compact", "nm"}
        assert kinds <= set(manifest["plan_kinds"])
        # The planner's fourth kind is declared even when this fixture's
        # checkpoints each collapse to a single backend: a heterogeneous
        # checkpoint mints ("mixed", widths, nm) keys, and the manifest
        # must already cover them.
        assert "mixed" in manifest["plan_kinds"]
        assert {meta["bucket"] for meta in ledger.values()} <= set(BUCKETS)
        # The production bucket set is covered end to end for every kind
        # this fleet exercised (the test fleet's (2,) is a deliberate
        # override; DEFAULT_BUCKETS is what ships).
        for kind in kinds | {"mixed"}:
            assert all(covers(manifest, kind, b) for b in manifest["buckets"])
        assert not covers(manifest, "mystery-plan", manifest["buckets"][0])

    def test_lru_eviction_and_page_back_in(self, fleet_expt, fleet):
        """max_resident_models=2: third model evicts the least-recently-used
        one; paging back in works and the evicted model's metrics instance
        keeps accumulating across the page cycle."""
        hub = MetricsHub()
        small = FleetEngine(
            ModelRegistry([fleet_expt]),
            buckets=BUCKETS,
            max_resident_models=2,
            aot_cache=AOTExecutableCache(fleet.aot_cache.dir),  # warm: fast
            hub=hub,
        )
        try:
            small.predict(_images(11, 2), model="level_0", timeout=60)
            small.predict(_images(11, 2), model="level_1", timeout=60)
            assert small.resident_ids == ["level_0", "level_1"]
            small.predict(_images(11, 2), model="level_2", timeout=60)
            assert small.resident_ids == ["level_1", "level_2"]
            assert small.metrics.counter("model_evictions_total") == 1
            assert small.metrics.counter("model_pageins_total") == 3
            # LRU refresh: touching level_1 makes level_2 the eviction victim
            small.predict(_images(12, 2), model="level_1", timeout=60)
            small.predict(_images(12, 2), model="level_0", timeout=60)
            assert small.resident_ids == ["level_1", "level_0"]
            # the paged-back-in model's counters survived eviction
            assert hub.counter("requests_total", "level_0") == 2
            assert hub.counter("model_pageins_total") == 4
            info = small.info()
            assert info["resident_models"] == 2
            assert info["models"]["level_2"]["resident"] is False
            assert info["models"]["level_2"]["level"] == 2  # still routable
        finally:
            small.close()


# ----------------------------------------------------------- metric labels
class TestMetricsLabels:
    def test_two_models_same_metric_render_distinct_series(self):
        """The PR-11 collision fix: before the hub, two engines writing
        plan_params_compacted silently overwrote each other."""
        hub = MetricsHub()
        hub.get("level_0").set_gauge("plan_params_compacted", 50)
        hub.get("level_1").set_gauge("plan_params_compacted", 80)
        text = hub.render_prometheus()
        assert (
            'turboprune_serve_plan_params_compacted{model="level_0"} 50'
            in text
        )
        assert (
            'turboprune_serve_plan_params_compacted{model="level_1"} 80'
            in text
        )
        # exactly one TYPE line per metric name (the spec requirement that
        # rules out naive per-model concatenation)
        assert (
            text.count(
                "# TYPE turboprune_serve_plan_params_compacted gauge"
            )
            == 1
        )

    def test_hub_returns_same_instance_per_model(self):
        hub = MetricsHub()
        assert hub.get("m") is hub.get("m")
        assert hub.get("") is hub.get("")
        assert hub.get("m") is not hub.get("")

    def test_unlabelled_exposition_format_unchanged(self):
        m = ServeMetrics()
        m.inc("compile_cache_misses_total", 3)
        text = m.render_prometheus()
        assert "turboprune_serve_compile_cache_misses_total 3\n" in text

    def test_label_values_escaped(self):
        m = ServeMetrics(labels=(("model", 'we"ird\\x'),))
        m.inc("requests_total")
        text = m.render_prometheus()
        assert 'model="we\\"ird\\\\x"' in text

    def test_histogram_buckets_carry_model_label(self):
        hub = MetricsHub()
        hub.get("level_3").observe_latency_ms(2.0)
        text = hub.render_prometheus()
        assert (
            'turboprune_serve_request_latency_ms_bucket{model="level_3",le="+Inf"} 1'
            in text
        )
        assert text.count("# TYPE turboprune_serve_request_latency_ms") == 1


# ------------------------------------------------------------------- HTTP
@pytest.fixture(scope="module")
def fleet_server(fleet):
    srv = InferenceServer(fleet=fleet, host="127.0.0.1", port=0)
    srv.start_background()
    yield srv
    # fleet teardown closes the engines; only the socket belongs to us here
    srv.shutdown()
    srv._server_close_once()


def _post(srv, payload, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/predict",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(srv, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{srv.port}{path}", timeout=30
    ) as r:
        return r.status, r.read()


class TestFleetHTTP:
    def test_predict_routes_on_model_field(self, fleet_server):
        imgs = _images(20, 2).tolist()
        status, resp = _post(
            fleet_server, {"instances": imgs, "model": "level_1"}
        )
        assert status == 200
        assert resp["model"] == "level_1"
        assert resp["backend"] == "compact"
        assert resp["model_level"] == 1
        assert len(resp["logits"]) == 2

    def test_default_route_no_model_field(self, fleet_server):
        status, resp = _post(fleet_server, {"instances": _images(21, 1).tolist()})
        assert status == 200
        assert resp["model"] == "level_2"
        assert resp["backend"] == "nm"

    def test_unknown_model_404_lists_known(self, fleet_server):
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(
                fleet_server,
                {"instances": _images(22, 1).tolist(), "model": "level_9"},
            )
        assert e.value.code == 404
        body = json.loads(e.value.read())
        assert "level_9" in body["error"] and "level_0" in body["error"]

    def test_healthz_reports_per_model_rows(self, fleet_server):
        status, body = _get(fleet_server, "/healthz")
        health = json.loads(body)
        assert status == 200 and health["status"] == "ok"
        assert health["default_model"] == "level_2"
        models = health["models"]
        assert set(models) == {"level_0", "level_1", "level_2"}
        for model_id, row in models.items():
            assert row["level"] == int(model_id.split("_")[1])
        assert models["level_1"]["backend"] == "compact"
        assert models["level_2"]["backend"] == "nm"
        assert "aot_cache" in health
        # the fleet-wide bucket surface is a first-class health field
        assert health["buckets"] == list(BUCKETS)

    def test_metrics_endpoint_labels_by_model(self, fleet_server):
        status, body = _get(fleet_server, "/metrics")
        text = body.decode()
        assert status == 200
        assert 'turboprune_serve_requests_total{model="level_1"}' in text
        assert 'turboprune_serve_requests_total{model="level_2"}' in text
        assert text.count("# TYPE turboprune_serve_requests_total counter") == 1
        assert "turboprune_serve_model_pageins_total" in text


# -------------------------------------------------------- graceful drain
class _FakeEngine:
    """Row-wise deterministic 'model' with a per-row service time, so drain
    and loadgen tests exercise real queueing without any jax compile."""

    input_shape = (4, 4, 3)
    level = 0
    density = 1.0

    def __init__(self, row_ms=0.0):
        self.row_s = row_ms / 1e3
        rng = np.random.default_rng(0)
        self._w = rng.standard_normal((4 * 4 * 3, 5)).astype(np.float32)

    def predict(self, images):
        if self.row_s:
            time.sleep(self.row_s * images.shape[0])
        return images.reshape(images.shape[0], -1) @ self._w

    def info(self):
        return {"level": self.level, "density": self.density}


def _fake_images(seed, n):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, 4, 4, 3)).astype(np.float32)


class TestBucketSurface:
    def test_batcher_bucket_sizes_is_replica_union(self, fleet):
        """bucket_sizes() is the sorted union across replica engines and
        tolerates engines with no bucket set (test doubles)."""

        class _Bucketed(_FakeEngine):
            def __init__(self, buckets):
                super().__init__()
                self.buckets = buckets

        batcher = DynamicBatcher(
            [_Bucketed((8, 2)), _Bucketed((2, 32)), _FakeEngine()]
        )
        try:
            assert batcher.bucket_sizes() == [2, 8, 32]
        finally:
            batcher.close()
        assert fleet.info()["buckets"] == list(BUCKETS)


class TestGracefulDrain:
    def test_drain_answers_inflight_then_sheds(self):
        batcher = DynamicBatcher(
            _FakeEngine(row_ms=2.0),
            max_batch=4,
            max_wait_ms=1.0,
            queue_depth=64,
            metrics=ServeMetrics(),
        ).start()
        futures = [batcher.submit(_fake_images(0, 1)) for _ in range(10)]
        report = batcher.drain(deadline_s=10.0)
        assert report == {"drained": True, "unanswered": 0}
        for f in futures:  # every accepted request was ANSWERED, not dropped
            assert f.result(timeout=0).shape == (1, 5)
        with pytest.raises(QueueFullError, match="draining"):
            batcher.submit(_fake_images(0, 1))

    def test_drain_deadline_bounds_the_wait(self):
        eng = _FakeEngine(row_ms=500.0)  # pathologically slow
        batcher = DynamicBatcher(
            eng, max_batch=2, max_wait_ms=1.0, queue_depth=8,
            metrics=ServeMetrics(),
        ).start()
        batcher.submit(_fake_images(1, 1))
        time.sleep(0.05)  # let the flush start
        t0 = time.perf_counter()
        report = batcher.drain(deadline_s=0.2)
        assert time.perf_counter() - t0 < 5.0  # bounded, not row_ms-bound
        assert report["drained"] is False or report["unanswered"] == 0

    def test_server_graceful_shutdown_answers_then_closes(self):
        srv = InferenceServer(
            _FakeEngine(),
            host="127.0.0.1",
            port=0,
            max_batch=4,
            max_wait_ms=1.0,
            queue_depth=16,
            metrics=ServeMetrics(),
        ).start_background()
        port = srv.port
        status, resp = _post(srv, {"instances": _fake_images(2, 1).tolist()})
        assert status == 200 and len(resp["logits"]) == 1
        report = srv.graceful_shutdown(drain_timeout_s=5.0)
        assert report == {"drained": True, "unanswered": 0}
        with pytest.raises(urllib.error.URLError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=2
            )
        srv.close()  # idempotent after graceful_shutdown

    def test_single_server_rejects_model_routing(self):
        srv = InferenceServer(
            _FakeEngine(),
            host="127.0.0.1",
            port=0,
            metrics=ServeMetrics(),
        ).start_background()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(
                    srv,
                    {
                        "instances": _fake_images(3, 1).tolist(),
                        "model": "level_1",
                    },
                )
            assert e.value.code == 404
        finally:
            srv.close()


# ---------------------------------------------------------------- loadgen
class TestLoadgen:
    def test_open_loop_point_counts_and_quantiles(self):
        batcher = DynamicBatcher(
            _FakeEngine(),
            max_batch=16,
            max_wait_ms=1.0,
            queue_depth=256,
            metrics=ServeMetrics(),
        ).start()
        try:
            point = run_open_loop(
                lambda: batcher.submit(_fake_images(4, 1)),
                offered_rps=200.0,
                duration_s=0.5,
                seed=0,
                depth_probe=lambda: batcher.queue_depth,
            )
        finally:
            batcher.close()
        assert point["issued"] > 50
        assert point["completed"] == point["issued"]  # lightly loaded
        assert point["rejected"] == 0 and point["errors"] == 0
        assert point["unfinished"] == 0
        assert 0 < point["p50_ms"] <= point["p99_ms"] <= point["p999_ms"]
        assert point["goodput_rps"] > 0

    def test_sweep_detects_saturation_knee(self):
        """1 ms/row engine == ~1000 rows/s capacity: 100 rps is healthy,
        1500 rps overloads (bounded queue sheds + tail explodes) — the knee
        must land on 1500, not on the healthy point."""
        engine = _FakeEngine(row_ms=1.0)
        batcher = DynamicBatcher(
            engine,
            max_batch=32,
            max_wait_ms=2.0,
            queue_depth=64,
            metrics=ServeMetrics(),
        ).start()
        try:
            result = sweep_offered_load(
                lambda: (lambda: batcher.submit(_fake_images(5, 1))),
                rps_list=[100, 1500],
                duration_s=1.0,
                seed=0,
                settle_s=0.1,
                drain_timeout_s=5.0,
                depth_probe=lambda: batcher.queue_depth,
            )
        finally:
            batcher.close()
        assert [p["offered_rps"] for p in result["points"]] == [100.0, 1500.0]
        assert result["saturated"] is True
        assert result["knee_rps"] == 1500.0
        healthy, overloaded = result["points"]
        assert healthy["completed"] / healthy["issued"] >= 0.9
        assert (
            overloaded["rejected"] > 0
            or overloaded["p99_ms"] > 5 * healthy["p99_ms"]
        )

    def test_detect_knee_pure(self):
        healthy = {"offered_rps": 100.0, "issued": 100, "completed": 99,
                   "p99_ms": 4.0}
        shedding = {"offered_rps": 400.0, "issued": 400, "completed": 300,
                    "p99_ms": 6.0}
        slow = {"offered_rps": 400.0, "issued": 400, "completed": 396,
                "p99_ms": 50.0}
        assert detect_knee([healthy]) is None
        assert detect_knee([healthy, shedding]) == 400.0
        assert detect_knee([healthy, slow]) == 400.0  # p99 blowup criterion
        assert detect_knee([]) is None


# ----------------------------------------------------------------- config
class TestServeFleetConfig:
    def test_compose_fleet_group(self):
        cfg = compose(
            "serve",
            ["serve=fleet", "serve.fleet.expt_dirs=[experiments/a]"],
        )
        assert cfg.serve.fleet is not None
        assert cfg.serve.fleet.expt_dirs == ["experiments/a"]
        assert cfg.serve.fleet.max_resident_models == 4
        assert cfg.serve.fleet.default_route == "latest"
        assert cfg.serve.fleet.backend == "auto"
        assert cfg.serve.drain_timeout_s == 10.0

    def test_default_group_has_no_fleet(self):
        assert compose("serve", []).serve.fleet is None

    def test_unknown_fleet_key_rejected_at_compose(self):
        with pytest.raises(ConfigError):
            compose("serve", ["serve=fleet", "serve.fleet.nope=1"])

    def test_bad_choice_rejected_at_compose(self):
        with pytest.raises(ConfigError, match="default_route"):
            compose(
                "serve", ["serve=fleet", "serve.fleet.default_route=fastest"]
            )
        with pytest.raises(ConfigError, match="backend"):
            compose("serve", ["serve=fleet", "serve.fleet.backend=gpu"])

    def test_fleet_config_validation(self):
        FleetConfig().validate()  # defaults valid
        with pytest.raises(ConfigError):
            FleetConfig(max_resident_models=0).validate()
        with pytest.raises(ConfigError):
            FleetConfig(replicas=0).validate()
        with pytest.raises(ConfigError, match="pinned"):
            FleetConfig(default_route="pinned").validate()  # needs an id
        with pytest.raises(ConfigError, match="pinned"):
            FleetConfig(pinned_model="level_3").validate()  # needs the route
        FleetConfig(default_route="pinned", pinned_model="level_3").validate()

    def test_build_server_fleet_path(self, fleet_expt):
        from turboprune_tpu.serve import build_server

        cfg = compose(
            "serve",
            [
                "serve=fleet",
                f"serve.fleet.expt_dirs=[{fleet_expt}]",
                "serve.port=0",
                "serve.warmup=false",  # construction-only: no compiles
                "serve.batch_buckets=[2]",
            ],
        )
        srv = build_server(cfg)
        try:
            assert srv.fleet is not None
            assert srv.batcher is None
            assert srv.fleet.default_model == "level_2"
            assert srv.fleet.resident_ids == []  # lazy: nothing paged yet
        finally:
            srv.close()

    def test_build_server_fleet_requires_dirs(self):
        from turboprune_tpu.serve import build_server

        with pytest.raises(ConfigError, match="expt_dirs"):
            build_server(compose("serve", ["serve=fleet"]))
