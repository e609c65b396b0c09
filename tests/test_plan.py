"""ExecutionPlan planner tests (sparse/plan.py + harness/serve wiring).

Acceptance coverage for the one-planner PR:

 - decision-table units: each mask population lands on the right backend —
   all-ones stays masked-dense, dead channels commit compaction, scattered
   2:4 routes gathered N:M, both together produce a MIXED plan — with the
   commit/decline reason, the savings numbers, and the per-layer routing
   all machine-readable in ``plan.decisions`` / ``plan.report``;
 - threshold + mode semantics: ``compact_min_savings`` declines with the
   threshold in the reason, ``compact="force"`` commits even the identity
   slice (the explicit-backend serving contract), bad mode strings fail
   fast with ValueError;
 - autotune: the analytic cost model records ``est_gain`` per routed layer
   and DEMOTES layers where gather overhead beats the reduced-GEMM win
   (the demotion is visible as a dense decision, never silent), and
   ``measure`` mode records real per-layer timings;
 - mixed-plan numerical parity on VGG and ViT: logits and the
   optimizer-visible grads (through the apply_masks chain) match
   masked-dense — compaction slices coordinates whose activations and
   grads are exactly zero, and nm_matmul's VJP keeps dw a dense GEMM, so
   composing them never changes the values the optimizer sees;
 - the end-to-end harness lifecycle (3 levels on synthetic .tpk data):
   dense level 0 plans "masked", a level with dead channels AND a
   projected pattern enters ONE mixed plan (single step-bundle cache
   entry keyed on (steps, widths, nm)), exits back to full coordinates,
   and the next level's smaller widths evict the stale bundle.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from turboprune_tpu.models.vgg import VGG
from turboprune_tpu.models.vit import VisionTransformer
from turboprune_tpu.ops.masking import apply_masks, make_masks
from turboprune_tpu.sparse import (
    build_graph,
    plan_execution,
    project_masks,
)
from turboprune_tpu.sparse.compact import (
    compact_stats,
    compact_tree,
    expand_tree,
)
from turboprune_tpu.utils import tracing

# Reassociation noise ceilings (see tests/test_sparse, tests/test_nm): the
# sliced/gathered programs sum the same terms in a different order.
LOGIT_ATOL = 1e-4
GRAD_RTOL = 1e-4

VGG_CFG = [16, "M", 32, "M", 32, 32, "M", 64, 64, "M", 64, 64, "M"]


def _vgg(ov=None, nm=None):
    return VGG(
        VGG_CFG, 10, batch_norm=True, fc_features=(96, 96), dropout_rate=0.0,
        width_overrides=tuple(sorted(dict(ov).items())) if ov else None,
        nm_overrides=nm,
    )


def _tiny_vgg():
    # batch_norm=False: the smallest model with both planner surfaces
    # (conv channel spaces + hookable fc layers); fc0 is (392, 32).
    return VGG(
        [8, "M", 8, "M", 8, "M", 8, "M", 8, "M"], 4,
        batch_norm=False, fc_features=(32, 32), dropout_rate=0.0,
    )


def _vit(ov=None, nm=None):
    return VisionTransformer(
        num_classes=10, patch_size=8, embed_dim=32, depth=1, num_heads=2,
        width_overrides=tuple(sorted(dict(ov).items())) if ov else None,
        nm_overrides=nm,
    )


def _init(model, hw=32):
    v = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, hw, hw, 3)), train=False
    )
    return v["params"], v.get("batch_stats", {})


def _kill_channels(masks, graph, frac):
    out = jax.tree.map(
        lambda m: None if m is None else np.array(m),
        masks,
        is_leaf=lambda x: x is None,
    )
    for _, sp in graph.spaces.items():
        node = out
        for k in sp.producer.kernel[:-1]:
            node = node[k]
        m = node[sp.producer.kernel[-1]]
        m[..., : int(m.shape[-1] * frac)] = False
    return out


def _kill_fc0_rows(masks, n_rows):
    out = jax.tree.map(
        lambda m: None if m is None else np.array(m),
        masks,
        is_leaf=lambda x: x is None,
    )
    out["fc0"]["kernel"][:n_rows, :] = False
    return out


def _flat(tree):
    return jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: x is None
    )[0]


# ---------------------------------------------------------- decision table


class TestPlannerDecisions:
    def test_bad_modes_fail_fast(self):
        model = _tiny_vgg()
        params, _ = _init(model)
        masks = make_masks(params)
        with pytest.raises(ValueError, match="compact mode"):
            plan_execution(model, params, masks, compact="maybe")
        with pytest.raises(ValueError, match="nm mode"):
            plan_execution(model, params, masks, nm="force")
        with pytest.raises(ValueError, match="autotune"):
            plan_execution(model, params, masks, autotune="fast")

    def test_dense_masks_stay_masked(self):
        model = _tiny_vgg()
        params, _ = _init(model)
        plan = plan_execution(model, params, make_masks(params))
        assert plan.kind == "masked"
        assert plan.plan_signature() == ("masked",)
        assert plan.compaction is None and plan.nm is None
        assert plan.width_key() == () and plan.nm_key() == ()
        comp = plan.decisions["compaction"]
        assert not comp["committed"]
        assert comp["reason"] == "no dead channels to slice"
        counts = plan.report["backend_counts"]
        assert counts["nm_layers"] == 0 and counts["compact_spaces"] == 0
        assert plan.report["coverage_frac"] == 0.0

    def test_dead_channels_commit_compaction(self):
        model = _tiny_vgg()
        params, _ = _init(model)
        graph = build_graph(model, params)
        masks = _kill_channels(make_masks(params), graph, 0.5)
        plan = plan_execution(model, params, masks)
        assert plan.kind == "compact"
        assert plan.plan_signature() == ("compact", plan.width_key())
        assert plan.width_key() != ()
        comp = plan.decisions["compaction"]
        assert comp["committed"] and comp["backend"] == "compact"
        assert comp["savings"] > 0.0
        assert comp["params_after"] < comp["params_before"]
        # after slicing, the survivor masks are all ones: nothing routes
        assert plan.nm is None
        assert plan.report["backend_counts"]["compact_spaces"] > 0

    def test_scattered_pattern_routes_nm(self):
        model = _tiny_vgg()
        params, _ = _init(model)
        # input-axis-only: the pattern thins contraction ROWS but keeps
        # every output column live, so no channel space dies — the planner
        # must decline compaction and route the fc pattern.
        masks, _ = project_masks(
            params, make_masks(params), 2, 4, transposable=False
        )
        plan = plan_execution(model, params, masks)
        assert plan.kind == "nm"
        assert plan.plan_signature() == ("nm", plan.nm_key())
        assert not plan.decisions["compaction"]["committed"]
        routed = {
            name
            for name, d in plan.decisions["layers"].items()
            if d["backend"] == "nm"
        }
        assert "fc0/kernel" in routed and "fc1/kernel" in routed
        layers = plan.report["nm"]["layers"]
        assert layers["fc0/kernel"]["kept_in_frac"] == pytest.approx(0.5)
        assert plan.report["coverage_frac"] > 0.0

    def test_both_populations_produce_mixed(self):
        model = _tiny_vgg()
        params, _ = _init(model)
        graph = build_graph(model, params)
        masks = _kill_channels(make_masks(params), graph, 0.5)
        masks, _ = project_masks(params, masks, 2, 4)
        plan = plan_execution(model, params, masks)
        assert plan.kind == "mixed"
        sig = plan.plan_signature()
        assert sig == ("mixed", plan.width_key(), plan.nm_key())
        assert plan.width_key() != () and plan.nm_key() != ()
        assert plan.decisions["compaction"]["committed"]
        assert any(
            d["backend"] == "nm" for d in plan.decisions["layers"].values()
        )
        counts = plan.report["backend_counts"]
        assert counts["nm_layers"] > 0 and counts["compact_spaces"] > 0

    def test_savings_threshold_declines_with_reason(self):
        model = _tiny_vgg()
        params, _ = _init(model)
        graph = build_graph(model, params)
        masks = _kill_channels(make_masks(params), graph, 0.5)
        plan = plan_execution(
            model, params, masks, compact_min_savings=0.99
        )
        comp = plan.decisions["compaction"]
        assert not comp["committed"]
        assert "below threshold 0.99" in comp["reason"]
        # consumer in-rows of dead channels still carry live masks, so
        # nothing routes either: the whole level stays masked-dense
        assert plan.kind == "masked"

    def test_force_commits_identity_slice(self):
        model = _tiny_vgg()
        params, _ = _init(model)
        plan = plan_execution(
            model, params, make_masks(params), compact="force"
        )
        assert plan.kind == "compact"
        comp = plan.decisions["compaction"]
        assert comp["committed"]
        assert comp["reason"] == "backend forced compact"
        assert comp["savings"] == 0.0
        assert comp["params_after"] == comp["params_before"]

    def test_off_modes_disable_backends(self):
        model = _tiny_vgg()
        params, _ = _init(model)
        graph = build_graph(model, params)
        masks = _kill_channels(make_masks(params), graph, 0.5)
        masks, _ = project_masks(params, masks, 2, 4)
        plan = plan_execution(model, params, masks, compact="off", nm="off")
        assert plan.kind == "masked"
        assert plan.decisions["compaction"]["reason"] == "compaction disabled"
        assert plan.decisions["layers"] == {}


class TestAutotune:
    """The cost model: est_cost = kept_in * kept_out + gather overhead
    (0.15). A layer keeping 352/392 = 0.898 of its rows costs 1.048 —
    gathering LOSES and must be demoted; keeping 0.5 costs 0.65 — a clear
    win that must stay routed with its gain recorded."""

    def _marginal_plan(self, autotune):
        model = _tiny_vgg()
        params, _ = _init(model)
        masks = _kill_fc0_rows(make_masks(params), 40)
        return plan_execution(
            model, params, masks,
            nm_min_axis_savings=0.05, autotune=autotune,
        )

    def test_cost_model_demotes_marginal_layer(self):
        baseline = self._marginal_plan("off")
        assert baseline.kind == "nm", "fixture must route without autotune"
        plan = self._marginal_plan("cost")
        assert plan.kind == "masked"
        d = plan.decisions["layers"]["fc0/kernel"]
        assert d["backend"] == "dense"
        assert d["reason"].startswith("autotune:")
        assert d["mode"] == "cost" and d["est_gain"] < 1.0
        # demotion keeps the coverage accounting honest
        assert plan.report["nm"]["layers"]["fc0/kernel"]["routed"] is False
        assert plan.report["coverage_frac"] < baseline.report["coverage_frac"]

    def test_cost_model_keeps_clear_winner(self):
        model = _tiny_vgg()
        params, _ = _init(model)
        masks, _ = project_masks(params, make_masks(params), 2, 4)
        plan = plan_execution(model, params, masks, autotune="cost")
        d = plan.decisions["layers"]["fc0/kernel"]
        assert d["backend"] == "nm"
        assert d["est_gain"] == pytest.approx(1.0 / 0.65, rel=1e-3)
        assert plan.report["autotune"] == "cost"

    def test_measure_mode_records_timings(self):
        plan = self._marginal_plan("measure")
        d = plan.decisions["layers"]["fc0/kernel"]
        assert d["mode"] == "measure"
        assert d["dense_ms"] > 0.0 and d["nm_ms"] > 0.0
        assert d["est_gain"] == pytest.approx(
            d["dense_ms"] / d["nm_ms"], rel=1e-3
        )


# ------------------------------------------------------------------ parity


def _assert_tree_close(got, want, what):
    for (p1, a), (p2, b) in zip(_flat(want), _flat(got)):
        assert p1 == p2
        a = np.asarray(jax.device_get(a))
        b = np.asarray(jax.device_get(b))
        scale = max(1.0, float(np.abs(a).max()))
        assert float(np.abs(a - b).max()) / scale < GRAD_RTOL, (
            f"{what}: {jax.tree_util.keystr(p1)}"
        )


class TestMixedPlanParity:
    """The gradient contract: a MIXED plan (compaction + N:M on the
    survivors) produces logits and optimizer-visible grads matching
    masked-dense. Compaction slices only coordinates whose activations are
    exactly zero (dead producer channels; conv/BN biases are zero at
    init), and nm_matmul's custom VJP keeps dw a full dense GEMM — so the
    composition changes which coordinates are materialized, never the
    values."""

    def _parity(self, model, rebuild, params, masks, bstats, x):
        plan = plan_execution(model, params, masks, bstats)
        assert plan.kind == "mixed", "fixture must exercise BOTH backends"
        exec_model = rebuild(plan.width_overrides, plan.nm.as_override_tuple())
        cplan = plan.compaction
        m_small = compact_tree(masks, cplan)
        p_small = compact_tree(params, cplan)
        s_small = compact_stats(bstats, cplan)

        def dense_loss(p):
            vs = {"params": apply_masks(p, masks)}
            if bstats:
                vs["batch_stats"] = bstats
            logits = model.apply(vs, x, train=False)
            return (logits**2).sum(), logits

        def mixed_loss(p):
            vs = {"params": apply_masks(p, m_small)}
            if s_small:
                vs["batch_stats"] = s_small
            logits = exec_model.apply(vs, x, train=False)
            return (logits**2).sum(), logits

        (l_d, y_d), g_d = jax.value_and_grad(dense_loss, has_aux=True)(params)
        (l_m, y_m), g_m = jax.value_and_grad(mixed_loss, has_aux=True)(
            p_small
        )
        assert float(jnp.abs(y_d - y_m).max()) < LOGIT_ATOL
        assert abs(float(l_d - l_m)) < 1e-3
        # The grad contract is over MATERIALIZED coordinates: every
        # coordinate the mixed plan executes gets the masked-dense grad.
        # Removed coordinates are frozen by design (dense training can
        # still move e.g. a dead GELU unit's bias, since gelu'(0) != 0) —
        # that is what the harness's anchor expansion carries across the
        # level, and it is invisible to the kernel-magnitude criterion.
        indicator = expand_tree(
            jax.tree.map(np.ones_like, g_m), cplan
        )
        kept_dense = jax.tree.map(lambda g, i: np.asarray(g) * i, g_d, indicator)
        _assert_tree_close(expand_tree(g_m, cplan), kept_dense, "grad diverged")

    def test_vgg_mixed_matches_masked_dense(self):
        model = _vgg()
        params, bstats = _init(model)
        graph = build_graph(model, params)
        masks = _kill_channels(make_masks(params), graph, 0.5)
        masks, _ = project_masks(params, masks, 2, 4)
        x = jnp.asarray(
            np.random.RandomState(0).randn(2, 32, 32, 3), jnp.float32
        )
        self._parity(
            model,
            lambda ov, nm: _vgg(ov, nm),
            params, masks, bstats, x,
        )

    def test_vit_mixed_matches_masked_dense(self):
        model = _vit()
        params, bstats = _init(model)
        graph = build_graph(model, params)
        masks = _kill_channels(make_masks(params), graph, 0.5)
        masks, _ = project_masks(params, masks, 2, 4)
        x = jnp.asarray(
            np.random.RandomState(1).randn(2, 32, 32, 3), jnp.float32
        )
        self._parity(
            model,
            lambda ov, nm: _vit(ov, nm),
            params, masks, bstats, x,
        )


# ---------------------------------------------------------- harness smoke


@pytest.mark.usefixtures("tmp_path")
class TestHarnessMixedPlanSmoke:
    """The scripts/check.sh plan stage. One harness with BOTH backends
    enabled: level 0 plans masked (no executables cached), level 1 (dead
    channels + projected pattern) enters one MIXED plan with a single
    step-bundle cache entry keyed (steps, widths, nm), exits back to full
    coordinates, and level 2's smaller widths evict the stale bundle."""

    def _harness(self, tmp_path):
        from turboprune_tpu.config.compose import compose
        from turboprune_tpu.data.native import write_tpk_raw
        from turboprune_tpu.harness.pruning_harness import PruningHarness

        rng = np.random.default_rng(0)
        write_tpk_raw(
            tmp_path / "train.tpk",
            rng.integers(0, 256, size=(16, 8, 8, 3), dtype=np.uint8),
            rng.integers(0, 4, size=(16,)).astype(np.int32),
        )
        write_tpk_raw(
            tmp_path / "val.tpk",
            rng.integers(0, 256, size=(8, 8, 8, 3), dtype=np.uint8),
            rng.integers(0, 4, size=(8,)).astype(np.int32),
        )
        cfg = compose(
            "cifar10_imp",
            overrides=[
                f"experiment_params.base_dir={tmp_path}",
                "dataset_params.dataloader_type=tpk",
                f"dataset_params.tpk_train_path={tmp_path / 'train.tpk'}",
                f"dataset_params.tpk_val_path={tmp_path / 'val.tpk'}",
                "dataset_params.total_batch_size=8",
                "dataset_params.image_size=8",
                "dataset_params.num_classes=4",
                "experiment_params.epochs_per_level=1",
                "experiment_params.max_steps_per_epoch=2",
                "experiment_params.training_precision=float32",
                "experiment_params.compact_train=true",
                "experiment_params.nm_sparsity='2:4'",
                "planner.compact_min_savings=0.1",
                "optimizer_params.lr=0.01",
                "optimizer_params.weight_decay=0.0",
                "model_params.model_name=resnet18",
            ],
        )
        return PruningHarness(cfg, ("smoke", str(tmp_path / "expt")))

    def _kill_and_project(self, h, frac):
        graph = build_graph(h.model, h.state.params)
        masks = _kill_channels(h.state.masks, graph, frac)
        masks, _ = project_masks(h.state.params, masks, 2, 4)
        h.state = h.state.replace(masks=masks)
        h.masks_written()

    def test_three_level_lifecycle_and_eviction(self, tmp_path):
        h = self._harness(tmp_path)
        full_shapes = jax.tree.map(lambda a: a.shape, h.state.params)

        h.train_one_level(1, 0)
        assert h._plan_ctx is None
        assert h.last_plan_report["kind"] == "masked"
        assert len(h._plan_step_cache) == 0

        self._kill_and_project(h, 0.5)
        h.train_one_level(1, 1)
        assert h._plan_ctx is None, "exit must restore dense fns in finally"
        rep = h.last_plan_report
        assert rep["kind"] == "mixed"
        assert rep["backend_counts"]["compact_spaces"] > 0
        assert rep["backend_counts"]["nm_layers"] > 0
        assert rep["coverage_frac"] > 0.0
        # one bundle, keyed on all three plan components
        assert len(h._plan_step_cache) == 1
        (key,) = h._plan_step_cache
        assert len(key) == 3 and key[1] != () and key[2] != ()
        keys_l1 = set(h._plan_step_cache)
        # exited back to full coordinates
        assert jax.tree.map(lambda a: a.shape, h.state.params) == full_shapes
        snap = tracing.gauges()
        assert snap["plan_layers_nm"] == rep["backend_counts"]["nm_layers"]
        assert snap["plan_spaces_compacted"] > 0
        assert snap["plan_coverage_frac"] == pytest.approx(
            rep["coverage_frac"]
        )
        assert snap["plan_step_cache_size"] == 1

        # strictly smaller widths at level 2: the stale bundle must be
        # evicted, not accumulated
        self._kill_and_project(h, 0.75)
        h.train_one_level(1, 2)
        assert h.last_plan_report["kind"] == "mixed"
        assert len(h._plan_step_cache) == 1
        assert set(h._plan_step_cache).isdisjoint(keys_l1)
