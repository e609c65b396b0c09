"""Compact-as-you-train tests (sparse/train_compact.py + harness wiring).

Satellite coverage for ISSUE-9:

 - the pure compact->expand round trip is EXACT — kept coordinates come
   back bit-identical, removed coordinates come back zero — for params,
   optimizer moments (SGD trace, AdamW mu/nu) and BN batch_stats, across
   all four architectures (VGG chain incl. the 7x7-flatten consumer,
   ResNet residual-stop, DenseNet concat-offset, ViT MLP hidden);
 - the next level's GLOBAL magnitude threshold sees full-coordinate
   magnitudes: level L+1 masks are identical whether level L trained
   dense or compacted (weight_decay=0), and the zeros-expanded negative
   control DIVERGES — the anchor restore is load-bearing, because a dead
   channel's consumer in-rows hold unmasked real magnitudes;
 - the end-to-end harness smoke (the scripts/check.sh fast-tier stage):
   on synthetic .tpk data the second level re-instantiates physically
   smaller, checkpoint/metric surfaces stay full-coordinate, eval parity
   holds across the exit expansion, and the per-width caches evict stale
   widths with their sizes exported as gauges.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from turboprune_tpu.models import create_model
from turboprune_tpu.models.densenet import DenseNet
from turboprune_tpu.models.vgg import VGG
from turboprune_tpu.models.vit import VisionTransformer
from turboprune_tpu.ops import masking
from turboprune_tpu.pruning.criteria import prune_mag
from turboprune_tpu.sparse import (
    build_graph,
    build_plan,
    compact_stats,
    compact_tree,
    compact_train_state,
    expand_opt_state,
    expand_stats,
    expand_train_state,
    expand_tree,
    slice_opt_state,
)
from turboprune_tpu.train import (
    create_optimizer,
    create_train_state,
    make_train_step,
)
from turboprune_tpu.utils import tracing

# Reassociation noise ceiling for fp32 logits/losses (see tests/test_sparse).
ATOL = 1e-5

VGG_CFG = [16, "M", 32, "M", 32, 32, "M", 64, 64, "M", 64, 64, "M"]


def _vgg(ov=None, dropout=0.0):
    # dropout=0 wherever dense-vs-compacted trajectories are compared:
    # per-unit dropout draws cannot align across differently-shaped hidden
    # axes, so with dropout on the comparison measures sampling, not the
    # round trip (the README-documented caveat).
    return VGG(
        VGG_CFG, 10, batch_norm=True, fc_features=(96, 96),
        dropout_rate=dropout,
        width_overrides=tuple(sorted(ov.items())) if ov else None,
    )


def _kill_channels(masks, graph, frac, spaces=None):
    out = jax.tree.map(
        lambda m: None if m is None else np.array(m),
        masks,
        is_leaf=lambda x: x is None,
    )
    for name, sp in graph.spaces.items():
        if spaces is not None and name not in spaces:
            continue
        node = out
        for k in sp.producer.kernel[:-1]:
            node = node[k]
        m = node[sp.producer.kernel[-1]]
        m[..., : int(m.shape[-1] * frac)] = False
    return out


def _flat(tree):
    return jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: x is None
    )[0]


def _ones_like_tree(tree):
    return jax.tree.map(
        lambda a: np.ones_like(np.asarray(jax.device_get(a))), tree
    )


def _assert_kept_exact_removed_zero(dense, small, rt, ind, what):
    """rt must equal dense at indicator-1 coordinates (bit-identical) and
    be exactly zero elsewhere; the indicator itself is the zeros-expanded
    all-ones small tree, so it doubles as the removed-coordinate map."""
    removed_any = False
    for (p1, d), (p2, r), (p3, i) in zip(_flat(dense), _flat(rt), _flat(ind)):
        assert p1 == p2 == p3
        if d is None:
            assert r is None
            continue
        d = np.asarray(jax.device_get(d))
        r = np.asarray(jax.device_get(r))
        i = np.asarray(i)
        np.testing.assert_array_equal(
            r, np.where(i.astype(bool), d, np.zeros_like(d)),
            err_msg=f"{what}: {jax.tree_util.keystr(p1)}",
        )
        removed_any |= not i.all()
    assert removed_any, f"{what}: plan removed nothing — vacuous round trip"
    assert sum(np.asarray(x).size for _, x in _flat(small) if x is not None) < sum(
        np.asarray(x).size for _, x in _flat(dense) if x is not None
    )


def _arch_setups():
    vgg = _vgg()
    resnet = create_model("resnet18", 10, "CIFAR10", compute_dtype=jnp.float32)
    densenet = DenseNet([2, 3], 10, growth_rate=8, init_features=16, cifar_stem=True)
    vit = VisionTransformer(
        num_classes=10, patch_size=8, embed_dim=32, depth=2, num_heads=2
    )
    return {
        "vgg": vgg,
        "resnet18": resnet,
        "densenet": densenet,
        "vit": vit,
    }


@pytest.fixture(scope="module", params=["vgg", "resnet18", "densenet", "vit"])
def arch(request):
    model = _arch_setups()[request.param]
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False)
    params, stats = v["params"], v.get("batch_stats", {})
    graph = build_graph(model, params)
    masks = _kill_channels(masking.make_masks(params), graph, 0.5)
    plan = build_plan(params, masks, graph, stats)
    return request.param, params, stats, plan


class TestRoundTripExact:
    def test_params_roundtrip(self, arch):
        name, params, _, plan = arch
        small = compact_tree(params, plan)
        rt = expand_tree(small, plan)
        ind = expand_tree(_ones_like_tree(small), plan)
        _assert_kept_exact_removed_zero(params, small, rt, ind, f"{name} params")

    @pytest.mark.parametrize("opt_name", ["SGD", "AdamW"])
    def test_opt_moments_roundtrip(self, arch, opt_name):
        """Moments made NONZERO first (one real update) so the kept-coord
        bit-identity is not trivially comparing zeros to zeros."""
        name, params, _, plan = arch
        tx = create_optimizer(opt_name, 0.1, momentum=0.9, weight_decay=0.0)
        opt = tx.init(params)
        grads = jax.tree.map(jnp.ones_like, params)
        _, opt = tx.update(grads, opt, params)
        small = slice_opt_state(opt, plan)
        rt = expand_opt_state(small, plan)
        ind = expand_opt_state(slice_opt_state(_ones_like_tree(opt), plan), plan)
        _assert_kept_exact_removed_zero(opt, small, rt, ind, f"{name} {opt_name}")

    def test_batch_stats_roundtrip(self, arch):
        name, _, stats, plan = arch
        if not stats:
            pytest.skip("architecture has no batch_stats")
        small = compact_stats(stats, plan)
        rt = expand_stats(small, plan)
        ind = expand_stats(_ones_like_tree(small), plan)
        _assert_kept_exact_removed_zero(stats, small, rt, ind, f"{name} stats")

    def test_expand_with_anchor_restores_removed_coords(self, arch):
        name, params, _, plan = arch
        anchor = jax.tree.map(lambda p: np.asarray(p) * 2.0 + 1.0, params)
        small = compact_tree(params, plan)
        rt = expand_tree(small, plan, anchor=anchor)
        ind = expand_tree(_ones_like_tree(small), plan)
        for (p1, d), (p2, a), (p3, r), (p4, i) in zip(
            _flat(params), _flat(anchor), _flat(rt), _flat(ind)
        ):
            assert p1 == p2 == p3 == p4
            d, a, r = (np.asarray(jax.device_get(x)) for x in (d, a, r))
            np.testing.assert_array_equal(
                r, np.where(np.asarray(i).astype(bool), d, a),
                err_msg=f"{name} anchor: {jax.tree_util.keystr(p1)}",
            )


class TestGlobalThresholdFullCoordinates:
    """Satellite 2: with weight_decay=0 and the per-level fresh optimizer, a
    removed coordinate never moves in the dense run (zero data-gradient,
    zero momentum) — so anchor-expansion makes the compacted level's
    full-coordinate endpoint give the IDENTICAL next-level global mask."""

    def _setup(self):
        model = _vgg()
        tx = create_optimizer("SGD", 0.05, momentum=0.9, weight_decay=0.0)
        state0 = create_train_state(
            model, tx, jax.random.PRNGKey(1), (1, 32, 32, 3)
        )
        graph = build_graph(model, state0.params)
        masks = _kill_channels(state0.masks, graph, 0.5)
        state0 = state0.replace(masks=masks, opt_state=tx.init(state0.params))
        rng = np.random.default_rng(7)
        batch = (
            jnp.asarray(rng.standard_normal((8, 32, 32, 3)).astype(np.float32)),
            jnp.asarray(rng.integers(0, 10, size=(8,)).astype(np.int32)),
        )
        return model, tx, state0, graph, batch

    def test_zero_step_roundtrip_mask_exact(self):
        model, _, state0, graph, _ = self._setup()
        plan = build_plan(state0.params, state0.masks, graph, state0.batch_stats)
        small = compact_train_state(state0, plan)
        rt = expand_train_state(small, plan, anchor=state0)
        for (p1, a), (p2, b) in zip(
            _flat(prune_mag(state0.params, state0.masks, 0.5)),
            _flat(prune_mag(rt.params, rt.masks, 0.5)),
        ):
            assert p1 == p2
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_next_level_mask_identical_after_training(self):
        model, tx, state0, graph, batch = self._setup()
        step = jax.jit(make_train_step(model, tx))
        dense = state0
        for _ in range(3):
            dense, _ = step(dense, batch)

        plan = build_plan(state0.params, state0.masks, graph, state0.batch_stats)
        small_model = _vgg(plan.width_overrides)
        small_step = jax.jit(make_train_step(small_model, tx))
        small = compact_train_state(state0, plan)
        for _ in range(3):
            small, _ = small_step(small, batch)
        rt = expand_train_state(small, plan, anchor=state0)

        # Premise check: the dense run really never moved removed coords.
        ind = expand_tree(
            _ones_like_tree(compact_tree(state0.params, plan)), plan
        )
        for (_, d), (_, a), (_, i) in zip(
            _flat(dense.params), _flat(state0.params), _flat(ind)
        ):
            d, a = (np.asarray(jax.device_get(x)) for x in (d, a))
            removed = ~np.asarray(i).astype(bool)
            np.testing.assert_array_equal(d[removed], a[removed])

        m_dense = prune_mag(dense.params, dense.masks, 0.5)
        m_compact = prune_mag(rt.params, rt.masks, 0.5)
        for (p1, a), (p2, b) in zip(_flat(m_dense), _flat(m_compact)):
            assert p1 == p2
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"level L+1 mask diverged: {jax.tree_util.keystr(p1)}",
            )

        # Negative control: a ZEROS-expanded endpoint re-ranks the global
        # top-k (dead channels' consumer in-rows lose their magnitudes) —
        # proving the anchor restore is what carries satellite 2.
        rt_zero = expand_train_state(small, plan)
        m_zero = prune_mag(rt_zero.params, rt.masks, 0.5)
        assert any(
            not np.array_equal(np.asarray(a), np.asarray(b))
            for (_, a), (_, b) in zip(_flat(m_dense), _flat(m_zero))
        ), "zeros-expansion produced the same mask — test lost its teeth"


@pytest.mark.usefixtures("tmp_path")
class TestHarnessCompactTrainSmoke:
    """Satellite 6 — the scripts/check.sh fast-tier smoke. One harness, three
    levels on synthetic .tpk data: level 0 must stay dense (no savings),
    level 1 must re-instantiate physically smaller and round-trip exactly,
    level 2 (more channels killed) must evict the level-1 width caches."""

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
                "planner.compact_min_savings=0.1",
                "optimizer_params.lr=0.01",
                "optimizer_params.weight_decay=0.0",
                "model_params.model_name=resnet18",
            ],
        )
        return PruningHarness(cfg, ("smoke", str(tmp_path / "expt")))

    def _kill(self, harness, frac):
        graph = build_graph(harness.model, harness.state.params)
        harness.state = harness.state.replace(
            masks=_kill_channels(harness.state.masks, graph, frac)
        )
        harness.masks_written()

    def test_levels_reinstantiate_and_roundtrip(self, tmp_path):
        h = self._harness(tmp_path)
        full_shapes = jax.tree.map(lambda a: a.shape, h.state.params)

        h.train_one_level(1, 0)
        assert h._plan_ctx is None
        assert h.last_compaction_report is None, "level 0 must train dense"

        self._kill(h, 0.5)
        masks_before = jax.tree.map(
            lambda m: None if m is None else np.array(m),
            h.state.masks,
            is_leaf=lambda x: x is None,
        )
        sparsity_before = masking.overall_sparsity(h.state.masks)
        reads = tracing.gauges()["mask_reads"]
        s1 = h.train_one_level(1, 1)
        # ``_kill`` wrote masks: they are read once, in set-up, in full
        # coordinates before the plan compacts them; the compacted epochs
        # and the summary carry that count.
        assert tracing.gauges()["mask_reads"] == reads + 1

        # Re-instantiated smaller, and exited back to full coordinates.
        assert h._plan_ctx is None
        rep = h.last_compaction_report
        assert rep is not None
        assert rep["params_after"] < rep["params_before"]
        assert jax.tree.map(lambda a: a.shape, h.state.params) == full_shapes
        # Masks bit-identical through the level (metric rows stayed
        # full-coordinate too: the logged sparsity is the dense-space one).
        for (p1, a), (p2, b) in zip(_flat(masks_before), _flat(h.state.masks)):
            assert p1 == p2
            if a is not None:
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(jax.device_get(b))
                )
        assert s1["sparsity"] == s1["final_sparsity"] == sparsity_before

        # Eval parity across the exit expansion: the level's logged test
        # metrics came from the SMALL model; re-evaluating the expanded
        # full-coordinate state must agree to reassociation noise.
        post = h.evaluate()
        assert post["test_loss"] == pytest.approx(s1["test_loss"], abs=1e-4)
        assert post["test_acc"] == pytest.approx(s1["test_acc"])

        # Gauges export the size the level ACTUALLY compiled.
        snap = tracing.gauges()
        assert snap["plan_params_compacted"] == rep["params_after"]
        assert snap["plan_step_cache_size"] == 1

        # Level 2 at strictly smaller widths: stale caches must be evicted,
        # not accumulated (widths never grow back).
        keys_l1 = set(h._plan_step_cache)
        self._kill(h, 0.75)
        h.train_one_level(1, 2)
        assert set(h._plan_step_cache).isdisjoint(keys_l1)
        snap = tracing.gauges()
        assert snap["plan_step_cache_size"] == 1
        assert snap["plan_eval_cache_size"] == 0  # compact_eval off
