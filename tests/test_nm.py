"""N:M transposable sparsity tests (sparse/nm.py + sparse/nm_execute.py).

Acceptance coverage for ISSUE-10:

 - projection solver properties (satellite 3): every M-block keeps exactly
   N entries, the transposable pattern satisfies N:M along BOTH matmul
   axes, and alternating maximization preserves >= the greedy-both-axes
   baseline magnitude;
 - projection is monotone (no resurrection), degrades to input-axis-only
   when the output axis is too narrow (the classifier-head guard), and
   fails fast with NMError on non-divisible contraction widths;
 - the gathered execution path is NUMERICALLY EQUIVALENT to masked-dense:
   forward parity for every NM module against its flax counterpart, and
   the grads that reach the optimizer (through the apply_masks chain)
   match masked-dense — including a full-model ViT check through the
   plan builder; jit compiles ONE executable per (ki, ko) shape;
 - the end-to-end harness smoke (the scripts/check.sh nm stage): a level
   whose masks carry a projected pattern runs gathered and exits back to
   the dense step functions, the per-level plan cache holds one entry
   (no steady-state recompiles), stale plans evict, and the coverage
   report makes unrouted eligible layers visible (satellite 6);
 - compact_train composability: channel-compact first, N:M the survivors.
"""

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

from turboprune_tpu.models.vit import VisionTransformer
from turboprune_tpu.ops.masking import apply_masks, make_masks
from turboprune_tpu.sparse import (
    NMError,
    build_nm_plan,
    check_divisibility,
    nm_pattern_inaxis,
    nm_pattern_transposable,
    project_masks,
)
from turboprune_tpu.sparse.nm import split_index
from turboprune_tpu.utils import tracing
from turboprune_tpu.sparse.nm_execute import (
    NMConv1x1,
    NMDense,
    NMDenseGeneral,
    NMSelfAttention,
    nm_matmul,
)

ATOL = 1e-5


def _scores(i, o, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.abs(jnp.asarray(rng.randn(i, o), jnp.float32))


def _live(mask2, full_len_out):
    """(kept_in, kept_out) index tuples the way build_nm_plan derives them."""
    m = np.asarray(mask2)
    ki = tuple(int(v) for v in np.nonzero(m.any(axis=1))[0])
    lo = np.nonzero(m.any(axis=0))[0]
    ko = tuple(int(v) for v in lo) if len(lo) < full_len_out else None
    return ki, ko


# ------------------------------------------------------- solver properties


class TestSolvers:
    @pytest.mark.parametrize("n,m", [(2, 4), (4, 8), (1, 4)])
    def test_inaxis_exactly_n_per_block(self, n, m):
        keep = nm_pattern_inaxis(_scores(8 * m, 24), n, m)
        counts = np.asarray(keep).reshape(-1, m).sum(axis=1)
        assert counts.tolist() == [n] * 8

    @pytest.mark.parametrize("n,m", [(2, 4), (4, 8)])
    def test_transposable_both_axes_exactly_n_per_block(self, n, m):
        i, o = 8 * m, 6 * m
        ki, ko = nm_pattern_transposable(_scores(i, o), n, m)
        assert np.asarray(ki).reshape(-1, m).sum(1).tolist() == [n] * (i // m)
        assert np.asarray(ko).reshape(-1, m).sum(1).tolist() == [n] * (o // m)

    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
    def test_transposable_preserves_at_least_greedy_baseline(self, seed):
        """Alternating maximization is monotone from the greedy-both-axes
        init, so it can never preserve LESS magnitude than that baseline
        (the ISSUE-10 satellite-3 property)."""
        n, m = 2, 4
        scores = _scores(32, 24, seed)
        gki = nm_pattern_inaxis(scores, n, m)
        gko = nm_pattern_inaxis(scores.T, n, m)
        base = float(jnp.where(gki[:, None] & gko[None, :], scores, 0.0).sum())
        tki, tko = nm_pattern_transposable(scores, n, m)
        trans = float(jnp.where(tki[:, None] & tko[None, :], scores, 0.0).sum())
        assert trans >= base - 1e-5 * base

    def test_split_index_geometry(self):
        assert split_index("fc/kernel", (512, 10)) == 1
        assert split_index("block0/attn/query/kernel", (32, 2, 16)) == 1
        assert split_index("block0/attn/out/kernel", (2, 16, 32)) == 2
        assert split_index("layer1_0/Conv_0/kernel", (1, 1, 64, 16)) == 3
        assert split_index("conv1/kernel", (3, 3, 3, 64)) is None
        assert split_index("bn/scale", (64,)) is None


# --------------------------------------------------------------- projection


class TestProjection:
    def _tree(self, seed=0):
        rng = np.random.RandomState(seed)
        params = {
            "fc": {
                "kernel": jnp.asarray(rng.randn(16, 8), jnp.float32),
                "bias": jnp.zeros((8,)),
            },
            "head": {
                "kernel": jnp.asarray(rng.randn(16, 10), jnp.float32),
                "bias": jnp.zeros((10,)),
            },
        }
        return params, make_masks(params)

    def test_monotone_no_resurrection(self):
        params, masks = self._tree()
        masks["fc"]["kernel"] = masks["fc"]["kernel"].at[0, :].set(False)
        new, _ = project_masks(params, masks, 2, 4)
        assert not bool(new["fc"]["kernel"][0].any())
        # globally: new_mask implies old_mask
        resurrected = new["fc"]["kernel"] & ~masks["fc"]["kernel"]
        assert int(resurrected.sum()) == 0

    def test_projected_blocks_satisfy_nm(self):
        params, masks = self._tree()
        new, _ = project_masks(params, masks, 2, 4)
        for name in ("fc", "head"):
            m2 = np.asarray(new[name]["kernel"])
            live_rows = m2.any(axis=1).reshape(-1, 4).sum(axis=1)
            assert live_rows.max() <= 2, name

    def test_output_axis_guard(self):
        """Transposable runs on the output axis only when it holds >= 2
        M-blocks: a 10-wide head is not divisible ('in'), a 4-wide head is
        one block whose 'pattern' would delete whole class logits ('in'),
        an 8-wide layer qualifies ('both')."""
        params, masks = self._tree()
        _, report = project_masks(params, masks, 2, 4)
        assert report["layers"]["fc/kernel"]["axes"] == "both"  # o=8=2M
        assert report["layers"]["head/kernel"]["axes"] == "in"  # o=10

        rng = np.random.RandomState(1)
        p4 = {"fc": {"kernel": jnp.asarray(rng.randn(16, 4), jnp.float32)}}
        new, rep = project_masks(p4, make_masks(p4), 2, 4)
        assert rep["layers"]["fc/kernel"]["axes"] == "in"
        # every output column survives — no class logit deleted
        assert np.asarray(new["fc"]["kernel"]).any(axis=0).all()

    def test_transposable_false_is_inaxis_only(self):
        params, masks = self._tree()
        new, report = project_masks(params, masks, 2, 4, transposable=False)
        assert report["layers"]["fc/kernel"]["axes"] == "in"
        assert np.asarray(new["fc"]["kernel"]).any(axis=0).all()

    def test_divisibility_fails_fast(self):
        with pytest.raises(NMError, match="not divisible by M=4"):
            check_divisibility(
                {"x": {"kernel": jnp.ones((6, 4), jnp.bool_)}}, 4
            )
        # non-divisible OUTPUT width is fine (input-axis-only degrade)
        check_divisibility({"x": {"kernel": jnp.ones((8, 10), jnp.bool_)}}, 4)

    def test_report_preserved_magnitude(self):
        params, masks = self._tree()
        new, report = project_masks(params, masks, 2, 4)
        frac = report["preserved_magnitude_frac"]
        # the solver keeps the HEAVY entries: the preserved-magnitude
        # fraction must beat the kept-entry fraction (what a random
        # pattern would preserve in expectation), and stay < 1 since a
        # both-axes 2:4 pattern really drops entries.
        kept = sum(int(np.asarray(new[k]["kernel"]).sum()) for k in new)
        total = sum(np.asarray(masks[k]["kernel"]).sum() for k in masks)
        assert kept / total < frac < 1.0
        assert report["pattern"] == "2:4"


# ------------------------------------------------------- execution parity


class TestExecutionParity:
    """Every NM module must match its flax counterpart bit-for-bit in
    structure: forward on mask-multiplied kernels, and the grads the
    optimizer sees once the apply_masks chain has multiplied in the mask."""

    def _masked_kernel(self, shape, seed=0, kill_lead=2):
        rng = np.random.RandomState(seed)
        w = jnp.asarray(rng.randn(*shape), jnp.float32)
        m = jnp.asarray(rng.rand(*shape) > 0.5)
        if kill_lead:  # force a strict live-row subset
            m = m.at[:kill_lead].set(False)
        return w * m, m

    def test_nmdense_forward_and_masked_grads(self):
        rng = np.random.RandomState(0)
        wm, mask = self._masked_kernel((16, 8))
        ki, ko = _live(np.asarray(mask), 8)
        b = jnp.asarray(rng.randn(8), jnp.float32)
        x = jnp.asarray(rng.randn(4, 16), jnp.float32)
        v = {"params": {"kernel": wm, "bias": b}}
        dense, nmd = nn.Dense(8), NMDense(features=8, kept_in=ki, kept_out=ko)
        assert float(jnp.abs(dense.apply(v, x) - nmd.apply(v, x)).max()) < ATOL

        gd = jax.grad(lambda v: (dense.apply(v, x) ** 2).sum())(v)
        gn = jax.grad(lambda v: (nmd.apply(v, x) ** 2).sum())(v)
        mk = mask.astype(jnp.float32)
        assert (
            float(
                jnp.abs(
                    gd["params"]["kernel"] * mk - gn["params"]["kernel"] * mk
                ).max()
            )
            < 1e-4
        )
        assert (
            float(jnp.abs(gd["params"]["bias"] - gn["params"]["bias"]).max())
            < 1e-4
        )

    def test_nmdensegeneral_qkv_layout(self):
        rng = np.random.RandomState(0)
        wm, mask = self._masked_kernel((16, 2, 4), kill_lead=4)
        ki, ko = _live(np.asarray(mask).reshape(16, -1), 8)
        b = jnp.asarray(rng.randn(2, 4), jnp.float32)
        v = {"params": {"kernel": wm, "bias": b}}
        x = jnp.asarray(rng.randn(3, 5, 16), jnp.float32)
        dg = nn.DenseGeneral((2, 4), axis=-1)
        ndg = NMDenseGeneral(features=(2, 4), kept_in=ki, kept_out=ko, axis=-1)
        assert float(jnp.abs(dg.apply(v, x) - ndg.apply(v, x)).max()) < ATOL

    def test_nmdensegeneral_out_layout(self):
        rng = np.random.RandomState(1)
        wm, mask = self._masked_kernel((2, 4, 16), kill_lead=1)
        ki, ko = _live(np.asarray(mask).reshape(8, 16), 16)
        b = jnp.asarray(rng.randn(16), jnp.float32)
        v = {"params": {"kernel": wm, "bias": b}}
        x = jnp.asarray(rng.randn(3, 5, 2, 4), jnp.float32)
        dg = nn.DenseGeneral(16, axis=(-2, -1))
        ndg = NMDenseGeneral(
            features=16, kept_in=ki, kept_out=ko, axis=(-2, -1)
        )
        assert float(jnp.abs(dg.apply(v, x) - ndg.apply(v, x)).max()) < ATOL

    def test_nmconv1x1_strided_no_bias(self):
        rng = np.random.RandomState(0)
        wm, mask = self._masked_kernel((1, 1, 8, 12), kill_lead=0)
        mask = mask.at[0, 0, :2].set(False)
        wm = wm * mask
        ki, ko = _live(np.asarray(mask).reshape(8, 12), 12)
        v = {"params": {"kernel": wm}}
        x = jnp.asarray(rng.randn(2, 8, 8, 8), jnp.float32)
        conv = nn.Conv(12, (1, 1), strides=(2, 2), use_bias=False)
        nconv = NMConv1x1(
            features=12, kept_in=ki, kept_out=ko, strides=(2, 2), use_bias=False
        )
        yd, yn = conv.apply(v, x), nconv.apply(v, x)
        assert yd.shape == yn.shape
        assert float(jnp.abs(yd - yn).max()) < ATOL

    def test_nmselfattention_vs_flax_mha(self):
        rng = np.random.RandomState(0)
        d, h = 16, 2
        mha = nn.MultiHeadDotProductAttention(num_heads=h, deterministic=True)
        x = jnp.asarray(rng.randn(2, 5, d), jnp.float32)
        variables = mha.init(jax.random.PRNGKey(0), x, x)
        qshape = variables["params"]["query"]["kernel"].shape
        mq = jnp.asarray(rng.rand(*qshape) > 0.5).at[:4].set(False)
        ki, ko = _live(np.asarray(mq).reshape(d, -1), qshape[1] * qshape[2])
        p = jax.tree.map(lambda a: a, variables["params"])
        p = dict(p)
        p["query"] = dict(p["query"])
        p["query"]["kernel"] = p["query"]["kernel"] * mq
        nsa = NMSelfAttention(num_heads=h, nm=(("query", (ki, ko)),))
        y_mha = mha.apply({"params": p}, x, x)
        y_nsa = nsa.apply({"params": p}, x)
        assert float(jnp.abs(y_mha - y_nsa).max()) < 1e-4

    def test_jit_one_executable_per_index_map(self):
        rng = np.random.RandomState(0)
        ki, ko = (0, 2, 3, 5), (0, 1, 2, 3, 5, 6)
        f = jax.jit(lambda x, w, b: nm_matmul(ki, ko, x, w, b))
        x = jnp.asarray(rng.randn(4, 8), jnp.float32)
        w = jnp.asarray(rng.randn(8, 8), jnp.float32)
        b = jnp.zeros((8,))
        f(x, w, b)
        first = f._cache_size()
        f(x + 1.0, w * 2.0, b)
        assert f._cache_size() == first == 1


class TestFullModelViTParity:
    """End-to-end acceptance: project a tiny ViT's masks, route it through
    the plan builder, and compare logits AND optimizer-visible grads with
    the masked-dense model on identical parameters."""

    def _setup(self):
        model = VisionTransformer(
            num_classes=10, patch_size=8, embed_dim=32, depth=1, num_heads=2
        )
        v = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False
        )
        params = v["params"]
        masks, report = project_masks(params, make_masks(params), 2, 4)
        plan = build_nm_plan(model, masks)
        assert plan.overrides, "projected ViT must route at least one layer"
        # qkv + out + both mlp layers + head are all hookable
        routed = {k for k in plan.overrides}
        assert {"block0/mlp/fc1", "block0/mlp/fc2", "head"} <= routed
        assert "block0/attn/query" in routed
        nm_model = VisionTransformer(
            num_classes=10,
            patch_size=8,
            embed_dim=32,
            depth=1,
            num_heads=2,
            nm_overrides=plan.as_override_tuple(),
        )
        return model, nm_model, params, masks

    def test_logits_and_grads_match_masked_dense(self):
        model, nm_model, params, masks = self._setup()
        x = jnp.asarray(
            np.random.RandomState(0).randn(2, 32, 32, 3), jnp.float32
        )

        def loss(m):
            def f(p):
                logits = m.apply(
                    {"params": apply_masks(p, masks)}, x, train=False
                )
                return (logits**2).sum(), logits

            return f

        (l_d, y_d), g_d = jax.value_and_grad(loss(model), has_aux=True)(params)
        (l_n, y_n), g_n = jax.value_and_grad(loss(nm_model), has_aux=True)(
            params
        )
        assert float(jnp.abs(y_d - y_n).max()) < 1e-4
        assert abs(float(l_d - l_n)) < 1e-3
        for (p1, a), (p2, b) in zip(
            jax.tree_util.tree_flatten_with_path(g_d)[0],
            jax.tree_util.tree_flatten_with_path(g_n)[0],
        ):
            assert p1 == p2
            scale = max(1.0, float(jnp.abs(a).max()))
            assert float(jnp.abs(a - b).max()) / scale < 1e-4, (
                jax.tree_util.keystr(p1)
            )


# ------------------------------------------------------------ plan builder


class TestPlanBuilder:
    def test_dense_masks_never_route(self):
        model = VisionTransformer(
            num_classes=10, patch_size=8, embed_dim=32, depth=1, num_heads=2
        )
        v = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False
        )
        plan = build_nm_plan(model, make_masks(v["params"]))
        assert plan.overrides == {}
        assert plan.report["coverage_frac"] == 0.0

    def test_unhookable_eligible_layers_reported(self):
        """Satellite 6: a resnet18 downsample 1x1 conv is ELIGIBLE for N:M
        but has no gathered hook — the report must show it unrouted so a
        silent masked-dense fallback is visible, not invisible."""
        from turboprune_tpu.models import create_model

        model = create_model(
            "resnet18", 4, "CIFAR10", compute_dtype=jnp.float32
        )
        v = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)), train=False
        )
        params = v["params"]
        masks, _ = project_masks(params, make_masks(params), 2, 4)
        plan = build_nm_plan(model, masks)
        assert plan.report["layers"]["fc/kernel"]["routed"]
        downsample = [
            rec
            for name, rec in plan.report["layers"].items()
            if not rec["hookable"]
        ]
        assert downsample, "expected unhookable eligible layers in report"
        assert all(not rec["routed"] for rec in downsample)
        assert 0.0 < plan.report["coverage_frac"] < 1.0


# ----------------------------------------------------------- harness smoke


@pytest.mark.usefixtures("tmp_path")
class TestHarnessNMSmoke:
    """The scripts/check.sh nm stage. One harness on synthetic .tpk data:
    level 0 trains dense (all-ones masks never route), the nm criterion
    projects at prune time, level 1 runs gathered and exits back to the
    dense step functions with one cached executable, and a further prune
    evicts the stale plan's cache entry."""

    def _harness(self, tmp_path, extra=()):
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
                # YAML 1.1 parses an unquoted 2:4 as the base-60 integer
                # 124 — the pattern must be quoted (parse_nm rejects the
                # int with exactly this hint).
                "experiment_params.nm_sparsity='2:4'",
                "optimizer_params.lr=0.01",
                "optimizer_params.weight_decay=0.0",
                "model_params.model_name=resnet18",
                *extra,
            ],
        )
        return PruningHarness(cfg, ("smoke", str(tmp_path / "expt")))

    def test_nm_levels_route_and_evict(self, tmp_path):
        from turboprune_tpu import driver

        h = self._harness(
            tmp_path,
            extra=(
                "pruning_params.prune_method=nm",
                "pruning_params.prune_rate=0.5",
            ),
        )

        h.train_one_level(1, 0)
        assert h._plan_ctx is None
        rep = h.last_nm_report
        assert rep is not None and rep["coverage_frac"] == 0.0, (
            "dense level-0 masks must not route"
        )

        driver.prune_level(h, 0.5, 1)
        fc_mask = np.asarray(jax.device_get(h.state.masks["fc"]["kernel"]))
        blocks = fc_mask.any(axis=1).reshape(-1, 4).sum(axis=1)
        assert blocks.max() <= 2, "nm criterion must leave 2:4 in-axis blocks"
        # 4-class head: the output-axis guard keeps every logit column
        assert fc_mask.any(axis=0).all()

        s1 = h.train_one_level(1, 1)
        assert h._plan_ctx is None, "exit must restore dense fns in finally"
        rep = h.last_nm_report
        assert rep["coverage_frac"] > 0.0
        fc = rep["layers"]["fc/kernel"]
        assert fc["routed"] and fc["kept_in_frac"] == pytest.approx(0.5)
        assert fc["kept_out_frac"] == 1.0
        assert len(h._plan_step_cache) == 1
        keys_l1 = set(h._plan_step_cache)
        snap = tracing.gauges()
        assert snap["plan_step_cache_size"] == 1
        assert snap["plan_coverage_frac"] == pytest.approx(rep["coverage_frac"])
        assert s1["test_acc"] >= 0.0

        # A further prune must evict the stale plan's executable. With only
        # 4 output columns, magnitude pruning alone can leave every fc row
        # a survivor — identical live set, identical key, cache *reuse*
        # (the no-recompile feature, not a bug) — so kill one whole live
        # in-block to guarantee the index map changes.
        driver.prune_level(h, 0.25, 2)
        masks = jax.tree.map(
            lambda m: None if m is None else np.array(m),
            h.state.masks,
            is_leaf=lambda x: x is None,
        )
        fc_mask = masks["fc"]["kernel"]
        blk = int(np.flatnonzero(fc_mask.any(axis=1))[0]) // 4
        fc_mask[blk * 4 : blk * 4 + 4, :] = False
        h.state = h.state.replace(masks=masks)
        h.masks_written()
        h.train_one_level(1, 2)
        assert len(h._plan_step_cache) == 1
        assert set(h._plan_step_cache).isdisjoint(keys_l1)

    def test_composes_with_compact_train(self, tmp_path):
        """Channel-compact first, N:M the survivors: with whole channels
        dead AND a projected pattern, the level must enter compact (small
        shapes), route the sliced fc through the gathered path, and exit
        both cleanly. Liveness-based planning keeps this exact even though
        slicing destroys M-block alignment."""
        from turboprune_tpu.sparse import build_graph

        h = self._harness(
            tmp_path,
            extra=(
                "experiment_params.compact_train=true",
                "planner.compact_min_savings=0.1",
            ),
        )
        graph = build_graph(h.model, h.state.params)
        masks = jax.tree.map(
            lambda m: None if m is None else np.array(m),
            h.state.masks,
            is_leaf=lambda x: x is None,
        )
        for name, sp in graph.spaces.items():
            node = masks
            for k in sp.producer.kernel[:-1]:
                node = node[k]
            m = node[sp.producer.kernel[-1]]
            m[..., : int(m.shape[-1] * 0.5)] = False
        masks, _ = project_masks(h.state.params, masks, 2, 4)
        h.state = h.state.replace(masks=masks)
        h.masks_written()

        h.train_one_level(1, 1)
        assert h._plan_ctx is None
        crep = h.last_compaction_report
        assert crep is not None and crep["params_after"] < crep["params_before"]
        nrep = h.last_nm_report
        assert nrep["coverage_frac"] > 0.0
        assert nrep["layers"]["fc/kernel"]["routed"]
        # sliced fc keeps only live-channel rows; the projected pattern
        # thins those further, so the gathered width is a strict subset
        assert nrep["layers"]["fc/kernel"]["kept_in_frac"] < 0.75
        # full-coordinate state restored after the level
        assert h.state.params["fc"]["kernel"].shape[0] == 512
