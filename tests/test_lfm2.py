"""The convolution-hybrid sparse-expert decoder (models/lfm2.py: the doubly
gated short convolution, rotary head-normed attention under ops/flash.py's
packed causal kernels, a dense SwiGLU layer and sigmoid-routed gated experts
in one tree, a tied head) against its plain reference
(benchmarks/reference/lfm2_moe.py, which shares no code with the package) on
seeded weights: the whole model and a chip's share of it, logits, loss and
every leaf's gradient; nothing crossing a document's start; the shares of
every divided part adding up to the uncut reference; the router; a routing
that overflows the pair buffer; the masks and their count at the published
cut; what a layer's backward pass keeps; the step's counters."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import lfm2_moe as reference
from turboprune_tpu.config import compose
from turboprune_tpu.models import BLOCK_DIFFUSION_MODELS, LANGUAGE_MODELS, SHARED_MODELS, create_model, lfm2
from turboprune_tpu.models.blocks import GatedExperts, RotaryAttention, Router, Share, SparseMoE, SwiGLU
from turboprune_tpu.ops import masking, moe

import remat_probe

VOCAB, T, BATCH = 50, 32, 2
# The tiny preset's entry overrides (tests/test_lfm2_ladder.py runs them).
TINY = [
    "model_params.model_name=lfm2_moe_tiny",
    "model_params.num_hidden_layers=3",
    "model_params.tensor_parallel=1",
    "model_params.expert_parallel=2",
    "model_params.expert_rank=1",
    "dataset_params.seq_len=64",
    "dataset_params.num_classes=96",
    "dataset_params.total_batch_size=2",
    "dataset_params.synthetic_num_train=8",
    "dataset_params.synthetic_num_test=3",
    "dataset_params.doc_len_mu=2.5",
    "dataset_params.doc_len_min=2",
    "experiment_params.num_devices=1",
]


def _batch(seed=0, one_id=False):
    """A next-token batch as data/tokens.py makes it: documents of one, two
    and many tokens, one that starts a token before the sequence ends."""
    rng = np.random.default_rng(seed)
    ids = np.zeros((BATCH, T), np.int64) if one_id else rng.integers(0, VOCAB, (BATCH, T))
    flags = np.zeros((BATCH, T), np.int32)
    flags[0, [5, 16, 17, 19]] = 1
    flags[1, [22, T - 1]] = 1
    seg = np.cumsum(flags, axis=1)
    tokens = jnp.asarray(np.stack([ids, seg], axis=1), jnp.int32)
    return tokens, reference.next_token_targets(tokens[:, 0], tokens[:, 1])


def _sparse_moe(c, experts_here, expert_offset):
    """A routed layer's feed-forward as models/lfm2.py builds it."""
    return SparseMoE(
        Router(c.num_experts, c.num_experts_per_tok, c.routed_scaling_factor, lfm2.ROUTER_EPS),
        GatedExperts(
            c.hidden_size, c.moe_intermediate_size, c.num_experts, c.num_experts_per_tok,
            experts_here, expert_offset,
        ),
    )  # fmt: skip


def _attention(c, heads, kv_heads):
    """An attention layer's mixer as models/lfm2.py calls it: ``(params, u, seg)``."""
    mixer = RotaryAttention(heads, kv_heads, c.head_dim, c.norm_eps, c.rope_theta)
    return lambda p, u, seg: mixer.apply({"params": p}, u, *lfm2.packed_causal(seg))


def _spec(model) -> dict:
    """What the reference is told: the published keys, the counts as held."""
    here = lfm2.held(model.cfg, model.share)
    return dict(
        dataclasses.asdict(model.cfg), num_attention_heads=here["query_heads"],
        num_key_value_heads=here["kv_heads"], expert_offset=here["expert_offset"],
    )  # fmt: skip


def _seeded(share, **batch):
    model = create_model("lfm2_moe_tiny", VOCAB, share=share)
    tokens, targets = _batch(**batch)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    # Every leaf off its initial value, so that the norms and the bias count.
    keys = jax.random.split(jax.random.PRNGKey(1), len(jax.tree.leaves(params)))
    params = jax.tree.unflatten(
        jax.tree.structure(params),
        [p + 0.05 * jax.random.normal(k, p.shape) for p, k in zip(jax.tree.leaves(params), keys)],
    )
    masks = masking.make_masks(params)
    half = jax.tree.map(
        lambda m: jax.random.bernoulli(jax.random.PRNGKey(m.size), 0.5, m.shape), masks
    )
    return model, params, {"dense": masks, "half": half}, (tokens, targets), _spec(model)


@pytest.fixture(scope="module")
def whole():
    return _seeded(())


@pytest.fixture(scope="module")
def share():
    return _seeded((2, 4, 1))


def _close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want))) + 1e-12
    assert float(jnp.max(jnp.abs(got - want))) / scale < tol


# ------------------------------------------------ (a) against the reference
@pytest.mark.parametrize("held, masked", [("whole", "half"), ("share", "dense")])
def test_the_model_equals_the_reference_logits_loss_gradients_and_choice(request, held, masked):
    model, params, masks, (tokens, targets), spec = request.getfixturevalue(held)

    def ours(p):
        logits, sown = model.apply(
            {"params": masking.apply_masks(p, masks[masked])}, tokens, mutable=["intermediates"]
        )
        return reference.mean_loss(logits, targets), (logits, sown["intermediates"])

    def theirs(p):
        logits = reference.forward(p, spec, tokens[:, 0], tokens[:, 1], train=True, masks=masks[masked])
        return reference.mean_loss(logits, targets), logits

    with jax.default_matmul_precision("highest"):
        (loss, (logits, sown)), grads = jax.jit(jax.value_and_grad(ours, has_aux=True))(params)
        (ref_loss, ref_logits), ref_grads = jax.jit(jax.value_and_grad(theirs, has_aux=True))(params)
    assert logits.shape == (BATCH, T, VOCAB) and logits.dtype == jnp.float32
    _close(logits, ref_logits, 1e-5)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref_grads)):
        name = masking.path_name(path)
        if name.endswith("router/bias"):  # it moves the choice alone: no gradient reaches it
            assert float(jnp.max(jnp.abs(g))) == float(jnp.max(jnp.abs(w))) == 0.0
            continue
        assert float(jnp.max(jnp.abs(w))) > 0, name  # every other leaf is in the graph
        _close(g, w, 1e-4)
    if masked == "half":  # a masked weight gets no data gradient
        for g, m in zip(masking.mask_leaves(masking.mask_where(masks["half"], lambda m, g: g, grads)),
                        masking.mask_leaves(masks["half"])):  # fmt: skip
            assert float(jnp.max(jnp.abs(jnp.where(m, 0.0, g)))) == 0.0
    # On the routed layers' own inputs (sown beside the choice) the reference's router
    # chooses what the program chose; the dense layer routes nothing.
    assert set(sown) == {"layers_1", "layers_2"}
    layers = masking.apply_masks(params, masks[masked])
    for name, layer in sown.items():
        ours_top = np.sort(layer["mlp"]["top"][0], axis=-1)
        np.testing.assert_array_equal(ours_top, reference.routing(layer["moe_in"][0], layers[name], spec))


def test_the_selection_bias_moves_the_choice_and_not_the_weights():
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    router = {
        "weight": jnp.asarray(0.3 * rng.normal(size=(32, 16)), jnp.float32),
        "bias": jnp.asarray(rng.normal(size=16), jnp.float32),
    }
    spec = {"num_experts_per_tok": 4, "routed_scaling_factor": 1.0}
    logits = jnp.einsum("nd,de->ne", h, router["weight"], precision=jax.lax.Precision.HIGHEST)
    top, w = moe.route(logits, router["bias"], 4, 1.0, lfm2.ROUTER_EPS)
    ref_top, ref_w = reference.route(h, router, spec)
    np.testing.assert_array_equal(np.sort(top, axis=-1), np.sort(ref_top, axis=-1))
    np.testing.assert_allclose(np.sort(w, axis=-1), np.sort(ref_w, axis=-1), rtol=1e-6)
    unbiased, _ = moe.route(logits, jnp.zeros(16), 4, 1.0, lfm2.ROUTER_EPS)
    assert np.any(np.sort(top, axis=-1) != np.sort(unbiased, axis=-1))
    # The weights are the chosen scores over their sum and the source's 1e-6: of s, never of s + b.
    s = np.take_along_axis(np.asarray(jax.nn.sigmoid(logits)), np.asarray(top), axis=-1)
    np.testing.assert_allclose(w, s / (s.sum(axis=-1, keepdims=True) + 1e-6), rtol=1e-6)
    # The default is the sparse-expert hybrid's 1e-20: its program is what it was.
    np.testing.assert_array_equal(moe.route(logits, router["bias"], 4, 5.0)[1],
                                  moe.route(logits, router["bias"], 4, 5.0, 1e-20)[1])  # fmt: skip


# ------------------------------------- (b) nothing crosses a document's start
def test_positions_restart_with_every_document():
    seg = jnp.asarray([[0] * 5 + [1] + [2] * 6, [3] * 12], jnp.int32)
    want = [[0, 1, 2, 3, 4, 0, 0, 1, 2, 3, 4, 5], list(range(12))]
    assert lfm2.positions(seg).tolist() == reference.positions(seg).tolist() == want


@pytest.mark.parametrize("kind", ["conv", "full_attention"])
def test_a_packed_pair_is_the_two_documents_run_apart(whole, kind):
    """Two documents of 11 and 21 tokens in one sequence against each alone
    (padded behind with a document of its own, which causality keeps out of
    it): the mixer's output at every token. Everything else of a layer is a
    token's own."""
    model, params, _, _, _ = whole
    c = model.cfg
    cut, rng = 11, np.random.default_rng(7)
    seg = jnp.asarray(np.r_[np.zeros(cut, np.int32), np.ones(T - cut, np.int32)][None])
    alone = lambda n: jnp.asarray(np.r_[np.zeros(n, np.int32), np.ones(T - n, np.int32)][None])
    x = jnp.asarray(rng.normal(size=(1, T, c.hidden_size)), jnp.float32)
    if kind == "conv":
        mixer, p = lfm2.ShortConv(c.hidden_size, c.conv_L_cache), params["layers_0"]["mixer"]
        run = jax.jit(lambda x, s: mixer.apply({"params": p}, x, s))
    else:
        mixer = _attention(c, c.num_attention_heads, c.num_key_value_heads)
        run = jax.jit(lambda x, s: mixer(params["layers_1"]["mixer"], x, s))
    pad = lambda x: jnp.pad(x, ((0, 0), (0, T - x.shape[1]), (0, 0)))
    with jax.default_matmul_precision("highest"):
        packed = run(x, seg)
        first = run(pad(x[:, :cut]), alone(cut))[:, :cut]
        second = run(pad(x[:, cut:]), alone(T - cut))[:, : T - cut]
        one_document = run(x, jnp.zeros_like(seg))
    np.testing.assert_allclose(packed[:, :cut], first, atol=2e-6)
    np.testing.assert_allclose(packed[:, cut:], second, atol=2e-6)
    assert float(jnp.max(jnp.abs(packed[:, cut:] - one_document[:, cut:]))) > 1e-3


# ------------------------------------------------- (c) the shares add up
def _slice(tree, **cuts):
    """``tree`` with the named leaves cut: name -> (axis, index array)."""

    def go(path, leaf):
        name = masking.path_name(path)
        for key, (axis, index) in cuts.items():
            if name == key:
                return jnp.take(leaf, index, axis=axis)
        return leaf

    return jax.tree_util.tree_map_with_path(go, tree)


@pytest.mark.parametrize("part", ["experts", "conv", "attention", "dense"])
def test_the_shares_of_every_divided_part_add_up_to_the_uncut_reference(whole, part):
    """Four chips share each layer, the same four for everything. Each holds
    four of the sixteen experts; a query head with the key/value head it reads
    (each of the two key/value heads is held by two chips and counted where it
    is held); a quarter of the convolution's channels, the same of B, C and u;
    a quarter of the dense MLP's columns, of the gate and of the value. The
    four partial sums of each part are the uncut reference's."""
    model, params, _, (tokens, _), spec = whole
    c, chips, seg = model.cfg, 4, tokens[:, 1]
    x = jax.random.normal(jax.random.PRNGKey(3), (BATCH, T, c.hidden_size))
    got = jnp.zeros_like(x)
    with jax.default_matmul_precision("highest"):
        if part == "experts":
            p = params["layers_1"]["mlp"]
            want = reference.sparse_moe(x, p, spec)
            for rank in range(chips):
                experts = jnp.arange(4 * rank, 4 * rank + 4)
                mine = _slice(p, **{f"experts/kernel_{k}": (0, experts) for k in ("gate", "up", "down")})
                out, sown = _sparse_moe(c, 4, 4 * rank).apply({"params": mine}, x, mutable=["counters"])
                assert int(sown["counters"]["moe_dropped_pairs"][0]) == 0
                got = got + out
        elif part == "conv":
            p, n = params["layers_0"]["mixer"], c.hidden_size // chips
            want = reference.short_conv(x, seg, p)
            for chip in range(chips):
                mine = jnp.arange(chip * n, (chip + 1) * n)
                bcu = jnp.concatenate([mine + k * c.hidden_size for k in range(3)])
                cut = _slice(p, **{"in_proj/kernel": (1, bcu), "conv_taps": (1, mine), "out_proj/kernel": (0, mine)})
                got = got + lfm2.ShortConv(n, c.conv_L_cache).apply({"params": cut}, x, seg)
        elif part == "attention":
            p, d = params["layers_1"]["mixer"], c.head_dim
            want = reference.attention(x, seg, p, spec)
            for chip in range(chips):
                q = jnp.arange(chip * d, (chip + 1) * d)
                kv = jnp.arange((chip // 2) * d, (chip // 2 + 1) * d)
                cut = _slice(
                    p, **{"q_proj/kernel": (1, q), "k_proj/kernel": (1, kv),
                          "v_proj/kernel": (1, kv), "o_proj/kernel": (0, q)},
                )  # fmt: skip
                got = got + _attention(c, 1, 1)(cut, x, seg)
        else:
            p, n = params["layers_0"]["mlp"], c.intermediate_size // chips
            want = reference.mlp(x, p)
            for chip in range(chips):
                mine = jnp.arange(chip * n, (chip + 1) * n)
                both = jnp.concatenate([mine, mine + c.intermediate_size])
                cut = _slice(p, **{"in_proj/kernel": (1, both), "out_proj/kernel": (0, mine)})
                got = got + SwiGLU(n).apply({"params": cut}, x)
    _close(got, want, 1e-5)


def test_held_divides_what_the_deployment_divides():
    c = lfm2.Lfm2Config(**lfm2.LFM2_8B_A1B)
    assert lfm2.held(c, Share(4, 4, 0)) == dict(
        query_heads=8, kv_heads=2, conv_channels=512, dense_columns=1792, experts_here=8, expert_offset=0
    )
    assert lfm2.held(c, Share(16, 4, 3))["kv_heads"] == 1 and lfm2.held(c, Share(16, 4, 3))["expert_offset"] == 24
    assert c.layer_types[:10] == ("conv", "conv", "full_attention", "conv", "conv", "conv") + (
        "full_attention", "conv", "conv", "conv")  # fmt: skip
    assert [i for i, k in enumerate(c.layer_types) if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    with pytest.raises(ValueError, match="does not divide"):
        lfm2.held(c, Share(3, 1, 0))
    with pytest.raises(ValueError, match="experts, rank"):
        lfm2.held(c, Share(1, 5, 0))


# --------------------------------------- (d) no pair dropped, and the counters
def test_no_pair_is_dropped_under_a_routing_that_needs_a_second_round(whole):
    """A routed layer alone, experts 0-7 of 16, on rows that differ little:
    they route almost alike, the held experts they choose outgrow the buffer,
    further rounds run, and the output and every gradient are the reference's."""
    model, params, _, _, spec = whole
    c, p = model.cfg, params["layers_1"]["mlp"]
    p = dict(p, experts={k: v[:8] for k, v in p["experts"].items()})
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(1, 1, 32)) + 0.05 * rng.normal(size=(BATCH, T, 32)), jnp.float32)
    layer = _sparse_moe(c, 8, 0)
    weigh = lambda fn: jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(jnp.sin(fn(p, x))), argnums=(0, 1)))
    ours = lambda p, x: layer.apply({"params": p}, x)
    theirs = lambda p, x: reference.sparse_moe(x, p, dict(spec, expert_offset=0))
    # A buffer a third of the configuration's, so that the rounds run at this size.
    with jax.default_matmul_precision("highest"), pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "CAPACITY_FACTOR", 0.1)
        assert moe.pair_capacity(BATCH * T, 4, 16, 8) == 16 + 8 * 8
        _, counted = layer.apply({"params": p}, x, mutable=["counters"])
        got, got_grads = weigh(ours)(p, x)
        want, want_grads = weigh(theirs)(p, x)
    counted = {k: int(v[0]) for k, v in counted["counters"].items()}
    assert counted["moe_dropped_pairs"] == 0 and counted["moe_rounds"] > 1
    assert counted["moe_load_max"] > BATCH * T // 2  # one expert has most rows
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grads), jax.tree.leaves(want_grads)):
        if not masking.path_name(path).endswith("router/bias"):
            _close(g, w, 1e-4)


def test_the_step_sums_the_routed_layers_counters(whole):
    import optax

    from turboprune_tpu.train import create_train_state
    from turboprune_tpu.train.steps import make_eval_step, make_train_step

    model, params, _, (tokens, targets), spec = whole
    state = create_train_state(
        model, optax.sgd(0.1), jax.random.PRNGKey(0), tokens.shape, variables={"params": params}
    )
    with jax.default_matmul_precision("highest"):
        _, m = jax.jit(make_train_step(model, optax.sgd(0.1)))(state, (tokens, targets))
        e = jax.jit(make_eval_step(model))(state, (tokens, targets))
        ref = reference.mean_loss(reference.forward(params, spec, tokens[:, 0], tokens[:, 1]), targets)
    assert model.counters == (*moe.COUNTERS, "moe_rounds")
    assert set(m) == {"loss_sum", "correct", "count", *model.counters}
    valid = int((np.asarray(targets) >= 0).sum())
    assert float(m["count"]) == valid and abs(float(m["loss_sum"]) / valid - float(ref)) < 1e-5
    # Two routed layers, every expert held: each token's four pairs in each, a round a layer.
    assert int(m["moe_pairs"]) == 2 * 4 * BATCH * T and int(m["moe_rounds"]) == 2
    assert int(m["moe_dropped_pairs"]) == 0
    assert set(e) == {"loss_sum", "correct", "count"}
    assert float(e["loss_sum"]) == pytest.approx(float(m["loss_sum"]), rel=1e-6)


# ------------------------------------------- (e) what a backward pass keeps
def test_one_trace_keeps_the_tagged_values_and_a_bare_checkpoint_none(whole, monkeypatch):
    """The policy is asked as a program that differentiates the layers is
    traced: nothing is compiled here."""
    model, params, _, (tokens, targets), _ = whole
    loss = lambda m: lambda p: reference.mean_loss(m.apply({"params": p}, tokens), targets)
    kept = remat_probe.kept_shapes(loss(model), params)
    # Two routed layers' logits, choice and order; one attention layer's q, k and v.
    assert remat_probe.gauges()[0] == 2 * 3 + 3 and remat_probe.gauges()[1] > 0
    rows, c = BATCH * T, model.cfg
    assert kept.count((rows, c.num_experts)) == 2 and kept.count((rows, c.num_experts_per_tok)) == 2
    assert kept.count((rows * c.num_experts_per_tok,)) == 2  # the pairs' sorted order
    assert kept.count((BATCH * c.num_attention_heads, T, c.head_dim)) == 1
    assert kept.count((BATCH * c.num_key_value_heads, T, c.head_dim)) == 2
    remat_probe.bare(monkeypatch)
    bare = remat_probe.kept_shapes(loss(create_model("lfm2_moe_tiny", VOCAB)), params)
    assert (rows, c.num_experts) not in bare and len(bare) < len(kept)
    assert set(lfm2.SAVED) == {"router_logits", "router_top", "moe_order", "attn_q", "attn_k", "attn_v"}


# --------------------------------------------- (f) pruning sees every kernel
def test_masks_reach_2d_and_stacked_kernels_and_count_the_cut():
    """Dense SwiGLU kernels and stacked expert kernels side by side in one
    tree. At the published cut: ten layers (c c a c c c a c c c), two dense;
    each conv mixer 2 kernels, each attention mixer 4, a dense MLP 2, a routed
    layer 3 x 8; 765,460,480 prunable weights and 34,134,528 others."""
    model = create_model("lfm2_8b_a1b", 16384, num_layers=10, share=(4, 4, 0))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 2, 8), jnp.int32))["params"]
    masks = jax.tree_util.tree_map_with_path(
        lambda p, s: np.ones(s.shape, bool) if masking.is_prunable_path(p) else None, shapes
    )
    layers = masking.mask_layers(masks)
    sizes = {n: s for n, s, _ in layers}
    assert len(layers) == 8 * 2 + 2 * 4 + 2 * 2 + 8 * 3 * 8 == 220
    assert ("layers_9/mlp/experts/kernel_down[7]", (1792, 2048), 1792 * 2048) in layers
    assert sizes["layers_0/mixer/in_proj/kernel"] == (2048, 1536) and sizes["layers_0/mixer/out_proj/kernel"] == (512, 2048)
    assert sizes["layers_1/mlp/in_proj/kernel"] == (2048, 3584) and sizes["layers_1/mlp/out_proj/kernel"] == (1792, 2048)
    assert sizes["layers_2/mixer/q_proj/kernel"] == (2048, 512) and sizes["layers_2/mixer/k_proj/kernel"] == (2048, 128)
    assert masks["embedding"] is None  # the tied head is the embedding: neither is pruned
    router, mixer = masks["layers_2"]["mlp"]["router"], masks["layers_3"]["mixer"]
    assert router["weight"] is None and router["bias"] is None and mixer["conv_taps"] is None
    conv, attn, expert = 4_194_304, 2_621_440, 11_010_048
    prunable = sum(n for _, _, n in layers)
    assert prunable == 2 * (conv + expert) + 2 * (attn + 8 * expert) + 6 * (conv + 8 * expert) == 765_460_480
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert total - prunable == 34_134_528 and shapes["embedding"].shape == (16384, 2048)
    # 13 bytes a prunable weight in use and 12 another (weight, momentum, mask, gradient): GiB.
    assert round((13 * prunable + 12 * (total - prunable)) / 2**30, 2) == 9.65


# ------------------------------------- (g) what the rest of the system says
def test_the_registry_and_the_configs_cross_checks():
    assert {"lfm2_8b_a1b", "lfm2_moe_tiny"} <= set(LANGUAGE_MODELS) & set(SHARED_MODELS)
    assert not {"lfm2_8b_a1b", "lfm2_moe_tiny"} & set(BLOCK_DIFFUSION_MODELS)
    cfg = compose("lfm2_8b_a1b_imp", [])
    assert cfg.model_params.share == (4, 4, 0) and cfg.model_params.num_hidden_layers == 10
    assert cfg.dataset_params.input_spec() == ((1, 2, 512), "int32")
    assert (cfg.dataset_params.num_classes, cfg.dataset_params.token_skew) == (16384, "uniform")
    assert moe.pair_capacity(8192, 4, 32, 8) == 13312 and moe.pair_tile(8192, 4, 32) == 128
    tiny = compose("lfm2_8b_a1b_imp", TINY)
    assert tiny.model_params.model_name == "lfm2_moe_tiny" and tiny.model_params.share == (1, 2, 1)
    with pytest.raises(ValueError, match="no layer_pattern"):
        create_model("lfm2_moe_tiny", VOCAB, layer_pattern="EM")
    with pytest.raises(ValueError, match="num_layers"):
        create_model("lfm2_moe_tiny", VOCAB, num_layers=4)
