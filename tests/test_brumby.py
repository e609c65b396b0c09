"""The power-retention decoder (models/brumby.py: the Qwen3 block with
ops/retention.py's chunked recurrence where attention stood, rotary
head-normed q and k, a gate a key/value head, SwiGLU, an untied head) against
its plain reference (benchmarks/reference/brumby.py, which shares no code with
the package and runs retention in its quadratic form) on seeded weights: the
whole model and a chip's share of it, logits, loss and every leaf's gradient,
on documents that start inside a chunk, span several and fill one exactly; a
carry that is lost failing that comparison; nothing crossing a document's
start; eight chips' shares of a layer adding up to the uncut reference; the
masks and their count at the published cut; what a layer's backward pass
keeps; the gate's first values."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import brumby as reference
from turboprune_tpu.config import compose
from turboprune_tpu.models import BLOCK_DIFFUSION_MODELS, LANGUAGE_MODELS, SHARED_MODELS, brumby, create_model
from turboprune_tpu.models.blocks import RMSNorm, Share, SwiGLU
from turboprune_tpu.ops import masking, retention
from turboprune_tpu.pruning import prune_the_model

import remat_probe

VOCAB, T, BATCH, CHUNK = 50, 48, 2, 16
# The tiny preset's entry overrides.
TINY = [
    "model_params.model_name=brumby_tiny",
    "model_params.num_hidden_layers=2",
    "model_params.tensor_parallel=2",
    "dataset_params.seq_len=64",
    "dataset_params.num_classes=96",
    "dataset_params.total_batch_size=2",
    "dataset_params.synthetic_num_train=8",
    "dataset_params.synthetic_num_test=3",
    "dataset_params.doc_len_mu=2.5",
    "dataset_params.doc_len_min=2",
    "experiment_params.num_devices=1",
]


def _batch(seed=0):
    """A next-token batch as data/tokens.py makes it, in chunks of 16: a
    document of 5 tokens and one of 11 (a start inside a chunk), one of
    exactly a chunk (16..31) and one to the end; the other sequence one
    document over two chunks and a half, then one of 8."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, VOCAB, (BATCH, T))
    flags = np.zeros((BATCH, T), np.int32)
    flags[0, [5, 16, 32]] = 1
    flags[1, [40]] = 1
    tokens = jnp.asarray(np.stack([ids, np.cumsum(flags, axis=1)], axis=1), jnp.int32)
    return tokens, reference.next_token_targets(tokens[:, 0], tokens[:, 1])


def _spec(model) -> dict:
    """What the reference is told: the published keys, the counts as held."""
    here = brumby.held(model.cfg, model.share)
    return dict(
        dataclasses.asdict(model.cfg), num_attention_heads=here["query_heads"],
        num_key_value_heads=here["kv_heads"], retention_eps=brumby.RETENTION_EPS,
    )  # fmt: skip


def _seeded(share):
    model = create_model("brumby_tiny", VOCAB, share=share)
    tokens, targets = _batch()
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    # Every leaf off its initial value, so that the norms count; the gates'
    # biases stay where the initialisation put them: horizons of 64 tokens and more.
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
    return _seeded((2, 1, 0))


def _close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want))) + 1e-12
    assert float(jnp.max(jnp.abs(got - want))) / scale < tol


def _both(model, params, masks, batch, spec, carry_cut=0):
    """((loss, logits), gradients) of the program and of the reference."""
    tokens, targets = batch

    def ours(p):
        logits = model.apply({"params": masking.apply_masks(p, masks)}, tokens)
        return reference.mean_loss(logits, targets), logits

    def theirs(p):
        logits = reference.forward(
            p, dict(spec, carry_cut=carry_cut), tokens[:, 0], tokens[:, 1], train=True, masks=masks
        )
        return reference.mean_loss(logits, targets), logits

    with jax.default_matmul_precision("highest"):
        return [jax.jit(jax.value_and_grad(f, has_aux=True))(params) for f in (ours, theirs)]


# ------------------------------------------------ (a) against the reference
@pytest.mark.parametrize("held, masked", [("whole", "half"), ("share", "dense")])
def test_the_model_equals_the_reference_logits_loss_and_gradients(request, held, masked):
    model, params, masks, batch, spec = request.getfixturevalue(held)
    ((loss, logits), grads), ((ref_loss, ref_logits), ref_grads) = _both(
        model, params, masks[masked], batch, spec
    )
    assert logits.shape == (BATCH, T, VOCAB) and logits.dtype == jnp.float32
    _close(logits, ref_logits, 1e-5)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref_grads)):
        assert float(jnp.max(jnp.abs(w))) > 0, masking.path_name(path)  # every leaf is in the graph
        _close(g, w, 1e-4)
    if masked == "half":  # a masked weight gets no data gradient
        for g, m in zip(masking.mask_leaves(masking.mask_where(masks["half"], lambda m, g: g, grads)),
                        masking.mask_leaves(masks["half"])):  # fmt: skip
            assert float(jnp.max(jnp.abs(jnp.where(m, 0.0, g)))) == 0.0
    # The reference's loss in blocks of rows (what follows the program's steps) is its loss.
    tokens, targets = batch
    with jax.default_matmul_precision("highest"):
        blocked = reference.loss(params, spec, tokens[:, 0], tokens[:, 1], targets, masks=masks[masked])
    assert float(blocked) == pytest.approx(float(ref_loss), rel=1e-6)


def test_a_carry_that_is_lost_fails_the_comparison(whole, monkeypatch):
    """The same model with nothing read of the state that enters a chunk:
    what a kernel whose carry is wrong would compute. It is far from the
    reference (the gates start at horizons of 64 tokens and more, so a
    chunk's state is most of what its first tokens see) and it is exactly the
    reference with its pairs cut at the chunk's length, the reading
    ``carry_cut`` exists for."""
    model, params, masks, batch, spec = whole
    sound = retention.chunk_decays

    def lost(log_decay, seg):
        cum, to_end, from_start, carried = sound(log_decay, seg)
        return cum, to_end, jnp.zeros_like(from_start), carried

    monkeypatch.setattr(retention, "chunk_decays", lost)
    ((loss, logits), grads), ((ref_loss, ref_logits), _) = _both(model, params, masks["dense"], batch, spec)
    gap = float(jnp.max(jnp.abs(logits - ref_logits))) / float(jnp.max(jnp.abs(ref_logits)))
    assert gap > 1e-2 and abs(float(loss) - float(ref_loss)) > 1e-4 * float(ref_loss)
    _, ((cut_loss, cut_logits), cut_grads) = _both(model, params, masks["dense"], batch, spec, CHUNK)
    _close(logits, cut_logits, 1e-5)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(cut_grads)):
        _close(g, w, 1e-4)


def test_the_gates_start_at_horizons_a_chunk_cannot_forget():
    bias = brumby._horizon_bias_init(jax.random.PRNGKey(0), (4096,))
    horizon = 1.0 + np.exp(np.asarray(bias, np.float64))  # 1 / (1 - sigmoid(b))
    lo, hi = brumby.HORIZON
    assert lo * 0.999 <= float(horizon.min()) and float(horizon.max()) <= hi * 1.001
    # Log-uniform: a quarter of them under lo^(3/4) hi^(1/4), half under the geometric mean.
    assert abs(np.mean(horizon < lo**0.75 * hi**0.25) - 0.25) < 0.03
    assert abs(np.mean(horizon < (lo * hi) ** 0.5) - 0.5) < 0.03
    # What a state entering a chunk of the cell's 512 tokens is worth at its end.
    assert float(jnp.exp(512 * jax.nn.log_sigmoid(bias)).max()) > 0.9


# ------------------------------------- (b) nothing crosses a document's start
def test_a_packed_pair_is_the_two_documents_run_apart(whole):
    """Two documents of 21 and 27 tokens in one sequence against each alone
    (padded behind with a document of its own, which causality keeps out of
    it): the mixer's output at every token, states and positions restarted."""
    model, params, _, _, _ = whole
    c = model.cfg
    mixer = brumby.RetentionMixer(
        c.num_attention_heads, c.num_key_value_heads, c.head_dim, c.rms_norm_eps, c.rope_theta, CHUNK
    )
    run = jax.jit(lambda x, s: mixer.apply({"params": params["layers_0"]["retention"]}, x, s))
    cut, rng = 21, np.random.default_rng(7)
    seg = jnp.asarray(np.r_[np.zeros(cut, np.int32), np.ones(T - cut, np.int32)][None])
    alone = lambda n: jnp.asarray(np.r_[np.zeros(n, np.int32), np.ones(T - n, np.int32)][None])
    x = jnp.asarray(rng.normal(size=(1, T, c.hidden_size)), jnp.float32)
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


def test_eight_chips_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Eight chips share the layer, tensor-parallel over its 8 key/value
    heads: each holds a key/value head with its 2 query heads and its gate,
    and 6 of the MLP's 48 columns. The norms are whole on every chip and
    counted once; the eight partial sums of the mixer go on to the MLP, whose
    eight partial sums end the layer: the uncut reference's layer."""
    chips, d = 8, 8
    c = dataclasses.replace(
        brumby.BrumbyConfig(**brumby.BRUMBY_TINY), num_attention_heads=16, num_key_value_heads=8
    )
    here = brumby.held(c, Share(chips, 1, 0))
    assert here == dict(query_heads=2, kv_heads=1, dense_columns=6)
    tokens, _ = _batch()
    seg = tokens[:, 1]
    x = jax.random.normal(jax.random.PRNGKey(3), (BATCH, T, c.hidden_size))
    p = jax.jit(brumby.BrumbyBlock(c, Share()).init)(jax.random.PRNGKey(4), x, seg)["params"]
    keys = jax.random.split(jax.random.PRNGKey(5), len(jax.tree.leaves(p)))
    p = jax.tree.unflatten(
        jax.tree.structure(p),
        [w + 0.05 * jax.random.normal(k, w.shape) for w, k in zip(jax.tree.leaves(p), keys)],
    )
    spec = dict(dataclasses.asdict(c), retention_eps=brumby.RETENTION_EPS)
    norm = lambda p, name, v: RMSNorm(c.rms_norm_eps).apply({"params": p[name]}, v)
    mixer = brumby.RetentionMixer(2, 1, d, c.rms_norm_eps, c.rope_theta, c.retention_chunk)
    n = c.intermediate_size // chips

    def shares(p, x):
        u, h = norm(p, "input_norm", x), x
        for chip in range(chips):
            q, kv = jnp.arange(2 * chip * d, 2 * (chip + 1) * d), jnp.arange(chip * d, (chip + 1) * d)
            mine = _slice(
                p["retention"], **{"q_proj/kernel": (1, q), "k_proj/kernel": (1, kv), "v_proj/kernel": (1, kv),
                                   "o_proj/kernel": (0, q), "gate_weight": (1, jnp.arange(chip, chip + 1)),
                                   "gate_bias": (0, jnp.arange(chip, chip + 1))},
            )  # fmt: skip
            h = h + mixer.apply({"params": mine}, u, seg)
        u, out = norm(p, "post_attention_norm", h), h
        for chip in range(chips):
            cols = jnp.arange(chip * n, (chip + 1) * n)
            both = jnp.concatenate([cols, cols + c.intermediate_size])
            mine = _slice(p["mlp"], **{"in_proj/kernel": (1, both), "out_proj/kernel": (0, cols)})
            out = out + SwiGLU(n).apply({"params": mine}, u)
        return out

    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: reference.block(x, seg, p, spec))(p, x)
        got = jax.jit(shares)(p, x)
    _close(got, want, 1e-5)


def test_held_divides_what_the_deployment_divides():
    c = brumby.BrumbyConfig(**brumby.BRUMBY_14B_BASE)
    assert brumby.held(c, Share(8, 1, 0)) == dict(query_heads=5, kv_heads=1, dense_columns=2176)
    assert brumby.held(c, Share()) == dict(query_heads=40, kv_heads=8, dense_columns=17408)
    with pytest.raises(ValueError, match="does not divide"):
        brumby.held(c, Share(3, 1, 0))
    with pytest.raises(ValueError, match="experts, rank"):  # a dense model: nothing for expert_parallel
        brumby.held(c, Share(8, 2, 0))


# ------------------------------------------- (d) what a backward pass keeps
def test_one_trace_keeps_q_k_v_and_the_gate_and_a_bare_checkpoint_none(whole, monkeypatch):
    model, params, _, (tokens, targets), _ = whole
    loss = lambda m: lambda p: reference.mean_loss(m.apply({"params": p}, tokens), targets)
    kept = remat_probe.kept_shapes(loss(model), params)
    c = model.cfg
    assert remat_probe.gauges()[0] == 2 * 4 and remat_probe.gauges()[1] > 0
    group = c.num_attention_heads // c.num_key_value_heads
    assert kept.count((BATCH, c.num_key_value_heads, group, T, c.head_dim)) == 2  # q, a layer
    assert kept.count((BATCH, c.num_key_value_heads, T, c.head_dim)) == 4  # k and v
    assert kept.count((BATCH, T, c.num_key_value_heads)) == 2  # lam
    remat_probe.bare(monkeypatch)
    bare = remat_probe.kept_shapes(loss(create_model("brumby_tiny", VOCAB)), params)
    assert (BATCH, T, c.num_key_value_heads) not in bare and len(bare) < len(kept)
    assert set(brumby.SAVED) == {"ret_q", "ret_k", "ret_v", "ret_lam"}


# --------------------------------------------- (e) pruning sees every kernel
def test_masks_and_magnitude_pruning_reach_every_kernel_and_count_the_cut():
    """ops/masking.py and pruning/ take the new tree with no edit. At the
    published cut, six layers as one chip of eight holds them: a layer's six
    kernels are 41,287,680 weights, the head 97,239,040; the gate's map and
    bias are no kernels."""
    model = create_model("brumby_14b_base", 18992, num_layers=6, share=(8, 1, 0))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 2, 8), jnp.int32))["params"]
    masks = jax.tree_util.tree_map_with_path(
        lambda p, s: np.ones(s.shape, bool) if masking.is_prunable_path(p) else None, shapes
    )
    layers = masking.mask_layers(masks)
    sizes = {n: s for n, s, _ in layers}
    assert len(layers) == 6 * 6 + 1
    assert sizes["layers_0/retention/q_proj/kernel"] == (5120, 640) == sizes["layers_5/retention/o_proj/kernel"][::-1]
    assert sizes["layers_0/retention/k_proj/kernel"] == sizes["layers_0/retention/v_proj/kernel"] == (5120, 128)
    assert sizes["layers_3/mlp/in_proj/kernel"] == (5120, 4352) and sizes["layers_3/mlp/out_proj/kernel"] == (2176, 5120)
    assert sizes["lm_head/kernel"] == (5120, 18992)
    gate = masks["layers_2"]["retention"]
    assert gate["gate_weight"] is None and gate["gate_bias"] is None and masks["embedding"] is None
    assert shapes["layers_2"]["retention"]["gate_weight"].shape == (5120, 1)
    layer = 2 * 3_276_800 + 2 * 655_360 + 22_282_240 + 11_141_120
    prunable = sum(n for _, _, n in layers)
    assert layer == 41_287_680 and prunable == 6 * layer + 97_239_040 == 344_965_120
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    # The embedding, thirteen norms of 5,120, twelve of 128, six gates of 5,120 + 1.
    assert total - prunable == 97_239_040 + 13 * 5120 + 12 * 128 + 6 * 5121 == 97_337_862
    # 17 bytes a prunable weight with the step's temporaries and 16 another (PERF.md section 4): GB.
    assert round((17 * prunable + 16 * (total - prunable)) / 1e9, 2) == 7.42

    # The tiny tree through the global magnitude criterion: a fifth of the kernels' weights go.
    tiny = create_model("brumby_tiny", VOCAB)
    params = jax.jit(tiny.init)(jax.random.PRNGKey(0), jnp.zeros((1, 2, 8), jnp.int32))["params"]
    pruned = prune_the_model("mag", tiny, {"params": params}, masking.make_masks(params), 0.8, jax.random.PRNGKey(0))
    assert masking.overall_sparsity(pruned) == pytest.approx(20.0, abs=0.1)  # per cent
    assert pruned["layers_0"]["retention"]["gate_weight"] is None
    assert all(0 < float(jnp.mean(m)) < 1 for m in masking.mask_leaves(pruned))


# ------------------------------------- (f) what the rest of the system says
def test_the_registry_and_the_configs_cross_checks():
    assert {"brumby_14b_base", "brumby_tiny"} <= set(LANGUAGE_MODELS) & set(SHARED_MODELS)
    assert not {"brumby_14b_base", "brumby_tiny"} & set(BLOCK_DIFFUSION_MODELS)
    cfg = compose("brumby_14b_imp", [])
    assert cfg.model_params.share == (8, 1, 0) and cfg.model_params.num_hidden_layers == 6
    assert (cfg.dataset_params.num_classes, cfg.dataset_params.seq_len) == (18992, 32768)
    assert (cfg.dataset_params.doc_len_mu, cfg.dataset_params.token_skew) == (9.0, "log_uniform")
    tiny = compose("brumby_14b_imp", TINY)
    assert tiny.model_params.model_name == "brumby_tiny" and tiny.model_params.share == (2, 1, 0)
    with pytest.raises(ValueError, match="no layer_pattern"):
        create_model("brumby_tiny", VOCAB, layer_pattern="EM")
    with pytest.raises(ValueError, match="num_layers"):
        create_model("brumby_tiny", VOCAB, num_layers=41)
    assert brumby.DEGREE == 2 and brumby.CHUNK == 512 and brumby.RETENTION_EPS == 1e-16
