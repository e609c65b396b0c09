"""The block-diffusion sparse-expert transformer (models/sdar.py, ops/moe.py's
softmax router and gated experts, ops/flash.py's third family, train/steps.py's
weighted loss) against its plain reference (benchmarks/reference/sdar_moe.py,
which shares no code with the package) on seeded weights: the whole model and
a chip's share of it, logits, loss and every leaf's gradient; the shares of a
whole layer and of the head adding up to the uncut reference; the router; a
routing that overflows the pair buffer because every row is one id; the two
copies agreeing; what a layer's backward pass keeps; stacked kernels as
layers; and the four language models' programs, which share models/blocks.py
and the kernels under it, being what they were."""

import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import sdar_moe as reference
from turboprune_tpu.config import compose
from turboprune_tpu.config.schema import ConfigError
from turboprune_tpu.data import tokens as tk
from turboprune_tpu.models import BLOCK_DIFFUSION_MODELS, LANGUAGE_MODELS, SHARED_MODELS, create_model, sdar
from turboprune_tpu.models.blocks import GatedExperts, Head, RotaryAttention, Share, SparseMoE, rotary
from turboprune_tpu.ops import masking, moe
from turboprune_tpu.train.steps import make_eval_step, make_train_step

import remat_probe

VOCAB, T, BATCH, BLOCK = 50, 32, 2, 4
# The tiny preset's entry overrides (tests/test_sdar_ladder.py runs them).
TINY = [
    "model_params.model_name=sdar_moe_tiny",
    "model_params.num_hidden_layers=2",
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
    """A block-diffusion batch as data/tokens.py makes it, documents that
    start inside blocks of the kernel and a last block that is short."""
    rng = np.random.default_rng(seed)
    ids = np.zeros((BATCH, T), np.int64) if one_id else rng.integers(0, VOCAB - 1, (BATCH, T))
    flags = np.zeros((BATCH, T), np.int32)
    flags[0, [5, 16, 17]] = 1
    flags[1, [22]] = 1
    seg = np.cumsum(flags, axis=1)
    rows = jnp.asarray(np.stack([ids, seg, *tk.block_ordinals(seg, BLOCK)], axis=1), jnp.int32)
    return tk.noise_epoch(jax.random.PRNGKey(seed), rows, BLOCK, VOCAB - 1)


def _spec(model) -> dict:
    """What the reference is told: the published keys, the counts as held."""
    here = sdar.held(model.cfg, model.share)
    return dict(
        dataclasses.asdict(model.cfg), num_attention_heads=here["query_heads"],
        num_key_value_heads=here["kv_heads"], expert_offset=here["expert_offset"],
        mask_row_scale=sdar.MASK_ROW,
    )  # fmt: skip


def _seeded(share, **batch):
    model = create_model("sdar_moe_tiny", VOCAB, share=share)
    tokens, labels = _batch(**batch)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    # Every leaf off its initial value, so that the norms count.
    keys = jax.random.split(jax.random.PRNGKey(1), len(jax.tree.leaves(params)))
    params = jax.tree.unflatten(
        jax.tree.structure(params),
        [p + 0.05 * jax.random.normal(k, p.shape) for p, k in zip(jax.tree.leaves(params), keys)],
    )
    masks = masking.make_masks(params)
    half = jax.tree.map(
        lambda m: jax.random.bernoulli(jax.random.PRNGKey(m.size), 0.5, m.shape), masks
    )
    return model, params, {"dense": masks, "half": half}, (tokens, labels), _spec(model)


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
def test_the_model_equals_the_reference_logits_loss_and_gradients(request, held, masked):
    model, params, masks, (tokens, labels), spec = request.getfixturevalue(held)

    def ours(p):
        logits = model.apply({"params": masking.apply_masks(p, masks[masked])}, tokens)
        return reference.loss(logits, *labels), logits

    def theirs(p):
        logits = reference.forward(p, spec, tokens, train=True, masks=masks[masked])
        return reference.loss(logits, *labels), logits

    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.jit(jax.value_and_grad(ours, has_aux=True))(params)
        (ref_loss, ref_logits), ref_grads = jax.jit(jax.value_and_grad(theirs, has_aux=True))(params)
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


def test_the_steps_read_the_weighted_loss_off_the_batch(whole):
    """A batch with weights: the step's loss is the weighted sum over the
    masked targets divided by the tokens, its ``count`` the tokens, and its
    counters the layers' and the model's own; the eval step likewise, without
    counters. The same step on a next-token batch is what it was."""
    import optax

    from turboprune_tpu.train import create_train_state
    from turboprune_tpu.train.steps import make_scan_chunk, masked_cross_entropy, weighted_cross_entropy

    model, params, _, (tokens, labels), spec = whole
    state = create_train_state(
        model, optax.sgd(0.1), jax.random.PRNGKey(0), tokens.shape, variables={"params": params}
    )
    step = make_train_step(model, optax.sgd(0.1))
    with jax.default_matmul_precision("highest"):
        stacked = jax.tree.map(lambda x: jnp.stack([x] * 2), (tokens, labels))
        _, sums = jax.jit(make_scan_chunk(step))(state, stacked)
        _, m = jax.jit(step)(state, (tokens, labels))
        e = jax.jit(make_eval_step(model))(state, (tokens, labels))
        ref = reference.loss(reference.forward(params, spec, tokens), *labels)
    assert set(m) == {"loss_sum", "correct", "count", *model.counters}
    assert model.counters == (*moe.COUNTERS, "moe_rounds", "masked_targets", *sdar.FLASH_COUNTERS)
    assert float(m["count"]) == BATCH * T and abs(float(m["loss_sum"]) / (BATCH * T) - float(ref)) < 1e-5
    masked = int((np.asarray(labels[0]) >= 0).sum())
    assert int(m["masked_targets"]) == masked and int(sums["masked_targets"]) == 2 * masked
    assert int(m["moe_rounds"]) == 2 and int(m["moe_dropped_pairs"]) == 0  # a round a layer
    # Attention's walk, two layers of two sequences: a kernel block is a whole copy here, so a
    # sequence runs clean onto clean, noised onto clean and noised onto noised, and no step more.
    assert int(m["flash_steps_run"]) == int(m["flash_steps_walked"]) == 2 * BATCH * 3
    assert int(sums["flash_steps_run"]) == 2 * int(m["flash_steps_run"])
    assert set(e) == {"loss_sum", "correct", "count"} and float(e["loss_sum"]) == pytest.approx(float(m["loss_sum"]), rel=1e-6)
    # A place without a token (weight -1) is no token; weights count nothing else.
    logits = jax.random.normal(jax.random.PRNGKey(2), (BATCH, T, VOCAB))
    targets, weights = labels
    gone = weights.at[1].set(-1.0)
    assert float(weighted_cross_entropy(logits, targets.at[1].set(-1), gone)[2]) == T
    ones = jnp.where(targets >= 0, 1.0, 0.0)
    np.testing.assert_allclose(
        weighted_cross_entropy(logits, targets, ones)[0], masked_cross_entropy(logits, targets)[0], rtol=1e-6
    )


@pytest.mark.parametrize(
    "counted, want",
    [
        (None, None),  # a job that keeps no counters
        ({"moe_pairs": 131072.0, "moe_load_max": 1700.0}, None),  # a program from before the walk
        ({"flash_steps_run": 0.0, "flash_steps_walked": 0.0}, None),
        ({"flash_steps_run": 960.0, "flash_steps_walked": 960.0}, 100.0),
        ({"flash_steps_run": 960.0, "flash_steps_walked": 8192.0}, 11.71875),
    ],
)
def test_the_walks_metric_reads_the_two_counters_or_nothing(counted, want):
    from benchmarks import registry

    read = registry.load_metric("flash_blockdiff_walk_run_pct").read
    assert read({} if counted is None else {"moe_softmax": counted}) == want


# ------------------------------------------------- (b) the shares add up
def _slice(tree, **cuts):
    """``tree`` with the named leaves cut: name -> (axis, index array)."""

    def go(path, leaf):
        name = masking.path_name(path)
        for key, (axis, index) in cuts.items():
            if name == key:
                return jnp.take(leaf, index, axis=axis)
        return leaf

    return jax.tree_util.tree_map_with_path(go, tree)


def test_the_shares_of_a_whole_layer_and_of_the_head_add_up_to_the_uncut_reference(whole):
    """Four chips share the layer: a query head each with the key/value head
    it reads (each of the two key/value heads is held by two chips and counted
    where it is held), four of the sixteen experts each, a quarter of the
    vocabulary each. Attention's four parts give the layer's ``h``, the
    experts' four parts the layer's output, the four slices the logits."""
    model, params, _, (tokens, _), spec = whole
    c, p = model.cfg, params["layers_0"]
    doc, blk, pos = tokens[:, tk.DOC], tokens[:, tk.BLK], tokens[:, tk.POS]
    x = jax.random.normal(jax.random.PRNGKey(3), (BATCH, 2 * T, c.hidden_size))
    d, chips = c.head_dim, 4
    norm = lambda scale, v: reference.rmsnorm(v, scale, c.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        want = reference.block(x, doc, blk, pos, p, spec)
        h = x
        for chip in range(chips):
            q = jnp.arange(chip * d, (chip + 1) * d)
            kv = jnp.arange((chip // 2) * d, (chip // 2 + 1) * d)
            part = _slice(
                p["attn"], **{"q_proj/kernel": (1, q), "k_proj/kernel": (1, kv),
                              "v_proj/kernel": (1, kv), "o_proj/kernel": (0, q)},
            )  # fmt: skip
            attn = RotaryAttention(1, 1, d, c.rms_norm_eps, c.rope_theta)
            h = h + attn.apply(
                {"params": part}, norm(p["input_norm"]["scale"], x), *sdar.block_diffusion(doc, blk, pos)
            )
        y = h
        for rank in range(chips):
            experts = jnp.arange(4 * rank, 4 * rank + 4)
            part = _slice(
                p["mlp"], **{f"experts/kernel_{k}": (0, experts) for k in ("gate", "up", "down")}
            )
            layer = SparseMoE(
                sdar.SoftmaxRouter(c.num_experts, c.num_experts_per_tok),
                GatedExperts(
                    c.hidden_size, c.moe_intermediate_size, c.num_experts, c.num_experts_per_tok,
                    4, 4 * rank,
                ),
            )  # fmt: skip
            out, sown = layer.apply(
                {"params": part}, norm(p["post_attention_norm"]["scale"], h), mutable=["counters"]
            )
            assert int(sown["counters"]["moe_dropped_pairs"][0]) == 0
            y = y + out
        _close(y, want, 1e-5)
        last = jax.random.normal(jax.random.PRNGKey(4), (BATCH, T, c.hidden_size))
        whole_logits = reference._mm(last, params["lm_head"]["kernel"], None)
        slices = [
            Head(VOCAB // 2 if i < 1 else VOCAB - VOCAB // 2).apply(
                {"params": {"kernel": params["lm_head"]["kernel"][:, i * (VOCAB // 2) : (i + 1) * (VOCAB // 2) if i < 1 else VOCAB]}},
                last,
            )
            for i in range(2)
        ]
    _close(jnp.concatenate(slices, axis=-1), whole_logits, 1e-5)


# --------------------------------------- (c) the router, and no pair dropped
def test_the_softmax_router_is_the_references():
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    weight = jnp.asarray(0.3 * rng.normal(size=(32, 16)), jnp.float32)
    spec = {"num_experts_per_tok": 4}
    logits = jnp.einsum("nd,de->ne", h, weight, precision=jax.lax.Precision.HIGHEST)
    top, w = moe.route_softmax(logits, 4)
    ref_top, ref_w = reference.route(h, {"weight": weight}, spec)
    np.testing.assert_array_equal(np.sort(top, axis=-1), np.sort(ref_top, axis=-1))
    np.testing.assert_allclose(np.sort(w, axis=-1), np.sort(ref_w, axis=-1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(axis=-1), 1.0, rtol=1e-6)  # over the chosen alone


def test_no_pair_is_dropped_when_every_row_is_one_id():
    """Every token the same id (and so the noised copy's masks one id too):
    the rows route almost alike, the held experts they choose outgrow the
    buffer, further rounds run, and logits and gradients are the reference's."""
    model, params, _, (tokens, labels), spec = _seeded((1, 2, 0), one_id=True)  # experts 0-7 of 16

    def ours(p):
        logits, sown = model.apply({"params": p}, tokens, mutable=["counters"])
        return reference.loss(logits, *labels), (logits, sown["counters"])

    theirs = lambda p: reference.loss(reference.forward(p, spec, tokens, train=True), *labels)
    # A buffer a third of the configuration's, so that the rounds run at this size.
    with jax.default_matmul_precision("highest"), pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "CAPACITY_FACTOR", 0.1)
        assert moe.pair_capacity(2 * BATCH * T, 4, 16, 8) == 32 + 8 * 8
        (_, (logits, counted)), grads = jax.jit(jax.value_and_grad(ours, has_aux=True))(params)
        ref_logits = jax.jit(lambda p: reference.forward(p, spec, tokens))(params)
        ref_grads = jax.jit(jax.grad(theirs))(params)
    layer = counted["layers_0"]["mlp"]
    assert int(layer["moe_dropped_pairs"][0]) == 0 and int(layer["moe_rounds"][0]) > 1
    assert int(layer["moe_load_max"][0]) > 2 * BATCH * T // 2  # one expert has most rows
    _close(logits, ref_logits, 1e-5)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        _close(g, w, 1e-4)


def test_the_gated_experts_are_a_plain_loop_over_the_experts_held():
    """ops/moe.py alone with three kernels an expert, experts 4-7 of 16."""
    rng = np.random.default_rng(6)
    z = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    gate, up = (jnp.asarray(0.2 * rng.normal(size=(4, 32, 24)), jnp.float32) for _ in range(2))
    down = jnp.asarray(0.2 * rng.normal(size=(4, 24, 32)), jnp.float32)
    top, w = moe.route_softmax(jnp.asarray(rng.normal(size=(64, 16)), jnp.float32), 4)

    def plain(z, gate, up, down):
        out = jnp.zeros(z.shape, jnp.float32)
        for e in range(4):
            we = jnp.sum(jnp.where(top == e + 4, w, 0), axis=-1)
            out += we[:, None] * ((jax.nn.silu(z @ gate[e]) * (z @ up[e])) @ down[e])
        return out

    ours = lambda z, gate, up, down: moe.routed_experts(z, top, w, (gate, up, down), 4, 16, 8)[0]
    weigh = lambda fn: jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3))
    with jax.default_matmul_precision("highest"):
        got, got_grads = weigh(ours)(z, gate, up, down)
        want, want_grads = weigh(plain)(z, gate, up, down)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, wg in zip(got_grads, want_grads):
        _close(g, wg, 1e-4)
    assert int(moe.rounds(top, 4, 4, 16, 8)) > 1


# ------------------------------------------------------ (d) the two copies
def test_rotary_is_the_references_and_restarts_with_a_document():
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 12, 2, 8))
    seg = np.asarray([[0] * 5 + [1] * 7])
    pos = jnp.asarray(tk.block_ordinals(seg, BLOCK)[1])
    assert pos[0].tolist() == [0, 1, 2, 3, 4, 0, 1, 2, 3, 4, 5, 6]
    got = rotary(x, pos, 1e6)
    np.testing.assert_allclose(got, reference.rotary(x, pos, 1e6), atol=1e-6)
    np.testing.assert_array_equal(got[:, 0], x[:, 0])  # position 0 turns nothing
    same = rotary(jnp.broadcast_to(x[:, :1], x.shape), pos, 1e6)
    np.testing.assert_array_equal(same[:, 0], same[:, 5])  # a document's first token, again


def test_an_unmasked_noised_copy_is_the_clean_copy(whole):
    """With no token masked the noised rows are the clean rows' tokens at the
    clean rows' positions, and each sees its own block as the clean row does:
    the two halves of every layer's residual stream agree, which they do only
    if positions and mask treat the copies alike."""
    model, params, _, (tokens, _), _ = whole
    tokens = tokens.at[:, tk.NOISED].set(tokens[:, tk.CLEAN])
    with jax.default_matmul_precision("highest"):
        _, sown = model.apply({"params": params}, tokens, mutable=["intermediates"])
    for name, layer in sown["intermediates"].items():
        stream = layer["moe_in"][0]
        np.testing.assert_allclose(stream[:, :T], stream[:, T:], atol=1e-5, err_msg=name)


# --------------------------------------- (e) what a backward pass keeps
def test_the_policys_gradient_is_the_bare_checkpoints(whole, monkeypatch):
    model, params, _, (tokens, labels), _ = whole
    loss = lambda m: lambda p: reference.loss(m.apply({"params": p}, tokens), *labels)
    with jax.default_matmul_precision("highest"):
        kept = jax.jit(jax.grad(loss(model)))(params)
        saved = remat_probe.gauges()
        remat_probe.bare(monkeypatch)
        bare = jax.jit(jax.grad(loss(create_model("sdar_moe_tiny", VOCAB))))(params)
    for g, w in zip(jax.tree.leaves(kept), jax.tree.leaves(bare)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=1e-7)
    # Two layers, six tags a layer: logits, choice, order, q, k, v.
    assert saved[0] == 2 * len(sdar.SAVED) and saved[1] > 0


# ------------------------------------------- (f) pruning sees every kernel
def test_every_experts_every_kernel_is_a_layer_of_its_own():
    """Nothing new in pruning/ or ops/masking.py: three stacked kernels an
    expert are three layers an expert. At the published cut, 8 layers of 4
    projections and 3 x 16 expert kernels, and the head."""
    model = create_model("sdar_30b_a3b", 18992, num_layers=8, share=(8, 8, 0))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 5, 8), jnp.int32))["params"]
    prunable = [masking.is_prunable_path(p) for p, _ in jax.tree_util.tree_leaves_with_path(shapes)]
    masks = jax.tree_util.tree_map_with_path(
        lambda p, s: np.ones(s.shape, bool) if masking.is_prunable_path(p) else None, shapes
    )
    layers = masking.mask_layers(masks)
    assert len(layers) == 8 * (4 + 3 * 16) + 1 == 417 and sum(prunable) == 8 * 7 + 1
    assert ("layers_3/mlp/experts/kernel_gate[15]", (2048, 768), 2048 * 768) in layers
    assert masks["layers_0"]["mlp"]["router"]["weight"] is None and masks["embedding"] is None
    sizes = {n: s for n, s, _ in layers}
    assert sizes["layers_0/attn/q_proj/kernel"] == (2048, 512) and sizes["lm_head/kernel"] == (2048, 18992)
    assert sum(n for _, _, n in layers) == 8 * 78_118_912 + 2048 * 18992


# ------------------------------------- (g) what the rest of the system says
def test_the_registry_and_the_configs_cross_checks():
    assert {"sdar_30b_a3b", "sdar_moe_tiny"} <= set(LANGUAGE_MODELS) & set(SHARED_MODELS)
    assert BLOCK_DIFFUSION_MODELS == ("sdar_30b_a3b", "sdar_moe_tiny")
    cfg = compose("sdar_30b_a3b_imp", [])
    assert cfg.dataset_params.block_length == 4 and cfg.dataset_params.input_spec() == ((1, 5, 512), "int32")
    assert cfg.model_params.share == (8, 8, 0) and cfg.model_params.num_hidden_layers == 8
    assert compose("granite_h_micro_imp", []).dataset_params.input_spec() == ((1, 2, 512), "int32")
    with pytest.raises(ConfigError, match="block_length"):
        compose("sdar_30b_a3b_imp", ["dataset_params.block_length=0"])
    with pytest.raises(ConfigError, match="block_length"):
        compose("granite_h_micro_imp", ["dataset_params.block_length=4"])
    with pytest.raises(ValueError, match="no layer_pattern"):
        create_model("sdar_moe_tiny", VOCAB, layer_pattern="EM")
    with pytest.raises(ValueError, match="does not divide"):
        sdar.held(sdar.SdarConfig(**sdar.SDAR_MOE_TINY), Share(3, 1, 0))


_PROGRAM = """
import hashlib, json, sys, jax, jax.numpy as jnp, numpy as np
from turboprune_tpu.data.tokens import block_ordinals
from turboprune_tpu.models import BLOCK_DIFFUSION_MODELS, create_model

name, kwargs = sys.argv[1], json.loads(sys.argv[2])
model = create_model(name, 50, **kwargs)
rng = np.random.default_rng(20261001)
flags = np.zeros((2, 32), np.int32)
flags[0, [5, 16, 17]], flags[1, [20]] = 1, 1
ids, seg = rng.integers(0, 49, (2, 32)), np.cumsum(flags, axis=1)
rows = [ids, seg]
if name in BLOCK_DIFFUSION_MODELS:
    rows += [*block_ordinals(seg, 4), np.where(rng.random((2, 32)) < 0.3, 49, ids)]
tokens = jnp.asarray(np.stack(rows, axis=1), jnp.int32)
shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)["params"]


def loss(p):
    logits, sown = model.apply({"params": p}, tokens, mutable=["counters"])
    return jnp.sum(jnp.sin(logits)), sown


text = jax.jit(jax.grad(loss, has_aux=True)).lower(shapes).as_text()
print(hashlib.sha256(text.encode()).hexdigest())
"""


@pytest.mark.parametrize(
    "name, kwargs, sha256",
    [
        ("hybrid_lm_tiny", {}, "8542c23bbe2f1c5a13677dad4ec68269c58046c62239f397f03e75ccf868ad9d"),
        (
            "nemotron_h_tiny", {"share": (2, 4, 1), "layer_pattern": "EM*E"},
            "108fb53b3ac9c6b76c2657a1d70e71d39441aa23a42ecd5cbf938b234aa7c8db",
        ),
        ("sdar_moe_tiny", {"share": (2, 4, 1)}, "3c40ece12117a4a70de00e8bca69800331ff37a2ddf410d9c3faf3734d3552a6"),
        ("lfm2_moe_tiny", {"share": (2, 4, 1)}, "027a0c615d4c6093812f59ec5e5f5356973a8c31949241ad449f968d45f5d192"),
    ],
    ids=lambda v: v if isinstance(v, str) and len(v) < 64 else "",
)  # fmt: skip
def test_the_sparse_expert_hybrid_is_the_program_it_was(name, kwargs, sha256):
    """The four language models share their blocks (models/blocks.py) and
    their kernels (ops/moe.py, ops/flash.py, ops/ssd.py): the lowered text of
    each tiny model (forward, counters and every gradient), with every kind of
    layer it has and at a share that is not the whole model where it takes
    one, hashes to what commit 7802b81 gave, the commit before the blocks
    were moved into a file of their own and their copies merged. What the
    test holds from there on is that a change to a neighbour (another router,
    another expert, another model, a merged block) leaves these programs
    alone; a PR that moves one by design retakes its hash at its own commit
    and says so here, as PR 42 did for ops/moe.py's group sizes. The
    sparse-expert hybrid's case was ``EMEM`` until PR 43 and its hash PR
    42's: that hash went only because the case now runs ``EM*E``, the
    attention layer with the rest. In a process of its own, as the hash was
    taken: inside a worker of the whole suite, after other files' tests, the
    same lowering gave another text (which of them leaves what behind was not
    found)."""
    import json
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c", _PROGRAM, name, json.dumps(kwargs)], capture_output=True, text=True,
        check=True, cwd=pathlib.Path(__file__).resolve().parents[1],
    )  # fmt: skip
    assert out.stdout.split()[-1] == sha256
