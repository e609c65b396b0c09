"""The hybrid language model (models/granite.py) against its plain reference
(benchmarks/reference/granite.py, which shares no code with the package) on
seeded weights: each kind of block and the whole model, forward, loss and
gradients, with and without masks; what a layer's backward pass keeps and
what it rebuilds. Then what the rest of the system says of it: what is
prunable, the planner's answer, the server's refusal, the config's
cross-checks."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import granite as reference
from turboprune_tpu.config import compose
from turboprune_tpu.config.schema import ConfigError
from turboprune_tpu.models import LANGUAGE_MODELS, blocks, create_model, granite
from turboprune_tpu.ops import masking
from turboprune_tpu.train.steps import make_eval_step, make_train_step
from turboprune_tpu.utils import tracing

import remat_probe

VOCAB, T, BATCH = 50, 32, 2
TINY = [
    "model_params.model_name=hybrid_lm_tiny",
    "model_params.num_hidden_layers=0",
    "dataset_params.seq_len=64",
    "dataset_params.num_classes=96",
    "dataset_params.total_batch_size=2",
    "dataset_params.synthetic_num_train=8",
    "dataset_params.synthetic_num_test=3",
    "dataset_params.doc_len_mu=2.5",
    "dataset_params.doc_len_min=2",
    "experiment_params.num_devices=1",
]


def _tokens(seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, VOCAB, (BATCH, T))
    flags = np.zeros((BATCH, T), np.int32)
    flags[0, [5, 16, 17]] = 1  # starts inside a chunk, on its border, and the token after
    flags[1, [20]] = 1
    return jnp.asarray(np.stack([ids, np.cumsum(flags, axis=1)], axis=1), jnp.int32)


@pytest.fixture(scope="module")
def seeded():
    model = create_model("hybrid_lm_tiny", VOCAB)
    tokens = _tokens()
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    # Every leaf off its initial value, so that D, the biases and the norms count.
    keys = jax.random.split(jax.random.PRNGKey(1), len(jax.tree.leaves(params)))
    params = jax.tree.unflatten(
        jax.tree.structure(params),
        [p + 0.05 * jax.random.normal(k, p.shape) for p, k in zip(jax.tree.leaves(params), keys)],
    )
    masks = masking.make_masks(params)
    half = jax.tree.map(
        lambda m: jax.random.bernoulli(jax.random.PRNGKey(m.size), 0.5, m.shape), masks
    )
    return model, params, {"dense": masks, "half": half}, tokens, dataclasses.asdict(model.cfg)


def _close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want))) + 1e-12
    assert float(jnp.max(jnp.abs(got - want))) / scale < tol


@pytest.mark.parametrize("kind", ["mamba", "attention"])
@pytest.mark.parametrize("masked", ["dense", "half"])
def test_a_block_equals_the_reference(seeded, kind, masked):
    model, params, masks, tokens, spec = seeded
    name = "layers_1" if kind == "attention" else "layers_0"
    block = granite.HybridBlock(kind, model.cfg)
    p = masking.apply_masks(params[name], masks[masked][name])
    x = jax.random.normal(jax.random.PRNGKey(2), (BATCH, T, model.cfg.hidden_size))
    seg = tokens[:, 1]
    run = jax.jit(lambda p, x: block.apply({"params": p}, x, seg))
    ref = jax.jit(lambda p, x: reference.block(x, seg, p, spec, train=True))
    weigh = lambda fn: jax.grad(lambda p, x: jnp.sum(jnp.sin(fn(p, x))), argnums=(0, 1))
    with jax.default_matmul_precision("highest"):
        _close(run(p, x), ref(p, x), 1e-5)
        got, want = weigh(run)(p, x), weigh(ref)(p, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(g, w, 1e-4)


@pytest.mark.parametrize("masked", ["dense", "half"])
def test_the_model_equals_the_reference_forward_loss_and_gradients(seeded, masked):
    model, params, masks, tokens, spec = seeded
    ids, seg = tokens[:, 0], tokens[:, 1]
    targets = reference.next_token_targets(ids, seg)

    def ours(p):
        logits = model.apply({"params": masking.apply_masks(p, masks[masked])}, tokens)
        return reference.mean_loss(logits, targets), logits

    def theirs(p):
        logits = reference.forward(reference.masked(p, masks[masked]), spec, ids, seg, train=True)
        return reference.mean_loss(logits, targets), logits

    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.jit(jax.value_and_grad(ours, has_aux=True))(params)
        (ref_loss, ref_logits), ref_grads = jax.jit(jax.value_and_grad(theirs, has_aux=True))(params)
    assert logits.shape == (BATCH, T, VOCAB) and logits.dtype == jnp.float32
    _close(logits, ref_logits, 1e-5)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref_grads)):
        assert float(jnp.max(jnp.abs(w))) > 0, jax.tree_util.keystr(path)  # every leaf is in the graph
        _close(g, w, 1e-4)
    if masked == "half":  # a masked weight gets no data gradient
        for g, m in zip(masking.mask_leaves(masking.mask_where(masks["half"], lambda m, g: g, grads)),
                        masking.mask_leaves(masks["half"])):  # fmt: skip
            assert float(jnp.max(jnp.abs(jnp.where(m, 0.0, g)))) == 0.0


def test_the_steps_count_target_tokens(seeded):
    """The train and eval steps sum the loss over the valid targets, count
    them, and call a hit a right next token; both agree with the reference."""
    import optax

    from turboprune_tpu.train import create_train_state

    model, params, masks, tokens, spec = seeded
    targets = reference.next_token_targets(tokens[:, 0], tokens[:, 1])
    state = create_train_state(
        model, optax.sgd(0.1), jax.random.PRNGKey(0), tokens.shape, variables={"params": params}
    )
    with jax.default_matmul_precision("highest"):
        _, m = jax.jit(make_train_step(model, optax.sgd(0.1)))(state, (tokens, targets))
        e = jax.jit(make_eval_step(model))(state, (tokens, targets))
        logits = reference.forward(params, spec, tokens[:, 0], tokens[:, 1])
    valid = np.asarray(targets) >= 0
    hits = (np.asarray(jnp.argmax(logits, -1)) == np.asarray(targets)) & valid
    for got in (m, e):
        assert float(got["count"]) == valid.sum() == BATCH * T - 6  # six documents, each with a last token
        assert float(got["correct"]) == hits.sum()
        np.testing.assert_allclose(
            float(got["loss_sum"]), float(jnp.sum(reference.token_losses(logits, targets))), rtol=1e-5
        )


# ------------------------------------------------ what a backward pass keeps
# By kind of layer, the shapes of granite.SAVED's values at BATCH x T tokens
# of the tiny model: the mixer's result beside in_proj's output, or q, k, v.
KEPT = {
    "mamba": [(2, 32, 32), (2, 32, 148)],
    "attention": [(2, 32, 32), (8, 32, 8), (4, 32, 8), (4, 32, 8)],
}


def _weighed(model, tokens):
    return lambda p: jnp.sum(jnp.sin(model.apply({"params": p}, tokens)))


@pytest.mark.parametrize("case", ["unit_embedding", "published_multipliers", "op_by_op"])
def test_the_gradient_is_the_bare_checkpoints(seeded, case, monkeypatch):
    """A value computed once and kept is the value computed twice: under the
    model's policy every leaf's gradient is the one a bare ``nn.remat`` gives,
    bit for bit in float32 on the CPU. With the published
    ``embedding_multiplier`` of 12 that holds op by op, and compiled for every
    leaf but the first layer's and the embedding's: XLA fuses ``12 * E[ids]``
    into the first layer's ``x + 0.22 * y`` and contracts that sum of two
    products into a multiply-add one way where ``y`` is rebuilt and the other
    way where it is read; those leaves then differ in their last bits."""
    model, params, _, tokens, _ = seeded
    if case == "unit_embedding":
        cfg = dataclasses.replace(model.cfg, embedding_multiplier=1.0)
        model = granite.HybridLM(VOCAB, cfg, model.layer_types)
    grad = jax.grad(_weighed(model, tokens))

    def run():
        if case == "op_by_op":
            with jax.disable_jit():
                return grad(params)
        return jax.jit(grad)(params)

    kept = run()
    remat_probe.bare(monkeypatch)
    rebuilt = run()
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(kept), jax.tree.leaves(rebuilt)):
        name = jax.tree_util.keystr(path)
        if case == "published_multipliers" and ("layers_0" in name or "embedding" in name):
            _close(g, w, 1e-5)
        else:
            np.testing.assert_array_equal(g, w, err_msg=name)


def test_a_backward_pass_is_handed_the_models_names_and_no_mlp_tensor(seeded, monkeypatch):
    model, params, _, tokens, _ = seeded
    loss = _weighed(model, tokens)
    assert model.layer_types == ("mamba", "attention", "mamba")
    assert remat_probe.kept_shapes(loss, params) == sorted(KEPT["mamba"] * 2 + KEPT["attention"])
    # The MLP's gate and value [.., 2 * hidden] are rebuilt, as they always were.
    wide = 2 * model.cfg.shared_intermediate_size
    assert not [s for s, _ in remat_probe.residuals(loss, params) if s[-1:] == (wide,) and len(s) == 3]
    with monkeypatch.context() as only_here:
        only_here.setattr(granite, "SAVED", ("attn_k",))  # each model's own tuple decides
        assert remat_probe.kept_shapes(loss, params) == [(4, 32, 8)]
    remat_probe.bare(monkeypatch)
    assert remat_probe.kept_shapes(loss, params) == []


def test_the_gauges_say_what_one_trace_keeps(seeded):
    """``remat_saved_values`` / ``remat_saved_mib``: what the newest trace of
    a program that differentiates the layers keeps; the trace of one that
    does not, and a run of the compiled program, set nothing."""
    model, params, _, tokens, _ = seeded
    grad = jax.jit(jax.grad(_weighed(model, tokens)))
    tracing.gauge("remat_saved_values", -1)
    jax.jit(_weighed(model, tokens))(params)
    assert remat_probe.gauges()[0] == -1
    grad(params)
    shapes = KEPT["mamba"] * 2 + KEPT["attention"]
    assert remat_probe.gauges() == [8, sum(4 * int(np.prod(s)) for s in shapes) / 2**20]
    tracing.gauge("remat_saved_values", -1)
    grad(params)
    assert remat_probe.gauges()[0] == -1


@pytest.mark.parametrize("kind", ["mamba", "attention"])
def test_a_tag_no_policy_names_is_inert(seeded, kind, monkeypatch):
    """Under a bare ``jax.checkpoint`` a mixer (the Mamba one at one group: the
    program this model has always run) gives the outputs and gradients it
    gives with no tag in it, and its backward pass is handed the layer's
    arguments and nothing tagged."""
    model, params, _, tokens, _ = seeded
    c, seg = model.cfg, tokens[:, 1]
    if kind == "mamba":
        mixer = blocks.MambaMixer(
            c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state, c.mamba_d_conv, c.mamba_chunk_size, c.rms_norm_eps
        )
        p, tags = params["layers_0"]["mixer"], 1
    else:
        mixer = blocks.AttentionMixer(
            c.num_attention_heads, c.num_key_value_heads, c.attention_head_dim, c.attention_multiplier
        )
        p, tags = params["layers_1"]["mixer"], 3
    u = jax.random.normal(jax.random.PRNGKey(3), (BATCH, T, c.hidden_size))

    def probe():  # fresh functions: a traced one is not traced again
        layer = lambda p, u: jax.checkpoint(lambda p, u: mixer.apply({"params": p}, u, seg))(p, u)
        weigh = lambda p, u: jnp.sum(jnp.sin(layer(p, u)))
        values = jax.jit(layer)(p, u), jax.jit(jax.grad(weigh, argnums=(0, 1)))(p, u)
        return values, remat_probe.primitives(weigh, p, u)["name"], remat_probe.residuals(weigh, p, u)

    tagged, names, handed = probe()
    # Arguments, a constant (``seg``) and this test's own cosine: nothing the layer computed.
    assert handed and not [why for _, why in handed if "turboprune_tpu" in why or "named '" in why]
    monkeypatch.setattr(blocks, "checkpoint_name", lambda x, name: x)
    plain, no_names, _ = probe()
    assert (names, no_names) == (tags, 0)
    for got, want in zip(jax.tree.leaves(tagged), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(got, want)


def test_the_published_cut_keeps_22_values_of_1565_mib():
    """ISSUE 37's count, from shapes alone: the mixer's result of ten layers,
    q, k and v of one, ``in_proj``'s output of nine, at 8,192 tokens in bf16."""
    model = create_model("granite_4_0_h_micro", 12544, num_layers=10, compute_dtype=jnp.bfloat16)
    tokens = jnp.zeros((1, 2, 8192), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)["params"]
    jax.eval_shape(jax.grad(_weighed(model, tokens)), params)
    gauges = tracing.gauges()
    assert gauges["remat_saved_values"] == 10 + 3 + 9
    assert gauges["remat_saved_mib"] == (10 * 2048 + (2048 + 512 + 512) + 9 * 8512) * 8192 * 2 / 2**20 == 1565.0


def test_what_is_prunable(seeded):
    _, params, masks, _, _ = seeded
    names = {masking.path_name(p) for p, _ in masking.mask_leaves_with_path(masks["dense"])}
    assert {n.split("/", 1)[1] for n in names} == {
        "mixer/in_proj/kernel", "mixer/out_proj/kernel", "mixer/q_proj/kernel", "mixer/k_proj/kernel",
        "mixer/v_proj/kernel", "mixer/o_proj/kernel", "mlp/in_proj/kernel", "mlp/out_proj/kernel",
    }  # fmt: skip
    assert all(m.ndim == 2 for m in masking.mask_leaves(masks["dense"]))
    left = {masking.path_name(p).rsplit("/", 1)[-1] for p in masking.tree_paths(params)} - {"kernel"}
    assert left == {"embedding", "conv_taps", "conv_bias", "A_log", "D", "dt_bias", "scale"}


def test_the_published_model_has_the_published_shapes():
    """granite-4.0-h-micro at one period and an eighth of the vocabulary:
    shapes only, nothing is allocated."""
    model = create_model("granite_4_0_h_micro", 12544, num_layers=10)
    assert model.layer_types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert create_model("granite_4_0_h_micro", 100352).layer_types.count("attention") == 4
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 2, 256), jnp.int32))["params"]
    size = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    prunable = masking.num_prunable(jax.eval_shape(masking.make_masks, shapes))
    assert shapes["layers_0"]["mixer"]["in_proj"]["kernel"].shape == (2048, 8512)
    assert shapes["layers_5"]["mixer"]["k_proj"]["kernel"].shape == (2048, 512)
    assert shapes["layers_0"]["mlp"]["in_proj"]["kernel"].shape == (2048, 16384)
    mamba, attention, mlp = 2048 * 8512 + 4096 * 2048, 2 * 2048 * 2048 + 2 * 2048 * 512, 3 * 2048 * 8192
    assert prunable == 9 * mamba + attention + 10 * mlp
    # ISSUE 32's 746.5 M counts the layers whole; 0.3 M of that (convolutions,
    # A, D, dt_bias, norms) is not prunable.
    assert round(prunable / 1e6, 1) == 746.2 and round(size(shapes) / 1e6, 1) == 772.2
    assert round((size(shapes) - 12544 * 2048) / 1e6, 1) == 746.5


def test_the_planner_answers_masked(seeded):
    from turboprune_tpu.sparse import CompactionError, build_graph, plan_execution

    model, params, masks, _, _ = seeded
    with pytest.raises(CompactionError, match="runs masked"):
        build_graph(model, params)
    plan = plan_execution(model, params, masks["half"], compact="auto", nm="auto")
    assert plan.kind == "masked" and plan.plan_signature() == ("masked",)
    assert plan.report["backend_counts"]["nm_layers"] == 0


def test_the_server_refuses_a_language_model_in_one_line(tmp_path, capsys):
    import run_server
    from turboprune_tpu.utils import save_config

    cfg = compose("granite_h_micro_imp", TINY)
    save_config(str(tmp_path), cfg)
    assert run_server.main(["--expt-dir", str(tmp_path)]) == 2
    err = capsys.readouterr().err.strip()
    assert len(err.splitlines()) == 1 and "language model" in err and "does not serve" in err


def test_the_config_keeps_models_and_datasets_together():
    cfg = compose("granite_h_micro_imp", [])
    assert (cfg.model_params.model_name, cfg.model_params.num_hidden_layers) == ("granite_4_0_h_micro", 10)
    assert cfg.dataset_params.input_spec() == ((1, 2, 512), "int32")
    assert compose("cifar10_imp", []).dataset_params.input_spec() == ((1, 32, 32, 3), "float32")
    assert {"granite_4_0_h_micro", "hybrid_lm_tiny"} <= set(LANGUAGE_MODELS)
    for bad in (
        ["model_params.model_name=resnet18"],
        ["dataset_params.dataset_name=CIFAR10"],
        ["dataset_params.seq_len=0"],
        ["dataset_params.dataloader_type=device"],
        ["model_params.attention_impl=flash"],
    ):
        with pytest.raises(ConfigError):
            compose("granite_h_micro_imp", bad)
    with pytest.raises(ConfigError, match="num_hidden_layers"):
        compose("cifar10_imp", ["model_params.num_hidden_layers=2"])
    with pytest.raises(ValueError, match="runs masked"):
        create_model("hybrid_lm_tiny", 10, width_overrides={"a": 1})
