"""The sparse-expert hybrid (models/nemotron_h.py, ops/moe.py) against its
plain reference (benchmarks/reference/nemotron_h.py, which shares no code with
the package) on seeded weights: the whole model and a chip's share of it,
forward, loss and gradients; the shares of every kind of layer adding up to
the uncut layer; a routing that overflows the pair buffer; the scan and the
norm at one group being what they were; stacked kernels through everything
that prunes; the router never masked; what a layer's backward pass keeps and
what it rebuilds. Then what the rest of the system says of it: the planner's
answer, the config's cross-checks."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron_h as reference
from turboprune_tpu.config import compose
from turboprune_tpu.config.schema import ConfigError
from turboprune_tpu.models import LANGUAGE_MODELS, blocks, create_model, nemotron_h
from turboprune_tpu.ops import masking, moe
from turboprune_tpu.ops.ssd import ssd_chunked
from turboprune_tpu.pruning import criteria
from turboprune_tpu.train.steps import make_eval_step, make_train_step
from turboprune_tpu.utils import tracing

import remat_probe

VOCAB, T, BATCH = 50, 32, 2
# The tiny preset's entry overrides (tests/test_nemotron_ladder.py runs them).
TINY = [
    "model_params.model_name=nemotron_h_tiny",
    "model_params.layer_pattern=EM*",
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
SHARES = {"whole": (), "share": (2, 4, 1)}


def _tokens(seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, VOCAB, (BATCH, T))
    flags = np.zeros((BATCH, T), np.int32)
    flags[0, [5, 16, 17]] = 1  # starts inside a chunk, on its border, and the token after
    flags[1, [20]] = 1
    return jnp.asarray(np.stack([ids, np.cumsum(flags, axis=1)], axis=1), jnp.int32)


def _spec(model) -> dict:
    """What the reference is told: the published keys, the counts as held."""
    held = model.share.of(model.cfg)
    return dict(
        dataclasses.asdict(model.cfg), num_attention_heads=held["query_heads"],
        num_key_value_heads=held["kv_heads"], n_groups=held["mamba_groups"],
        expert_offset=held["expert_offset"],
    )  # fmt: skip


def _seeded(share):
    model = create_model("nemotron_h_tiny", VOCAB, share=share)
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
    return model, params, {"dense": masks, "half": half}, tokens, _spec(model)


@pytest.fixture(scope="module", params=list(SHARES))
def seeded(request):
    return _seeded(SHARES[request.param])


@pytest.fixture(scope="module")
def whole():
    return _seeded(())


def _close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want))) + 1e-12
    assert float(jnp.max(jnp.abs(got - want))) / scale < tol


# ------------------------------------------------ (a) against the reference
@pytest.mark.parametrize("masked", ["dense", "half"])
def test_the_model_equals_the_reference_forward_loss_and_gradients(seeded, masked):
    model, params, masks, tokens, spec = seeded
    ids, seg = tokens[:, 0], tokens[:, 1]
    targets = reference.next_token_targets(ids, seg)

    def ours(p):
        logits = model.apply({"params": masking.apply_masks(p, masks[masked])}, tokens)
        return reference.mean_loss(logits, targets), logits

    def theirs(p):
        logits = reference.forward(p, spec, ids, seg, train=True, masks=masks[masked])
        return reference.mean_loss(logits, targets), logits

    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.jit(jax.value_and_grad(ours, has_aux=True))(params)
        (ref_loss, ref_logits), ref_grads = jax.jit(jax.value_and_grad(theirs, has_aux=True))(params)
    assert logits.shape == (BATCH, T, VOCAB) and logits.dtype == jnp.float32
    _close(logits, ref_logits, 1e-5)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref_grads)):
        name = masking.path_name(path)
        if name.endswith("router/bias"):  # the selection bias: no gradient reaches it
            assert float(jnp.max(jnp.abs(g))) == float(jnp.max(jnp.abs(w))) == 0.0
            continue
        assert float(jnp.max(jnp.abs(w))) > 0, name  # every other leaf is in the graph
        _close(g, w, 1e-4)
    if masked == "half":  # a masked weight gets no data gradient
        for g, m in zip(masking.mask_leaves(masking.mask_where(masks["half"], lambda m, g: g, grads)),
                        masking.mask_leaves(masks["half"])):  # fmt: skip
            assert float(jnp.max(jnp.abs(jnp.where(m, 0.0, g)))) == 0.0


def test_the_steps_carry_the_layers_counters(seeded):
    """The train step's metrics hold the counters, summed over the layers
    (one ``E`` layer here) and by ``scan_chunk`` over the steps; the eval step
    and a model without counters return what they always did."""
    import optax

    from turboprune_tpu.train import create_train_state
    from turboprune_tpu.train.steps import make_scan_chunk

    model, params, masks, tokens, spec = seeded
    targets = reference.next_token_targets(tokens[:, 0], tokens[:, 1])
    state = create_train_state(
        model, optax.sgd(0.1), jax.random.PRNGKey(0), tokens.shape, variables={"params": params}
    )
    step = make_train_step(model, optax.sgd(0.1))
    with jax.default_matmul_precision("highest"):
        _, m = jax.jit(step)(state, (tokens, targets))
        _, sums = jax.jit(make_scan_chunk(step))(state, (jnp.stack([tokens] * 3), jnp.stack([targets] * 3)))
        e = jax.jit(make_eval_step(model))(state, (tokens, targets))
        top = reference.routing(params["embedding"][tokens[:, 0]], params["layers_0"], spec)
    offset, held = spec["expert_offset"], model.share.of(model.cfg)["experts_here"]
    here = np.asarray((top >= offset) & (top < offset + held))
    loads = np.bincount(np.asarray(top)[here] - offset, minlength=held)
    assert set(m) == {"loss_sum", "correct", "count", *moe.COUNTERS}
    assert int(m["moe_pairs"]) == here.sum() and int(m["moe_load_max"]) == loads.max()
    assert int(m["moe_dropped_pairs"]) == 0 and m["moe_pairs"].dtype == jnp.int32
    assert int(sums["moe_pairs"]) >= int(m["moe_pairs"])  # three steps, the later ones after an update
    assert set(e) == {"loss_sum", "correct", "count"}
    plain = create_model("hybrid_lm_tiny", VOCAB)
    assert not hasattr(plain, "counters") and model.counters == moe.COUNTERS


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


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(whole):
    """The routed parts of all four expert-parallel shares, and the shared
    expert's two tensor-parallel halves counted once, are the reference's
    layer with every expert and every column."""
    model, params, _, tokens, spec = whole
    c, p = model.cfg, params["layers_0"]["mixer"]
    h = jax.random.normal(jax.random.PRNGKey(3), (BATCH, T, c.hidden_size))
    zero = lambda tree, name: _slice(tree, **{name: (0, jnp.zeros((0,), jnp.int32))})
    with jax.default_matmul_precision("highest"):
        want = reference.latent_moe(h, p, spec)
        total = jnp.zeros_like(want)
        for rank in range(4):  # the routed experts, four to a chip; no shared expert
            experts = jnp.arange(4 * rank, 4 * rank + 4)
            part = _slice(
                p, **{"experts/kernel_up": (0, experts), "experts/kernel_down": (0, experts),
                      "shared_up/kernel": (1, jnp.zeros((0,), jnp.int32)),
                      "shared_down/kernel": (0, jnp.zeros((0,), jnp.int32))},
            )  # fmt: skip
            layer = nemotron_h.LatentMoE(c, 4, 4 * rank, 0)
            out, sown = layer.apply({"params": part}, h, mutable=["counters"])
            assert int(sown["counters"]["moe_dropped_pairs"][0]) == 0
            total += out
        for rank in range(2):  # the shared expert's columns, half to a chip; no routed expert
            cols = jnp.arange(48 * rank, 48 * rank + 48)
            part = _slice(p, **{"shared_up/kernel": (1, cols), "shared_down/kernel": (0, cols)})
            part["experts"] = jax.tree.map(jnp.zeros_like, part["experts"])
            total += nemotron_h.LatentMoE(c, 16, 0, 48).apply({"params": part}, h)
    _close(total, want, 1e-5)


def test_the_shares_of_a_scan_layer_add_up_to_the_uncut_layer(whole):
    """Two groups of two heads, a group to a chip: each chip's ``in_proj``
    columns, convolution channels, norm run and ``out_proj`` rows."""
    model, params, _, tokens, spec = whole
    c, p = model.cfg, params["layers_1"]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(4), (BATCH, T, c.hidden_size))
    seg = tokens[:, 1]
    heads, hd, n, groups = c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size, c.n_groups
    inner = heads * hd
    with jax.default_matmul_precision("highest"):
        want = reference.mamba_mixer(u, seg, p, spec)
        total = jnp.zeros_like(want)
        for g in range(groups):
            per = heads // groups
            chan = jnp.arange(g * per * hd, (g + 1) * per * hd)  # of the inner channels
            state = jnp.arange(g * n, (g + 1) * n)
            head = jnp.arange(g * per, (g + 1) * per)
            conv = jnp.concatenate([chan, inner + state, inner + groups * n + state])
            cols = jnp.concatenate([chan, inner + conv, 2 * inner + 2 * groups * n + head])
            part = _slice(
                p, **{"in_proj/kernel": (1, cols), "conv_taps": (1, conv), "conv_bias": (0, conv),
                      "dt_bias": (0, head), "A_log": (0, head), "D": (0, head),
                      "gate_norm/scale": (0, chan), "out_proj/kernel": (0, chan)},
            )  # fmt: skip
            mixer = blocks.MambaMixer(per, hd, n, c.conv_kernel, c.chunk_size, c.layer_norm_epsilon)
            total += mixer.apply({"params": part}, u, seg)
    _close(total, want, 1e-5)


def test_the_shares_of_an_attention_layer_add_up_to_the_uncut_layer(whole):
    model, params, _, tokens, spec = whole
    c, p = model.cfg, params["layers_2"]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(5), (BATCH, T, c.hidden_size))
    seg, d = tokens[:, 1], c.head_dim
    with jax.default_matmul_precision("highest"):
        want = reference.block(u, seg, {"norm": {"scale": jnp.ones(c.hidden_size)}, "mixer": p}, spec) - u
        total = jnp.zeros_like(u)
        norm = blocks.RMSNorm(c.layer_norm_epsilon)
        h = norm.apply({"params": {"scale": jnp.ones(c.hidden_size)}}, u)
        for kv in range(c.num_key_value_heads):  # a key/value head and its two query heads to a chip
            q = jnp.arange(2 * kv * d, 2 * (kv + 1) * d)
            k = jnp.arange(kv * d, (kv + 1) * d)
            part = _slice(
                p, **{"q_proj/kernel": (1, q), "k_proj/kernel": (1, k), "v_proj/kernel": (1, k),
                      "o_proj/kernel": (0, q)},
            )  # fmt: skip
            total += blocks.AttentionMixer(2, 1, d, d**-0.5).apply({"params": part}, h, seg)
    _close(total, want, 1e-5)


# ---------------------------------------------- (c) no pair is ever dropped
@pytest.mark.parametrize("favoured", [5, None], ids=["one_expert", "every_held_expert"])
def test_no_pair_is_dropped_when_the_routing_overflows_the_buffer(favoured):
    """A selection bias that sends every token to one held expert (or to all
    four): the pairs outgrow the buffer, the rounds run, the counters say so,
    and the output and the gradients are still the reference's."""
    model, params, _, tokens, spec = _seeded((1, 4, 1))  # experts 4-7 of 16
    bias = jnp.zeros(16).at[jnp.arange(4, 8) if favoured is None else favoured].set(10.0)
    params["layers_0"]["mixer"]["router"]["bias"] = bias
    ids, seg = tokens[:, 0], tokens[:, 1]
    capacity = moe.pair_capacity(BATCH * T, 4, 16, 4)
    assert capacity == 128 < BATCH * T * 4

    def ours(p):
        logits, sown = model.apply({"params": p}, tokens, mutable=["counters"])
        return jnp.sum(jnp.sin(logits)), (logits, sown["counters"]["layers_0"]["mixer"])

    theirs = lambda p: jnp.sum(jnp.sin(reference.forward(p, spec, ids, seg, train=True)))
    with jax.default_matmul_precision("highest"):
        (_, (logits, counted)), grads = jax.jit(jax.value_and_grad(ours, has_aux=True))(params)
        ref_logits = jax.jit(lambda p: reference.forward(p, spec, ids, seg))(params)
        ref_grads = jax.jit(jax.grad(theirs))(params)
    pairs, dropped, fullest, run = (int(counted[k][0]) for k in moe.COUNTERS)
    assert dropped == 0 and fullest == BATCH * T  # every token at the favoured expert
    assert pairs <= run <= pairs + 4 * 7 and run % 8 == 0  # the four held experts' rows on whole tiles of 8, no slack
    # One expert's 64 pairs and the others' few fit the buffer; four times 64 do not.
    assert (pairs > capacity) == (favoured is None) and (favoured is not None or pairs == BATCH * T * 4)
    _close(logits, ref_logits, 1e-5)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        _close(g, w, 1e-4)


def test_uniform_ids_spread_a_sequence_over_the_vocabulary():
    """What keeps the held experts' load the same under every seed: no token
    is 7 % of a sequence, as the log-uniform default's commonest is."""
    from turboprune_tpu.data.tokens import token_ids

    skewed, even = (token_ids((6, 8192), 16384, 3, skew) for skew in ("log_uniform", "uniform"))
    assert compose("granite_h_micro_imp", []).dataset_params.token_skew == "log_uniform"
    np.testing.assert_array_equal(skewed, token_ids((6, 8192), 16384, 3))
    assert np.bincount(skewed.ravel()).max() > 0.06 * skewed.size
    assert np.bincount(even.ravel()).max() < 20 and even.min() >= 0 and even.max() == 16383


def test_the_capacity_is_the_configurations_alone():
    # The cell: 8,192 tokens, top-22 of 512, 16 held: the 5,632 expected pairs, half as
    # many again, and a tile of 128 rows for each expert's last.
    assert moe.pair_tile(8192, 22, 512) == 128
    assert moe.pair_capacity(8192, 22, 512, 16) == 5632 * 3 // 2 + 16 * 128 == 10496
    # Never more than the worst case: every pair there can be, and each expert's last
    # tile (the small one, where an expert expects few rows) all but empty.
    assert moe.pair_tile(64, 4, 16) == 8 and moe.pair_capacity(64, 4, 16, 16) == 64 * 4 + 16 * 7
    assert moe.pair_capacity(8, 1, 512, 1) == 16


def _plain_experts(z, top, weights, up, down, offset):
    out = jnp.zeros(z.shape, jnp.float32)
    for e in range(up.shape[0]):
        w = jnp.sum(jnp.where(top == e + offset, weights, 0), axis=-1)
        out += w[:, None] * (jnp.square(jax.nn.relu(z @ up[e])) @ down[e])
    return out


@pytest.mark.parametrize(
    "capacity, tile, favoured, n, latent",
    [
        (128, 8, None, 64, 32), (16, 8, None, 64, 32), (64, 16, 5, 64, 32), (128, 128, None, 64, 32),
        (640, 128, None, 128, 1024), (256, 128, None, 128, 1024),
    ],
    ids=[
        "one_round", "many_rounds", "many_rounds_one_full_expert", "tile_of_128",
        "row_kernels", "row_kernels_many_rounds",
    ],
)  # fmt: skip
def test_the_routed_experts_are_a_plain_loop_over_the_experts_held(capacity, tile, favoured, n, latent):
    """ops/moe.py alone, experts 4-7 of 16, whatever the buffer's size and
    however many rounds the pairs take: result, gradients and counters. At
    rows of 1,024 float32 values and whole tiles of tokens the rows travel
    through the two Pallas row kernels (interpreted here), in every round."""
    k, experts, held, offset, width = 4, 16, 4, 4, 48
    forms = lambda: [tracing.gauges().get(f"moe_row_{form}_calls", 0) for form in ("kernel", "xla")]
    before = forms()
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 4)
    z, logits = jax.random.normal(k0, (n, latent)), jax.random.normal(k1, (n, experts))
    up = 0.2 * jax.random.normal(k2, (held, latent, width))
    down = 0.2 * jax.random.normal(k3, (held, width, latent))
    bias = jnp.zeros(experts) if favoured is None else jnp.zeros(experts).at[favoured].set(10.0)

    def ours(z, logits, up, down):
        top, w = moe.route(logits, bias, k, 5.0)
        out, counted = moe.routed_experts(z, top, w, (up, down), offset, capacity, tile)
        return jnp.sum(jnp.sin(out)), (out, counted, top)

    def theirs(z, logits, up, down):
        top, w = moe.route(logits, bias, k, 5.0)
        out = _plain_experts(z, top, w, up, down, offset)
        return jnp.sum(jnp.sin(out)), out

    every = (0, 1, 2, 3)
    with jax.default_matmul_precision("highest"):
        (_, (out, counted, top)), grads = jax.jit(jax.value_and_grad(ours, every, has_aux=True))(z, logits, up, down)
        (_, want), ref_grads = jax.jit(jax.value_and_grad(theirs, every, has_aux=True))(z, logits, up, down)
    load = np.bincount(np.asarray(top).ravel(), minlength=experts)[offset : offset + held]
    assert {name: int(v) for name, v in counted.items()} == {
        "moe_pairs": load.sum(), "moe_dropped_pairs": 0, "moe_load_max": load.max(),
        "moe_rows_run": (-(-load // tile) * tile).sum(),  # each expert's rows on whole tiles, no more
    }  # fmt: skip
    assert favoured is None or load.max() == n
    kernel_calls, xla_calls = (after - was for after, was in zip(forms(), before))
    assert (xla_calls == 0 and kernel_calls >= 2) if latent == 1024 else kernel_calls == 0
    _close(out, want, 1e-5)
    for g, w in zip(grads, ref_grads):
        _close(g, w, 1e-4)


# ------------------------------------- (d) one group is what there was before
def test_the_scan_at_one_group_is_the_ungrouped_scan_bit_for_bit():
    k0, k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(0), 5)
    b, t, h, p, n = 2, 40, 4, 8, 8
    x = jax.random.normal(k0, (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(k1, (b, t, h)))
    a = -jnp.exp(jax.random.normal(k2, (h,)))
    bb, cc = jax.random.normal(k3, (b, t, 2, n)), jax.random.normal(k4, (b, t, 2, n))
    seg = jnp.asarray(np.cumsum(np.random.default_rng(0).random((b, t)) < 0.1, axis=1), jnp.int32)
    run = jax.jit(ssd_chunked, static_argnums=6)
    one = run(x, dt, a, bb[:, :, 0], cc[:, :, 0], seg, 16)
    np.testing.assert_array_equal(one, run(x, dt, a, bb[:, :, :1], cc[:, :, :1], seg, 16))
    # Two groups are two scans of two heads each.
    two = run(x, dt, a, bb, cc, seg, 16)
    for g in range(2):
        s = slice(2 * g, 2 * g + 2)
        alone = run(x[:, :, s], dt[:, :, s], a[s], bb[:, :, g], cc[:, :, g], seg, 16)
        np.testing.assert_allclose(two[:, :, s], alone, rtol=1e-6, atol=1e-6)


def test_granites_mixer_traces_to_the_program_it_was():
    """At one group and the default ``out_std`` neither the grouped norm nor
    the grouped scan adds an operation: no reshape of the norm's input, no
    fourth axis on B and C."""
    mixer = blocks.MambaMixer(4, 16, 8, 4, 16, 1e-5)
    u, seg = jnp.zeros((1, 32, 32)), jnp.zeros((1, 32), jnp.int32)
    params = jax.eval_shape(mixer.init, jax.random.PRNGKey(0), u, seg)["params"]
    assert params["in_proj"]["kernel"].shape == (32, 2 * 64 + 2 * 8 + 4)
    norm = jax.make_jaxpr(lambda x: blocks.RMSNorm(1e-5).apply({"params": {"scale": jnp.ones(64)}}, x))
    assert "reshape" not in str(norm(jnp.zeros((1, 32, 64))))
    grouped = jax.make_jaxpr(lambda x: blocks.RMSNorm(1e-5, groups=2).apply({"params": {"scale": jnp.ones(64)}}, x))
    assert "reshape" in str(grouped(jnp.zeros((1, 32, 64))))
    two = blocks.MambaMixer(4, 16, 8, 4, 16, 1e-5, n_groups=2)
    assert jax.eval_shape(two.init, jax.random.PRNGKey(0), u, seg)["params"]["in_proj"]["kernel"].shape == (32, 2 * 64 + 4 * 8 + 4)


# ------------------------------------------- (g) what a backward pass keeps
# By kind of layer, the shapes of nemotron_h.SAVED's values at BATCH x T = 64
# tokens of the tiny model: the router's logits, the experts chosen, the
# pairs' order, latent_down's and shared_up's outputs; in_proj's output; q, k, v.
KEPT = {
    "E": [(64, 16), (64, 4), (256,), (2, 32, 32), (2, 32, 96)],
    "M": [(2, 32, 164)],
    "*": [(8, 32, 8), (4, 32, 8), (4, 32, 8)],
}


def _kept_bytes(pattern):  # float32 and int32 alike
    return sum(4 * int(np.prod(shape)) for kind in pattern for shape in KEPT[kind])


def _weighed(pattern, dtype=jnp.float32):
    model = create_model("nemotron_h_tiny", VOCAB, layer_pattern=pattern, compute_dtype=dtype)
    tokens = _tokens()
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    return params, lambda p: jnp.sum(jnp.sin(model.apply({"params": p}, tokens)))


@pytest.mark.parametrize("pattern", ["E", "M", "*", "EM*"])
def test_the_gradient_is_the_bare_checkpoints_bit_for_bit(pattern, monkeypatch):
    """A value computed once and kept is the value computed twice: under the
    model's policy every leaf's gradient is the one a bare ``nn.remat`` gives,
    bit for bit in float32 on the CPU."""
    params, loss = _weighed(pattern)
    kept = jax.jit(jax.grad(loss))(params)
    remat_probe.bare(monkeypatch)
    rebuilt = jax.jit(jax.grad(loss))(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(kept), jax.tree.leaves(rebuilt)):
        np.testing.assert_array_equal(g, w, err_msg=jax.tree_util.keystr(path))


def test_an_expert_layers_backward_pass_rebuilds_less(monkeypatch):
    """One ``top_k``, one sort and one float32 product at ``HIGHEST`` fewer
    than under the bare checkpoint, and ``latent_down``'s and ``shared_up``'s
    products with them; the two grouped products keep their rebuilt forward
    (the kernels are interpreted here: one ``pallas_call`` each either way)."""
    params, loss = _weighed("E")
    kept = remat_probe.primitives(jax.grad(loss), params)
    remat_probe.bare(monkeypatch)
    rebuilt = remat_probe.primitives(jax.grad(loss), params)
    fewer = {name: rebuilt[name] - kept[name] for name in rebuilt if rebuilt[name] != kept[name]}
    assert (kept["top_k"], kept["sort"], kept["dot_general_highest"]) == (1, 1, 3)
    assert (rebuilt["top_k"], rebuilt["sort"], rebuilt["dot_general_highest"]) == (2, 2, 4)
    assert fewer["dot_general"] == 3 and fewer["name"] == len(KEPT["E"])
    assert kept["pallas_call"] == rebuilt["pallas_call"] > 0


@pytest.mark.parametrize("kind", ["E", "M", "*"])
def test_a_backward_pass_is_handed_the_models_names_and_nothing_else_tagged(kind, monkeypatch):
    params, loss = _weighed(kind)
    assert remat_probe.kept_shapes(loss, params) == sorted(KEPT[kind])
    with monkeypatch.context() as only_here:
        only_here.setattr(nemotron_h, "SAVED", nemotron_h.SAVED[:1])  # each model's own tuple decides
        assert remat_probe.kept_shapes(loss, params) == ([KEPT["E"][0]] if kind == "E" else [])
    remat_probe.bare(monkeypatch)
    assert remat_probe.kept_shapes(loss, params) == []


@pytest.mark.parametrize("pattern", ["E", "M", "*", "EM*"])
def test_the_gauges_say_what_one_trace_keeps(pattern):
    """``remat_saved_values`` / ``remat_saved_mib``: what the newest trace of
    a program that differentiates the layers keeps; the trace of one that
    does not, and a run of the compiled program, set nothing."""
    params, loss = _weighed(pattern)
    grad = jax.jit(jax.grad(loss))
    tracing.gauge("remat_saved_values", -1)
    jax.jit(loss)(params)
    assert remat_probe.gauges()[0] == -1
    grad(params)
    assert remat_probe.gauges() == [sum(len(KEPT[kind]) for kind in pattern), _kept_bytes(pattern) / 2**20]
    tracing.gauge("remat_saved_values", -1)
    grad(params)
    assert remat_probe.gauges()[0] == -1


def test_the_published_share_keeps_33_values_of_413_mib():
    """ISSUE 37's count, from shapes alone: five ``E`` layers of five values,
    five ``M`` of one, three of the ``*`` layer, at 8,192 tokens in bf16."""
    model = create_model(
        "nemotron_3_super_120b_a12b", 16384, layer_pattern="EMEMEMEMEM*", share=(8, 32, 0),
        compute_dtype=jnp.bfloat16,
    )  # fmt: skip
    tokens = jnp.zeros((1, 2, 8192), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)["params"]
    jax.eval_shape(jax.grad(lambda p: jnp.sum(model.apply({"params": p}, tokens))), params)
    e = 8192 * (512 * 4 + 22 * 4 + 22 * 4 + 1024 * 2 + 672 * 2)
    m, a = 8192 * 2320 * 2, 8192 * (512 + 128 + 128) * 2
    gauges = tracing.gauges()
    assert gauges["remat_saved_values"] == 5 * 5 + 5 + 3
    assert gauges["remat_saved_mib"] == (5 * e + 5 * m + a) / 2**20 and gauges["remat_saved_mib"] == 412.625


# ------------------------------------ (e), (f) stacked kernels and the router
def test_what_is_prunable_and_the_router_is_not(whole):
    _, params, masks, _, _ = whole
    names = {masking.path_name(p) for p, _ in masking.mask_leaves_with_path(masks["dense"])}
    assert names == {
        "layers_0/mixer/experts/kernel_up", "layers_0/mixer/experts/kernel_down",
        "layers_0/mixer/latent_down/kernel", "layers_0/mixer/latent_up/kernel",
        "layers_0/mixer/shared_up/kernel", "layers_0/mixer/shared_down/kernel",
        "layers_1/mixer/in_proj/kernel", "layers_1/mixer/out_proj/kernel",
        "layers_2/mixer/q_proj/kernel", "layers_2/mixer/k_proj/kernel",
        "layers_2/mixer/v_proj/kernel", "layers_2/mixer/o_proj/kernel", "lm_head/kernel",
    }  # fmt: skip
    router = masks["dense"]["layers_0"]["mixer"]["router"]
    assert router == {"weight": None, "bias": None} and masks["dense"]["embedding"] is None
    assert params["layers_0"]["mixer"]["router"]["weight"].ndim == 2  # a matrix, and not a kernel
    assert masks["dense"]["layers_0"]["mixer"]["experts"]["kernel_up"].shape == (16, 32, 48)
    assert len(masking.mask_layers(masks["dense"])) == 2 * 16 + 11
    assert masking.mask_layers(masks["dense"])[0] == ("layers_0/mixer/experts/kernel_down[0]", (48, 32), 48 * 32)


@pytest.mark.parametrize("method", ["mag", "random_erk", "random_balanced", "er_erk", "er_balanced"])
def test_every_criterion_prunes_stacked_kernels_per_expert_and_leaves_the_router(whole, method):
    from turboprune_tpu.pruning import prune_the_model

    model, params, masks, _, _ = whole
    density = 0.5
    new = prune_the_model(method, model, {"params": params}, masks["dense"], density, jax.random.PRNGKey(7))
    assert jax.tree.structure(new, is_leaf=lambda x: x is None) == jax.tree.structure(
        masks["dense"], is_leaf=lambda x: x is None
    )
    assert new["layers_0"]["mixer"]["router"] == {"weight": None, "bias": None}
    assert new["layers_0"]["mixer"]["experts"]["kernel_up"].shape == (16, 32, 48)
    total = masking.count_masks(new)
    slack = 0.05 if method.startswith("er_") else 1e-3  # Bernoulli draws
    assert abs(total.density - density) < slack
    table = masking.layerwise_sparsity(new)
    experts = [table[f"layers_0/mixer/experts/kernel_up[{e}]"] for e in range(16)]
    kept = masking.kept_counts(new)
    layers = masking.mask_layers(new)
    assert len(kept) == len(layers) == len(table) == 43 and sum(kept) == total.total - total.zeros
    stacked = np.asarray(new["layers_0"]["mixer"]["experts"]["kernel_down"])
    assert kept[:16] == [int(stacked[e].sum()) for e in range(16)]
    if method == "mag":  # one global threshold: the experts differ
        assert len({round(x, 6) for x in experts}) > 1
    elif method.startswith("random_"):  # per expert, each to its own exact budget
        want = (criteria.erk_densities if "erk" in method else criteria.balanced_densities)(masks["dense"], density)
        for e in range(16):
            name = f"layers_0/mixer/experts/kernel_up[{e}]"
            assert abs((1 - experts[e] / 100.0) - want[name]) <= 1.0 / (32 * 48)


def test_the_allocators_see_sixteen_kernels_and_not_one(whole):
    _, _, masks, _, _ = whole
    erk = criteria.erk_densities(masks["dense"], 0.3)
    balanced = criteria.balanced_densities(masks["dense"], 0.3)
    assert len(erk) == len(balanced) == 43
    # ERK's sum(shape) / numel of an expert's [32, 48] kernel, not of [16, 32, 48].
    up, dense = erk["layers_0/mixer/experts/kernel_up[3]"], erk["layers_0/mixer/latent_down/kernel"]
    assert up / dense == pytest.approx(((32 + 48) / (32 * 48)) / ((64 + 32) / (64 * 32)))
    assert erk["layers_0/mixer/experts/kernel_up[0]"] == erk["layers_0/mixer/experts/kernel_down[15]"]
    budget = sum(d * n for d, (_, _, n) in zip(erk.values(), masking.mask_layers(masks["dense"])))
    assert budget == pytest.approx(0.3 * masking.num_prunable(masks["dense"]))
    # Balanced: every layer the same count, an expert's kernel like any other.
    kept = {name: balanced[name] * n for name, _, n in masking.mask_layers(masks["dense"]) if balanced[name] < 1}
    assert max(kept.values()) == pytest.approx(min(kept.values()))


# --------------------------------------------- what the rest of the system says
def test_the_published_share_has_the_issues_shapes():
    """One period of Nemotron-3-Super as one chip of 32 holds it: shapes only."""
    cfg = compose("nemotron3_super_imp", [])
    mp = cfg.model_params
    assert (mp.model_name, mp.layer_pattern, mp.share) == ("nemotron_3_super_120b_a12b", "EMEMEMEMEM*", (8, 32, 0))
    assert cfg.dataset_params.num_classes == 16384 and cfg.dataset_params.input_spec() == ((1, 2, 512), "int32")
    assert (cfg.dataset_params.token_skew, cfg.optimizer_params.lr) == ("uniform", 0.002)
    model = create_model(mp.model_name, 16384, layer_pattern=mp.layer_pattern, share=mp.share)
    assert nemotron_h.NEMOTRON_3_SUPER["hybrid_override_pattern"][26:37] == model.pattern
    assert len(create_model(mp.model_name, 131072).pattern) == 88
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 2, 256), jnp.int32))["params"]
    e, m, a = shapes["layers_0"]["mixer"], shapes["layers_1"]["mixer"], shapes["layers_10"]["mixer"]
    assert m["in_proj"]["kernel"].shape == (4096, 2320) and m["out_proj"]["kernel"].shape == (1024, 4096)
    assert a["q_proj"]["kernel"].shape == (4096, 512) and a["k_proj"]["kernel"].shape == (4096, 128)
    assert e["router"]["weight"].shape == (4096, 512) and e["experts"]["kernel_up"].shape == (16, 1024, 2688)
    assert e["latent_down"]["kernel"].shape == (4096, 1024) and e["shared_up"]["kernel"].shape == (4096, 672)
    prunable = masking.num_prunable(jax.eval_shape(masking.make_masks, shapes))
    size = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # ISSUE 34: 650.7 M prunable x 13 bytes + 77.7 M x 12 = 9.39 GB.
    assert round(prunable / 1e6, 1) == 650.7 and round((size - prunable) / 1e6, 1) == 77.7
    assert round((13 * prunable + 12 * (size - prunable)) / 1e9, 2) == 9.39


def test_the_planner_answers_masked(whole):
    from turboprune_tpu.sparse import CompactionError, build_graph, plan_execution

    model, params, masks, _, _ = whole
    with pytest.raises(CompactionError, match="runs masked"):
        build_graph(model, params)
    plan = plan_execution(model, params, masks["half"], compact="auto", nm="auto")
    assert plan.kind == "masked" and plan.plan_signature() == ("masked",)


def test_the_config_keeps_a_share_with_the_models_that_have_one():
    assert {"nemotron_3_super_120b_a12b", "nemotron_h_tiny"} <= set(LANGUAGE_MODELS)
    assert compose("nemotron3_super_imp", TINY).model_params.share == (1, 2, 1)
    for bad in (
        ["model_params.model_name=granite_4_0_h_micro"],  # a share, and a model without one
        ["model_params.expert_rank=32"],
        ["model_params.tensor_parallel=0"],
        ["dataset_params.dataset_name=CIFAR10"],
    ):
        with pytest.raises(ConfigError):
            compose("nemotron3_super_imp", bad)
    with pytest.raises(ConfigError, match="share"):
        compose("granite_h_micro_imp", ["model_params.tensor_parallel=2"])
    with pytest.raises(ConfigError, match="token_skew"):
        compose("nemotron3_super_imp", ["dataset_params.token_skew=zipf"])
    with pytest.raises(ValueError, match="does not divide"):
        create_model("nemotron_h_tiny", 10, share=(3, 1, 0)).share.of(nemotron_h.NemotronHConfig(**nemotron_h.NEMOTRON_H_TINY))
    with pytest.raises(ValueError, match="no layer_pattern"):
        create_model("hybrid_lm_tiny", 10, share=(2, 1, 0))
    with pytest.raises(ValueError, match="runs masked"):
        create_model("nemotron_h_tiny", 10, width_overrides={"a": 1})
