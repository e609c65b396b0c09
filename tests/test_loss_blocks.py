"""The loss in blocks (train/steps.py): a token batch whose float32 logits
would take more than ``LOGITS_BYTES`` has head and loss run a block of tokens
at a time and never has ``[tokens, vocabulary]`` whole, in the train step, the
eval step and so every probe; its sums, counts, top-1 and gradients are the
whole form's, for every language model. A smaller batch runs the program it
always ran: the four earlier language-model entries' lowered train and eval
programs at the tiny sizes hash to what the commit before the blocks gave."""

import hashlib
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from turboprune_tpu.data.tokens import block_ordinals
from turboprune_tpu.models import BLOCK_DIFFUSION_MODELS, create_model
from turboprune_tpu.train import create_train_state, steps
from turboprune_tpu.utils import tracing

VOCAB, T, BATCH = 50, 48, 2


def _batch():
    rng = np.random.default_rng(3)
    flags = np.zeros((BATCH, T), np.int32)
    flags[0, [5, 16, 32]], flags[1, [40]] = 1, 1
    tokens = jnp.asarray(np.stack([rng.integers(0, VOCAB, (BATCH, T)), np.cumsum(flags, axis=1)], axis=1), jnp.int32)
    targets = jnp.asarray(np.where(rng.random((BATCH, T)) < 0.9, rng.integers(0, VOCAB, (BATCH, T)), -1), jnp.int32)
    return tokens, targets


def _batch_of(name):
    """``_batch()`` as the model ``name`` takes it: a block-diffusion model's
    has the block ordinals and the noised ids as rows, and weights."""
    tokens, targets = _batch()
    if name not in BLOCK_DIFFUSION_MODELS:
        return tokens, targets
    ids, seg = np.asarray(tokens[:, 0]), np.asarray(tokens[:, 1])
    noised = np.where(np.random.default_rng(4).random(ids.shape) < 0.3, VOCAB - 1, ids)
    tokens = jnp.asarray(np.stack([ids, seg, *block_ordinals(seg, 4), noised], axis=1), jnp.int32)
    return tokens, (targets, jnp.where(targets >= 0, 0.5, 0.0).astype(jnp.float32))


def _in_blocks(monkeypatch, block=16):
    """From here on every token batch is over the limit, ``block`` tokens a block."""
    monkeypatch.setattr(steps, "LOGITS_BYTES", 0)
    monkeypatch.setattr(steps, "LOSS_BLOCK", block)


def _state_of(model, tx, batch):
    return jax.jit(
        lambda: create_train_state(model, tx, jax.random.PRNGKey(0), batch[0].shape, input_dtype="int32")
    )()


@pytest.fixture()
def own_gauges(monkeypatch):
    """The process's gauges set aside for one case."""
    monkeypatch.setattr(tracing, "_gauges", {})
    monkeypatch.setattr(tracing, "_traced", set())


def _same_sums(got, want):
    assert float(got["count"]) == float(want["count"]) > 0
    assert float(got["correct"]) == float(want["correct"])  # top-1
    assert float(got["loss_sum"]) == pytest.approx(float(want["loss_sum"]), rel=1e-6)


# The untied head and the tied one with its scaling (brumby's and granite's, one layer of each: the heads are what differ).
HEADS = pytest.mark.parametrize(
    "name, kwargs", [["brumby_tiny", {"num_layers": 1}], ["hybrid_lm_tiny", {"num_layers": 1}]], ids=["brumby", "granite"]
)


@HEADS
def test_the_step_and_the_eval_in_blocks_are_the_whole_ones(name, kwargs, monkeypatch, own_gauges):
    model, tx, batch = create_model(name, VOCAB, **kwargs), optax.sgd(0.1, momentum=0.9), _batch_of(name)
    state = _state_of(model, tx, batch)
    with jax.default_matmul_precision("highest"):
        whole_state, whole = jax.jit(steps.make_train_step(model, tx))(state, batch)
        whole_eval = jax.jit(steps.make_eval_step(model))(state, batch)
        assert tracing.gauges().get("loss_blocks_per_step") is None  # whole logits set no gauge
        _in_blocks(monkeypatch)
        blocked_eval = jax.jit(steps.make_eval_step(model))(state, batch)
        assert tracing.trace_gauges()["loss_blocks_per_step"] == T // 16
        assert "loss_grad_blocks_per_step" not in tracing.gauges()  # an eval makes no gradient
        blocked_state, blocked = jax.jit(steps.make_train_step(model, tx))(state, batch)
        assert tracing.trace_gauges()["loss_grad_blocks_per_step"] == T // 16
    _same_sums(blocked, whole)
    _same_sums(blocked_eval, whole_eval)
    # One SGD step from the same state: the gradients, every leaf's that the loss reaches.
    reached = 0
    for a, b, w in zip(*(jax.tree.leaves(s.params) for s in (blocked_state, whole_state, state))):
        moved = float(jnp.max(jnp.abs(b - w)))
        reached += moved > 0
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * moved
    assert reached > 0.8 * len(jax.tree.leaves(state.params))


@pytest.mark.parametrize(
    "name, kwargs",
    [
        ["nemotron_h_tiny", {"share": [2, 4, 1], "layer_pattern": "E"}],
        ["sdar_moe_tiny", {"share": [2, 4, 1], "num_layers": 1}],  # weights, and the noised half of the rows
        ["lfm2_moe_tiny", {"share": [2, 4, 1], "num_layers": 2}],  # tied; its second layer is the first routed
    ],
    ids=["nemotron", "sdar", "lfm2"],
)
def test_the_routed_models_take_the_heads_reduce(name, kwargs, monkeypatch):
    """One routed layer of each: the eval step in blocks is the whole one, and
    the train step, where a model with counters is applied another way, traces
    in blocks to the same sums and counters (no program compiled for it: how a
    gradient passes the blocks is the two models' above and no model's own)."""
    model, tx, batch = create_model(name, VOCAB, **kwargs), optax.sgd(0.1, momentum=0.9), _batch_of(name)
    state = _state_of(model, tx, batch)
    step = lambda: jax.eval_shape(steps.make_train_step(model, tx), state, batch)[1]
    with jax.default_matmul_precision("highest"):
        whole_eval, whole = jax.jit(steps.make_eval_step(model))(state, batch), step()
        _in_blocks(monkeypatch)
        _same_sums(jax.jit(steps.make_eval_step(model))(state, batch), whole_eval)
    assert step() == whole and set(model.counters) < set(whole)


@pytest.mark.parametrize("weighted", [False, True], ids=["next_token", "weighted"])
def test_the_sums_of_the_blocks_are_the_sums_of_the_whole(weighted):
    """Both kinds of token labels, the block-diffusion pair too, against
    ``token_loss_sums`` of logits formed whole, and the gradients to ``x`` and
    to the kernel, at a cotangent that is not the mean's."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(BATCH, T, 8)), jnp.float32)
    kernel = jnp.asarray(rng.normal(size=(8, VOCAB)), jnp.float32)
    labels = _batch()[1]
    if weighted:
        labels = (labels, jnp.asarray(np.where(labels >= 0, rng.uniform(0.2, 1.0, labels.shape), -1.0), jnp.float32))
    logits_of = lambda x, kernel: jnp.einsum("btd,dv->btv", x, kernel, precision="highest")
    whole = lambda x, kernel: steps.token_loss_sums(logits_of(x, kernel), labels)
    blocked = lambda x, kernel: steps.sums_in_blocks(labels, 3)(logits_of, x, kernel)
    np.testing.assert_allclose(blocked(x, kernel), whole(x, kernel), rtol=1e-6)
    grad = lambda f: jax.grad(lambda *a: 0.37 * f(*a)[0], argnums=(0, 1))(x, kernel)
    for got, want in zip(grad(blocked), grad(whole)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize(
    "tokens, vocab, blocks",
    [
        (8192, 12544, 0),  # the granite cell: 0.38 GiB
        (8192, 16384, 0),  # the convolution-hybrid cell: 0.50 GiB
        (8192, 18992, 0),  # the block-diffusion cell: 0.58 GiB
        (32768, 18992, 16),  # the retention cell: 2.3 GiB, 16 blocks of 2,048
        (2 * 8192, 16384, 8),  # two of the sparse-expert cell's sequences a step: 1.0 GiB
        (8192, 24576, 0),  # exactly the limit, 0.75 GiB, is under it
        (3 * 4099, 30000, 3),  # 12,297 tokens: the fewest equal blocks of at most 2,048 are 3 x 4,099 / ... none: 4,099 is prime
    ],
)
def test_the_blocks_are_read_off_the_shapes(tokens, vocab, blocks):
    model = types.SimpleNamespace(vocab_size=vocab)
    labels = jax.ShapeDtypeStruct((1, tokens), jnp.int32)
    got = steps.loss_blocks(model, labels)
    if blocks == 3:  # no divisor gives blocks of at most LOSS_BLOCK but the tokens themselves
        assert tokens % got == 0 and tokens // got <= steps.LOSS_BLOCK
    else:
        assert got == blocks
    assert steps.loss_blocks(model, (labels, labels)) == got  # a batch with weights alike
    assert steps.loss_blocks(model, jax.ShapeDtypeStruct((4096,), jnp.int32)) == 0  # images
    assert steps.loss_blocks(object(), labels) == 0  # a model that names no vocabulary


def _rows_by_vocabulary(fn, vocab, *args) -> int:
    """Of every array of ``fn``'s jaxpr (inner jaxprs included) that has the
    vocabulary as an axis, the most elements beside it."""
    most = 0

    def walk(jaxpr):
        nonlocal most
        for v in [*jaxpr.invars, *jaxpr.constvars, *(o for e in jaxpr.eqns for o in e.outvars)]:
            shape = getattr(v.aval, "shape", ())
            if vocab in shape:
                most = max(most, int(np.prod(shape)) // vocab)
        for eqn in jaxpr.eqns:
            for inner in jax.core.jaxprs_in_params(eqn.params):
                walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return most


@pytest.mark.parametrize("program", ["train", "eval"])
def test_no_array_of_tokens_by_vocabulary_exists_in_the_new_models_programs(monkeypatch, program):
    """Whole, the logits ``[2, 48, vocabulary]`` are in the program. In blocks
    of 8 tokens nothing with the vocabulary as an axis has more beside it
    than the head's and the embedding's 32 channels, a block's 16 rows among
    them: not in the train step (forward, rebuilt forward, backward), not in
    the eval step, which is the program of ``evaluate()`` and of every probe."""
    vocab = 4099  # no other size of the model
    model, tx = create_model("brumby_tiny", vocab), optax.sgd(0.1)
    tokens, targets = _batch()
    state = jax.eval_shape(
        lambda: create_train_state(model, tx, jax.random.PRNGKey(0), tokens.shape, input_dtype="int32")
    )
    make = (lambda: steps.make_train_step(model, tx)) if program == "train" else (lambda: steps.make_eval_step(model))
    assert _rows_by_vocabulary(make(), vocab, state, (tokens, targets)) == BATCH * T
    _in_blocks(monkeypatch, block=8)
    assert _rows_by_vocabulary(make(), vocab, state, (tokens, targets)) == model.cfg.hidden_size < BATCH * T


def _products_by_scan(jaxpr, vocab) -> list[int]:
    """The ``dot_general``s of ``jaxpr`` that have the vocabulary as an axis,
    counted by the outermost scan that holds them (a scan that holds none is
    left out), those in no scan last."""

    def inner(eqn):
        return list(jax.core.jaxprs_in_params(eqn.params))

    def products(jaxpr):
        return sum(
            (eqn.primitive.name == "dot_general" and any(vocab in v.aval.shape for v in [*eqn.invars, *eqn.outvars]))
            + sum(map(products, inner(eqn)))
            for eqn in jaxpr.eqns
        )

    def scans(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                yield sum(map(products, inner(eqn)))
            else:
                for j in inner(eqn):
                    yield from scans(j)

    found = [n for n in scans(jaxpr) if n]
    return [*found, products(jaxpr) - sum(found)]


@pytest.mark.parametrize("program, products", [["train", 3], ["eval", 1]])
@HEADS
def test_a_blocks_logits_are_formed_once(name, kwargs, program, products, monkeypatch, own_gauges):
    """Traced, never compiled: in blocks the train step's products with the
    vocabulary as an axis are three, in ONE scan over the blocks (the logits,
    ``d x``, the gradient of the kernel or of the tied table), where a
    backward scan that rebuilt the logits made them four over two; the eval
    step's is the one. The train trace says so in a gauge, the eval's does not."""
    vocab = 4099  # no other size of either model
    model, tx, batch = create_model(name, vocab, **kwargs), optax.sgd(0.1), _batch_of(name)
    state = jax.eval_shape(
        lambda: create_train_state(model, tx, jax.random.PRNGKey(0), batch[0].shape, input_dtype="int32")
    )
    _in_blocks(monkeypatch)
    step = steps.make_train_step(model, tx) if program == "train" else steps.make_eval_step(model)
    assert _products_by_scan(jax.make_jaxpr(step)(state, batch).jaxpr, vocab) == [products, 0]
    gauges = tracing.gauges()
    assert gauges["loss_blocks_per_step"] == T // 16
    assert gauges.get("loss_grad_blocks_per_step") == (T // 16 if program == "train" else None)


_PROGRAMS = """
import hashlib, json, sys, jax, jax.numpy as jnp, numpy as np, optax
from turboprune_tpu.data.tokens import block_ordinals
from turboprune_tpu.models import BLOCK_DIFFUSION_MODELS, create_model
from turboprune_tpu.train import create_train_state
from turboprune_tpu.train.steps import make_eval_step, make_train_step

rng = np.random.default_rng(20261004)
flags = np.zeros((2, 32), np.int32)
flags[0, [5, 16, 17]], flags[1, [20]] = 1, 1
ids, seg = rng.integers(0, 49, (2, 32)), np.cumsum(flags, axis=1)
targets = jnp.asarray(np.where(rng.random((2, 32)) < 0.9, rng.integers(0, 49, (2, 32)), -1), jnp.int32)
for name, kwargs in json.loads(sys.argv[1]):
    model = create_model(name, 50, **kwargs)
    rows, labels = [ids, seg], targets
    if name in BLOCK_DIFFUSION_MODELS:
        rows += [*block_ordinals(seg, 4), np.where(rng.random((2, 32)) < 0.3, 49, ids)]
        labels = (targets, jnp.where(targets >= 0, 0.5, 0.0).astype(jnp.float32))
    tokens = jnp.asarray(np.stack(rows, axis=1), jnp.int32)
    tx = optax.sgd(0.1, momentum=0.9)
    state = jax.eval_shape(
        lambda: create_train_state(model, tx, jax.random.PRNGKey(0), tokens.shape, input_dtype="int32")
    )
    for kind, fn in (("train", make_train_step(model, tx)), ("eval", make_eval_step(model))):
        text = jax.jit(fn).lower(state, (tokens, labels)).as_text()
        print(name, kind, hashlib.sha256(text.encode()).hexdigest(), flush=True)
"""
# Every language-model entry at its tiny size, every kind of layer it has, and
# a share that is not the whole model where it takes one.
_CASES = [
    ["hybrid_lm_tiny", {}],
    ["nemotron_h_tiny", {"share": [2, 4, 1], "layer_pattern": "EM*E"}],
    ["sdar_moe_tiny", {"share": [2, 4, 1]}],
    ["lfm2_moe_tiny", {"share": [2, 4, 1]}],
]


@pytest.fixture(scope="module", autouse=True)
def lowering(tmp_path_factory):
    """The eight programs lowered in a process of its own, as the hashes were
    taken (the names a trace gives its functions count what the process traced
    before), started with the module's first test: it runs beside the other
    tests and not after them (71 s of this file's 177 in the driver's run of
    PR 44's tree, which took 1,434 s of its 1,470)."""
    out = tmp_path_factory.mktemp("lowering")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(sys.path))
    with open(out / "stdout", "w") as stdout, open(out / "stderr", "w") as stderr:
        proc = subprocess.Popen([sys.executable, "-c", _PROGRAMS, json.dumps(_CASES)], stdout=stdout, stderr=stderr, env=env)
    yield proc, out
    proc.kill()  # a run that selected none of its readers
    proc.wait()


@pytest.fixture(scope="module")
def program_hashes(lowering):
    proc, out = lowering
    assert proc.wait(timeout=600) == 0, (out / "stderr").read_text()[-2000:]
    lines = (out / "stdout").read_text().splitlines()
    return {tuple(line.split()[:2]): line.split()[2] for line in lines if len(line.split()) == 3}


@pytest.mark.parametrize(
    "name, kind, sha256",
    [
        ("hybrid_lm_tiny", "train", "e0afa68b28b4c0d82bc126c7ef8068ba268af6ed41b78d9ca3623e4b5313b31c"),
        ("hybrid_lm_tiny", "eval", "12de8830c29d1ca1609ab2a887b6f946b3a9de385c7e83c0d59a55d0267be522"),
        ("nemotron_h_tiny", "train", "447590941790ab1c9406b01dbd2b85818ba7f84df4758f337af9a6d05f7e6ed8"),
        ("nemotron_h_tiny", "eval", "ecfefc958c3123f57921005c324e71cf7480f96eb0da9a7e10d23d91a7f5f69e"),
        ("sdar_moe_tiny", "train", "3abb637b5bc9fd8a1b2712d62238155c07346104161ed3f8242e4e7fb3d0b246"),
        ("sdar_moe_tiny", "eval", "586f092185e38b5ab3d154214b27f42498eeaa0fa6d11998701998a711c46598"),
        ("lfm2_moe_tiny", "train", "662c088c0c7adb9e17cd03c5ed6668262c4078fd6c2e2693ecfbacaa1182c2c5"),
        ("lfm2_moe_tiny", "eval", "5dabc0f1d314560eccd8dbc54a724a4d5c66d65b1f8bcdf83ce75514b021b7ba"),
    ],
)
def test_the_four_entries_step_and_eval_programs_are_the_programs_they_were(program_hashes, name, kind, sha256):
    """``make_train_step`` and ``make_eval_step`` of each existing language
    model at its tiny size, every kind of layer it has and a share that is not
    the whole model where it takes one, lowered on one seeded batch (the
    block-diffusion model's with weights): the text hashes to what commit
    5b7c73b gave, the commit before head and loss could run in blocks. A PR
    that moves one by design retakes its hash at its own commit and says so."""
    assert program_hashes[(name, kind)] == sha256
