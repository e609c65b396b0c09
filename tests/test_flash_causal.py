"""The causal, grouped, packed path of the Pallas flash kernel
(ops/flash.py::flash_attention_causal) against a plain masked softmax,
forward and gradient, in interpret mode; its walk (the pairs of blocks the
kernels visit are the pairs the square grid ran, in its order, and no other
block is read); the bidirectional call, which the language model's path
may not have changed by a bit; and the two packed families' calls, which are
written once and lower to the programs they were."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from turboprune_tpu.data.tokens import block_ordinals
from turboprune_tpu.ops.flash import (
    _block_ranges, _causal_setup, flash_attention, flash_attention_blockdiff, flash_attention_causal,
)  # fmt: skip

import flash_walk

BATCH, HEADS, KV_HEADS, T, D = 2, 4, 2, 64, 8
SCALE = 0.3


def plain(q, k, v, seg, scale, heads=HEADS, kv_heads=KV_HEADS):
    bsz, t, d = seg.shape[0], q.shape[1], q.shape[2]
    q = q.reshape(bsz, kv_heads, heads // kv_heads, t, d)
    k, v = k.reshape(bsz, kv_heads, t, d), v.reshape(bsz, kv_heads, t, d)
    s = jnp.einsum("bkgqd,bksd->bkgqs", q, k) * scale
    pos = jnp.arange(t)
    keep = (seg[:, :, None] == seg[:, None, :]) & (pos[None, :, None] >= pos[None, None, :])
    w = jax.nn.softmax(jnp.where(keep[:, None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bkgqs,bksd->bkgqd", w, v).reshape(bsz * heads, t, d)


def inputs(seed=0, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (BATCH * HEADS, T, D), dtype)
    k = jax.random.normal(kk, (BATCH * KV_HEADS, T, D), dtype)
    v = jax.random.normal(kv, (BATCH * KV_HEADS, T, D), dtype)
    flags = np.zeros((BATCH, T), np.int32)
    flags[0, [5, 16, 17, 40]] = 1  # starts inside blocks and on a block's border
    flags[1, [32]] = 1
    return q, k, v, jnp.asarray(np.cumsum(flags, axis=1))


@pytest.mark.parametrize("blocks", [(16, 16), (32, 32), (64, 64), (16, 32), (32, 16)])
def test_forward_equals_a_plain_masked_softmax(blocks, seg=None):
    q, k, v, packed = inputs()
    seg = packed if seg is None else seg
    with jax.default_matmul_precision("highest"):
        got = flash_attention_causal(q, k, v, seg, SCALE, *blocks)
        want = plain(q, k, v, seg, SCALE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("blocks", [(16, 16), (64, 64), (16, 32)])
def test_gradients_equal_a_plain_masked_softmax(blocks, seg=None):
    q, k, v, packed = inputs(seed=1)
    seg = packed if seg is None else seg
    weigh = lambda fn: jax.grad(lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v))), argnums=(0, 1, 2))
    with jax.default_matmul_precision("highest"):
        got = weigh(lambda q, k, v: flash_attention_causal(q, k, v, seg, SCALE, *blocks))(q, k, v)
        want = weigh(lambda q, k, v: plain(q, k, v, seg, SCALE))(q, k, v)
    for name, g, w in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5, err_msg=name)


def test_one_key_value_head_a_query_head_is_plain_multi_head():
    q, k, v, seg = inputs()
    k = jnp.repeat(k.reshape(BATCH, KV_HEADS, T, D), HEADS // KV_HEADS, axis=1).reshape(q.shape)
    v = jnp.repeat(v.reshape(BATCH, KV_HEADS, T, D), HEADS // KV_HEADS, axis=1).reshape(q.shape)
    got = flash_attention_causal(q, k, v, seg, SCALE, 16, 16)
    want = plain(q, k, v, seg, SCALE, kv_heads=HEADS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_blocks_of_other_documents_are_skipped_and_change_nothing():
    """Poison (NaN) in the keys and values of the first document's blocks
    reaches no query of a later document: those blocks are skipped, not
    multiplied by zero."""
    q, k, v, _ = inputs()
    seg = jnp.asarray(np.repeat(np.arange(4), 16)[None].repeat(BATCH, 0), jnp.int32)
    clean = flash_attention_causal(q, k, v, seg, SCALE, 16, 16)
    k, v = k.at[:, :16].set(jnp.nan), v.at[:, :16].set(jnp.nan)
    got = flash_attention_causal(q, k, v, seg, SCALE, 16, 16)
    np.testing.assert_array_equal(np.asarray(got[:, 16:]), np.asarray(clean[:, 16:]))
    lo, hi = _block_ranges(seg, 16)
    assert lo.tolist() == hi.tolist() == [[0, 1, 2, 3]] * BATCH


def test_bf16_operands():
    q, k, v, seg = inputs(dtype=jnp.bfloat16)
    got = flash_attention_causal(q, k, v, seg, SCALE, 16, 16)
    want = plain(*(t.astype(jnp.float32) for t in (q, k, v)), seg, SCALE)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=0.05)


@pytest.mark.parametrize(
    "change, match",
    [
        (lambda q, k, v, seg: (q, k, v, seg[:, :48]), "share a batch"),
        (lambda q, k, v, seg: (q[:6], k[:4], v[:4], seg), "not a multiple"),
        (lambda q, k, v, seg: (q[:, :40], k[:, :40], v[:, :40], seg[:, :40]), "multiple of"),
    ],
)
def test_shapes_that_do_not_fit_are_refused(change, match):
    with pytest.raises(ValueError, match=match):
        flash_attention_causal(*change(*inputs()), SCALE, 16, 16)


# Segment ids [BATCH, T] by the tokens at which documents start: packed (the
# oracle tests' layout), one document, documents of one kernel block each,
# documents of one token, and ids that do not ascend (the test of blocks is
# then conservative, and the walk lists what it accepts all the same).
LAYOUTS = {
    "packed": [[5, 16, 17, 40], [32]],
    "one_document": [[], []],
    "documents_of_one_kernel_block": [[16, 32, 48], [16, 32, 48]],
    "documents_of_one_token": [list(range(1, T)), list(range(1, T, 2))],
    "random_0": None,
    "random_1": None,
    "random_2": None,
    "ids_that_do_not_ascend": None,
}


def segments(layout):
    if layout == "ids_that_do_not_ascend":
        return jnp.asarray(np.random.default_rng(5).integers(0, 3, size=(BATCH, T)) // 2 * np.array([[1], [2]]), jnp.int32)
    starts = LAYOUTS[layout]
    if starts is None:
        rng = np.random.default_rng(int(layout[7:]))
        starts = [rng.choice(np.arange(1, T), size=rng.integers(0, 9), replace=False) for _ in range(BATCH)]
    flags = np.zeros((BATCH, T), np.int32)
    for b, at in enumerate(starts):
        flags[b, list(at)] = 1
    return jnp.asarray(np.cumsum(flags, axis=1))


def square_grid(seg, block_q, block_k):
    """[B, nq, nk] bool: the pairs the square grid's predicate ran, written out
    a (batch row, query block, key block) at a time as the kernels of the
    commit before the walk tested it (``_runs`` on scalars)."""
    seg = np.asarray(seg)
    nq, nk = T // block_q, T // block_k
    runs = np.zeros((BATCH, nq, nk), bool)
    for b in range(BATCH):
        for qi in range(nq):
            for ki in range(nk):
                qs, ks = seg[b, qi * block_q : (qi + 1) * block_q], seg[b, ki * block_k : (ki + 1) * block_k]
                causal = ki * block_k <= qi * block_q + (block_q - 1)
                runs[b, qi, ki] = causal and ks.max() >= qs.min() and ks.min() <= qs.max()
    return runs


@pytest.mark.parametrize("blocks", [(16, 16), (16, 32)])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_walk_lists_the_pairs_the_square_grid_ran_in_its_order(layout, blocks):
    seg = segments(layout)
    q, k, _, _ = inputs()
    square = square_grid(seg, *blocks)
    n_run = flash_walk.assert_lists(_causal_setup(q, k, seg, *blocks)[3], square, HEADS // KV_HEADS)
    nq, nk = square.shape[1:]
    assert n_run.max() <= nq * (nk + 1)  # no longer than the causal order leaves


@pytest.mark.parametrize(
    "layout", ["one_document", "documents_of_one_kernel_block", "documents_of_one_token", "ids_that_do_not_ascend"]
)
def test_forward_and_gradients_equal_a_plain_masked_softmax_on_the_walks_edges(layout):
    test_forward_equals_a_plain_masked_softmax((16, 16), segments(layout))
    test_gradients_equal_a_plain_masked_softmax((16, 16), segments(layout))


@pytest.mark.parametrize("layout", ["documents_of_one_token", "random_1", "random_2"])
def test_poison_in_a_block_reaches_the_blocks_paired_with_it_and_no_other(layout):
    seg = segments(layout)
    q, k, v, _ = inputs(seed=3)
    flash_walk.assert_poison_stays_in_its_pairs(
        lambda q, k, v: flash_attention_causal(q, k, v, seg, SCALE, 16, 16),
        square_grid(seg, 16, 16), q, k, v, HEADS, KV_HEADS, 16,
    )  # fmt: skip


def test_the_bidirectional_call_is_the_program_it_was():
    """The lowered program of ``flash_attention`` (forward and its three
    gradients, interpret mode) hashes to what the commit before the causal
    path gave: the same program gives the same bits. Its values are held to
    the dense oracle in test_flash.py."""
    rng = np.random.default_rng(20260929)
    q, k, v = (jnp.asarray(rng.normal(size=(6, 32, 8)), jnp.float32) for _ in range(3))
    valid = jnp.asarray([[1.0] * 27 + [0.0] * 5])
    f = lambda q, k, v: flash_attention(q, k, v, valid, 0.35, 16, 8)
    g = jax.jit(lambda q, k, v: jax.grad(lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v))), argnums=(0, 1, 2))(q, k, v))
    text = g.lower(q, k, v).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "23c63effc2c0d248fc9f1d0bbe75e7d8842bbe9fdf9f5f4e9e7e3af35340d7e8"
    )


@pytest.mark.parametrize(
    "family, sha256",
    [
        ("causal", "6c5edd0d84d3a06ec3587ba93c0ec36486111fc6a5d5746cb0959b3e4a5db71f"),
        ("blockdiff", "e26e2a0ea3dd17ee3f59c1ebac051f884517d16891fc62bdf728c7eb9c8ceab9"),
    ],
)
def test_a_packed_familys_call_is_the_program_it_was(family, sha256):
    """The two packed families' three ``pallas_call``s are written once
    (``_packed_fwd``, ``_packed_bwd`` over a ``_Family``): the lowered program
    of each entry point (forward and its three gradients, interpret mode,
    grouped heads, documents that start inside kernel blocks, block_q other
    than block_k) hashes to what commit 7802b81 gave, where each family had
    its own copy of the calls."""
    rng = np.random.default_rng(20261004)
    q = jnp.asarray(rng.normal(size=(8, 64, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(4, 64, 8)), jnp.float32) for _ in range(2))
    flags = np.zeros((2, 64), np.int32)
    flags[0, [5, 16, 17, 40]], flags[1, [22]] = 1, 1
    seg = np.cumsum(flags, axis=1)
    if family == "causal":
        f = lambda q, k, v: flash_attention_causal(q, k, v, jnp.asarray(seg), 0.35, 16, 32)
    else:  # 64 rows are the two copies of 32 tokens
        doc = seg[:, :32]
        blk = block_ordinals(doc, 4)[0]
        f = lambda q, k, v: flash_attention_blockdiff(q, k, v, jnp.asarray(doc), jnp.asarray(blk), 0.35, 16, 32)
    g = jax.jit(lambda q, k, v: jax.grad(lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v))), argnums=(0, 1, 2))(q, k, v))
    text = g.lower(q, k, v).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == sha256
