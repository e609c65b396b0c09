"""The block-diffusion path of the Pallas flash kernel
(ops/flash.py::flash_attention_blockdiff) against a dense oracle built from
the rule, forward and the three gradients, in interpret mode; and its walk:
the (query block, key block) pairs the kernels visit are the pairs the square
grid ran, in its order, and no other block is read."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from turboprune_tpu.ops.flash import (
    _blockdiff_bounds,
    _blockdiff_pairs,
    blockdiff_walk_counts,
    flash_attention_blockdiff,
)

import flash_walk

BATCH, HEADS, KV_HEADS, T, D = 2, 4, 2, 64, 8
SCALE = 0.3


def ordinals(starts, t, block_length):
    """(doc, blk) [len(starts), t] of documents starting at ``starts[b]``."""
    flags = np.zeros((len(starts), t), np.int32)
    for b, at in enumerate(starts):
        flags[b, at] = 1
    doc = np.cumsum(flags, axis=1)
    first = np.maximum.accumulate(np.where(flags | (np.arange(t) == 0), np.arange(t), 0), axis=1)
    return jnp.asarray(doc), jnp.asarray((np.arange(t) - first) // block_length)


def rule(doc, blk):
    """keep [B, 2T, 2T] as the top of ops/flash.py's third part states it."""
    doc2, blk2 = (np.concatenate([np.asarray(x)] * 2, axis=1) for x in (doc, blk))
    noised = np.arange(doc2.shape[1]) >= doc.shape[1]
    same = doc2[:, :, None] == doc2[:, None, :]
    qb, kb = blk2[:, :, None], blk2[:, None, :]
    qn, kn = noised[None, :, None], noised[None, None, :]
    return same & ((~qn & ~kn & (kb <= qb)) | (qn & ~kn & (kb < qb)) | (qn & kn & (kb == qb)))


def plain(q, k, v, keep, scale, heads=HEADS, kv_heads=KV_HEADS):
    bsz, rows, d = keep.shape[0], q.shape[1], q.shape[2]
    q = q.reshape(bsz, kv_heads, heads // kv_heads, rows, d)
    k, v = k.reshape(bsz, kv_heads, rows, d), v.reshape(bsz, kv_heads, rows, d)
    s = jnp.einsum("bkgqd,bksd->bkgqs", q, k) * scale
    w = jax.nn.softmax(jnp.where(jnp.asarray(keep)[:, None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bkgqs,bksd->bkgqd", w, v).reshape(bsz * heads, rows, d)


def inputs(seed=0, dtype=jnp.float32, t=T):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (BATCH * HEADS, 2 * t, D), dtype)
    k = jax.random.normal(kk, (BATCH * KV_HEADS, 2 * t, D), dtype)
    v = jax.random.normal(kv, (BATCH * KV_HEADS, 2 * t, D), dtype)
    return q, k, v


# Packed documents that start inside kernel blocks and on their borders; a
# last block that is short (a document of 11 tokens in blocks of 4); a
# document cut by the sequence's end.
LAYOUTS = {
    "packed": ([[5, 16, 17, 40], [32]], 4),
    "one_document": ([[], []], 4),
    "blocks_of_3": ([[7, 30], [1, 2, 50]], 3),  # T is no multiple of the block length
    "blocks_of_16": ([[9], [48, 59]], 16),
}
# The walk's worst cases and edges: one block of tokens as long as its
# document (``blk`` constant over it: the noised-onto-noised quadrant runs
# whole), documents of one kernel block each, documents of one token.
WORST = {
    "one_block_one_document": ([[], []], T),
    "one_block_a_document": ([[5, 16, 17, 40], [32]], T),
    "documents_of_one_kernel_block": ([[16, 32, 48], [16, 32, 48]], 4),
    "documents_of_one_token": ([list(range(1, T)), list(range(1, T, 2))], 4),
}
EVERY = {**LAYOUTS, **WORST}


def random_layout(seed):
    rng = np.random.default_rng(seed)
    starts = [sorted(rng.choice(np.arange(1, T), size=rng.integers(0, 9), replace=False).tolist()) for _ in range(BATCH)]
    return starts, int(rng.choice([1, 3, 4, 16, T]))


def layout_of(name):
    return random_layout(int(name[7:])) if name.startswith("random_") else EVERY[name]


def square_grid(doc, blk, block_q, block_k):
    """[B, nq, nk] bool: the pairs the square grid's predicate ran, written
    out a (batch row, query block, key block) at a time as the kernels of the
    commit before the walk tested it (``_interval_runs`` on scalars)."""
    lo, hi = (np.asarray(x)[..., 0] for x in _blockdiff_bounds(doc, blk))
    bsz, rows = lo.shape[0], lo.shape[2]
    nq, nk = rows // block_q, rows // block_k
    runs = np.zeros((bsz, nq, nk), bool)
    for b in range(bsz):
        for qi in range(nq):
            for ki in range(nk):
                half = ki // (nk // 2)
                at = slice(qi * block_q, (qi + 1) * block_q)
                runs[b, qi, ki] = lo[b, half, at].min() // block_k <= ki <= hi[b, half, at].max() // block_k
    return runs


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("blocks", [(16, 16), (64, 64), (16, 32), (32, 16)])
def test_forward_equals_the_rule(layout, blocks):
    starts, block_length = EVERY[layout]
    doc, blk = ordinals(starts, T, block_length)
    q, k, v = inputs()
    with jax.default_matmul_precision("highest"):
        got = flash_attention_blockdiff(q, k, v, doc, blk, SCALE, *blocks)
        want = plain(q, k, v, rule(doc, blk), SCALE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("layout", ["packed", "blocks_of_3"])
@pytest.mark.parametrize("blocks", [(16, 16), (32, 16)])
def test_gradients_equal_the_rule(layout, blocks):
    starts, block_length = EVERY[layout]
    doc, blk = ordinals(starts, T, block_length)
    q, k, v = inputs(seed=1)
    keep = rule(doc, blk)
    weigh = lambda fn: jax.grad(lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v))), argnums=(0, 1, 2))
    with jax.default_matmul_precision("highest"):
        got = weigh(lambda q, k, v: flash_attention_blockdiff(q, k, v, doc, blk, SCALE, *blocks))(q, k, v)
        want = weigh(lambda q, k, v: plain(q, k, v, keep, SCALE))(q, k, v)
    for name, g, w in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5, err_msg=name)


def test_every_row_keeps_itself_and_no_clean_row_a_noised_key():
    doc, blk = ordinals(*LAYOUTS["packed"][:1], T, 4)
    keep = rule(doc, blk)
    assert keep[:, np.arange(2 * T), np.arange(2 * T)].all()
    assert not keep[:, :T, T:].any()
    lo, hi = (np.asarray(x)[..., 0] for x in _blockdiff_bounds(doc, blk))
    at = np.arange(2 * T)
    from_bounds = ((at >= lo[:, 0, :, None]) & (at <= hi[:, 0, :, None]) & (at < T)) | (
        (at >= lo[:, 1, :, None]) & (at <= hi[:, 1, :, None]) & (at >= T)
    )
    np.testing.assert_array_equal(from_bounds, keep)


def test_a_noised_row_does_not_see_its_own_clean_tokens():
    """Other clean keys and values in one block of tokens move the clean rows
    from that block on and the noised rows of later blocks, and no noised row
    of the block itself or before it."""
    doc, blk = ordinals([[], []], T, 4)
    q, k, v = inputs()
    block = slice(20, 24)
    before = flash_attention_blockdiff(q, k, v, doc, blk, SCALE, 16, 16)
    after = flash_attention_blockdiff(
        q, k.at[:, block].add(1.0), v.at[:, block].add(1.0), doc, blk, SCALE, 16, 16
    )
    moved = (np.asarray(before) != np.asarray(after)).any(axis=(0, 2))
    assert not moved[:20].any() and moved[20:T].all()
    assert not moved[T : T + 24].any() and moved[T + 24 :].all()


def test_quadrants_no_row_keeps_are_skipped():
    """The clean-onto-noised quadrant never runs: poison in every noised key
    and value leaves the clean rows as they were."""
    doc, blk = ordinals(*LAYOUTS["packed"][:1], T, 4)
    q, k, v = inputs()
    clean = flash_attention_blockdiff(q, k, v, doc, blk, SCALE, 16, 16)
    got = flash_attention_blockdiff(
        q, k.at[:, T:].set(jnp.nan), v.at[:, T:].set(jnp.nan), doc, blk, SCALE, 16, 16
    )
    np.testing.assert_array_equal(np.asarray(got[:, :T]), np.asarray(clean[:, :T]))


def test_bf16_operands():
    doc, blk = ordinals(*LAYOUTS["packed"][:1], T, 4)
    q, k, v = inputs(dtype=jnp.bfloat16)
    got = flash_attention_blockdiff(q, k, v, doc, blk, SCALE, 16, 16)
    want = plain(*(t.astype(jnp.float32) for t in (q, k, v)), rule(doc, blk), SCALE)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=0.05)


@pytest.mark.parametrize(
    "change, match",
    [
        (lambda q, k, v, doc, blk: (q[:, :T], k, v, doc, blk), "the clean and the noised copy"),
        (lambda q, k, v, doc, blk: (q[:6], k[:4], v[:4], doc, blk), "not a multiple"),
        (lambda q, k, v, doc, blk: (q[:, :80], k[:, :80], v[:, :80], doc[:, :40], blk[:, :40]), "multiple of"),
    ],
)
def test_shapes_that_do_not_fit_are_refused(change, match):
    doc, blk = ordinals(*LAYOUTS["packed"][:1], T, 4)
    with pytest.raises(ValueError, match=match):
        flash_attention_blockdiff(*change(*inputs(), doc, blk), SCALE, 16, 16)


ALL = [*EVERY, "random_0", "random_1", "random_2"]


@pytest.mark.parametrize("blocks", [(16, 16), (16, 32)])
@pytest.mark.parametrize("layout", ALL)
def test_the_walk_lists_the_pairs_the_square_grid_ran_in_its_order(layout, blocks):
    starts, block_length = layout_of(layout)
    doc, blk = ordinals(starts, T, block_length)
    square = square_grid(doc, blk, *blocks)
    n_run = flash_walk.assert_lists(_blockdiff_pairs(doc, blk, *blocks)[2], square, HEADS // KV_HEADS)
    # Clean onto noised never runs, so no list outgrows three quadrants; every key block is met.
    nq, nk = square.shape[1:]
    assert not square[:, : nq // 2, nk // 2 :].any() and n_run.max() <= 3 * nq * nk // 4
    assert square.any(axis=1).all()


@pytest.mark.parametrize("layout", ALL)
def test_the_counts_are_the_walks(layout):
    starts, block_length = layout_of(layout)
    doc, blk = ordinals(starts, T, block_length)
    a_row = square_grid(doc, blk, 16, 16).sum(axis=(1, 2))
    run, walked = blockdiff_walk_counts(doc, blk, 16, 16)
    assert (run.dtype, walked.dtype) == (jnp.int32, jnp.int32)
    assert (int(run), int(walked)) == (a_row.sum(), BATCH * a_row.max())


@pytest.mark.parametrize("layout", WORST)
def test_forward_and_gradients_equal_the_rule_on_the_worst_cases(layout):
    test_forward_equals_the_rule(layout, (16, 16))
    test_gradients_equal_the_rule(layout, (16, 16))


@pytest.mark.parametrize("layout", ["packed", "blocks_of_16", "documents_of_one_token", "random_2"])
def test_poison_in_a_block_reaches_the_blocks_paired_with_it_and_no_other(layout):
    starts, block_length = layout_of(layout)
    doc, blk = ordinals(starts, T, block_length)
    flash_walk.assert_poison_stays_in_its_pairs(
        lambda q, k, v: flash_attention_blockdiff(q, k, v, doc, blk, SCALE, 16, 16),
        square_grid(doc, blk, 16, 16), *inputs(seed=3), HEADS, KV_HEADS, 16, every=3,
    )  # fmt: skip
