"""The block-diffusion path of the Pallas flash kernel
(ops/flash.py::flash_attention_blockdiff) against a dense oracle built from
the rule, forward and the three gradients, in interpret mode; and the causal
call, whose bodies this path shares and may not have changed."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from turboprune_tpu.ops.flash import (
    _blockdiff_bounds,
    flash_attention_blockdiff,
    flash_attention_causal,
)

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


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("blocks", [(16, 16), (64, 64), (16, 32), (32, 16)])
def test_forward_equals_the_rule(layout, blocks):
    starts, block_length = LAYOUTS[layout]
    doc, blk = ordinals(starts, T, block_length)
    q, k, v = inputs()
    with jax.default_matmul_precision("highest"):
        got = flash_attention_blockdiff(q, k, v, doc, blk, SCALE, *blocks)
        want = plain(q, k, v, rule(doc, blk), SCALE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("layout", ["packed", "blocks_of_3"])
@pytest.mark.parametrize("blocks", [(16, 16), (32, 16)])
def test_gradients_equal_the_rule(layout, blocks):
    starts, block_length = LAYOUTS[layout]
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


def test_the_causal_call_is_the_program_it_was():
    """The causal family's three bodies are now functions this path calls
    too: its lowered program (forward and the three gradients, interpret
    mode) hashes to what the commit before gave."""
    rng = np.random.default_rng(20261001)
    q = jnp.asarray(rng.normal(size=(4, 32, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, 32, 8)), jnp.float32) for _ in range(2))
    seg = jnp.asarray(np.cumsum(np.isin(np.arange(32), [5, 16]))[None], jnp.int32)
    f = lambda q, k, v: flash_attention_causal(q, k, v, seg, 0.35, 16, 8)
    g = jax.jit(lambda q, k, v: jax.grad(lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v))), argnums=(0, 1, 2))(q, k, v))
    text = g.lower(q, k, v).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "edf025376cf91099562cee5e1965f8ffd5511a94d0e42064b5ff5e03ed9adf26"
    )
