"""What tests/test_flash_causal.py and tests/test_flash_blockdiff.py ask of
the packed attention kernels' walk (ops/flash.py): the words list exactly the
pairs of a square of blocks, in its row-major order, under the right flags;
and poison in one block reaches the blocks paired with it and no other."""

import jax
import jax.numpy as jnp
import numpy as np

from turboprune_tpu.ops.flash import _FIRST, _LAST, _RUN, _by_key, _pair, _walk


def assert_lists(pairs, square, group):
    """``pairs`` [B, nq, nk], what the rule under test said runs; ``square``
    the same as a numpy oracle has it. Both walks of ``pairs``, the forward
    and dq kernels' and the dkv kernel's over ``group`` query heads a
    key/value head, against ``square``; returns the pairs a batch row."""
    np.testing.assert_array_equal(np.asarray(pairs), square)
    bsz, nq, nk = square.shape
    by_key = np.tile(square.transpose(0, 2, 1), (1, 1, group))
    for want, walk, by in ((square, _walk(pairs), "query"), (by_key, _walk(_by_key(pairs, group)), "key")):
        words, n_run = (np.asarray(x) for x in walk)
        words = words.reshape(bsz, -1)
        assert words.shape[1] == want[0].size, by  # as long as the square, which no list outgrows
        outer_of, inner_of = (np.asarray(x) for x in _pair(words))
        first, last, run = ((words & flag) != 0 for flag in (_FIRST, _LAST, _RUN))
        for b in range(bsz):
            # Row-major over the square: query-major with key blocks ascending;
            # key-major, then the group's heads, then query blocks ascending.
            outer, inner = np.nonzero(want[b])
            n = len(outer)
            assert n_run[b] == n, by
            np.testing.assert_array_equal(outer_of[b, :n], outer, err_msg=by)
            np.testing.assert_array_equal(inner_of[b, :n], inner, err_msg=by)
            # Every block of the result has a pair, is zeroed at its first and written at its last.
            assert set(outer) == set(range(want.shape[1])), by
            np.testing.assert_array_equal(first[b, :n], np.r_[True, outer[1:] != outer[:-1]], err_msg=by)
            np.testing.assert_array_equal(last[b, :n], np.r_[outer[1:] != outer[:-1], True], err_msg=by)
            # The tail repeats the last pair without the flags: nothing is fetched, run or written.
            assert run[b, :n].all() and not (run | first | last)[b, n:].any(), by
            assert (outer_of[b, n:] == outer[-1]).all() and (inner_of[b, n:] == inner[-1]).all(), by
    return square.sum(axis=(1, 2))


def assert_poison_stays_in_its_pairs(attend, runs, q, k, v, heads, kv_heads, block, every=1):
    """``attend(q, k, v)`` with blocks of ``block`` rows both ways, ``runs``
    [B, nq, nk] its pairs. NaN in one key block's keys and values reaches
    exactly the query blocks paired with it (the output and dq), and NaN in
    one query block's queries exactly the key blocks paired with it (dk and
    dv): no kernel reads a block its walk does not list. The batch's rows must
    walk lists of different lengths, so that the shorter row's tail is among
    the steps that have to read nothing new."""
    bsz = runs.shape[0]
    assert len(set(runs.sum(axis=(1, 2)))) == bsz
    by_block = lambda x, n: np.asarray(x).reshape(bsz, n, -1, block, x.shape[-1])
    forward = jax.jit(attend)
    grads = jax.jit(jax.grad(lambda q, k, v: jnp.sum(jnp.sin(attend(q, k, v))), argnums=(0, 1, 2)))
    clean_o, (clean_dq, clean_dk, clean_dv) = forward(q, k, v), grads(q, k, v)
    for at in range(0, runs.shape[2], every):
        rows = slice(at * block, (at + 1) * block)
        o = forward(q, k.at[:, rows].set(jnp.nan), v.at[:, rows].set(jnp.nan))
        dq, _, _ = grads(q, k.at[:, rows].set(jnp.nan), v.at[:, rows].set(jnp.nan))
        _, dk, dv = grads(q.at[:, rows].set(jnp.nan), k, v)
        for b in range(bsz):
            for got, clean, n, spared in (
                (o, clean_o, heads, ~runs[b, :, at]),
                (dq, clean_dq, heads, ~runs[b, :, at]),
                (dk, clean_dk, kv_heads, ~runs[b, at, :]),
                (dv, clean_dv, kv_heads, ~runs[b, at, :]),
            ):
                got, clean = by_block(got, n)[b], by_block(clean, n)[b]
                np.testing.assert_array_equal(got[:, spared], clean[:, spared])
                assert np.isnan(got[:, ~spared]).any(axis=(0, 2, 3)).all()
