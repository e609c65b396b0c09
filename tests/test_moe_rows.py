"""How rows travel between the token array and the pair buffer
(ops/moe.py::take_rows, add_rows): XLA's gather one way, the Pallas row
kernel (interpreted here) the other, against ``src[idx]`` and
``zeros.at[idx].add`` in numpy, each against the other as its transpose, and
which form a shape takes.

A row moves in whole (8, 128) tiles of 32-bit words, so the narrowest rows
the kernel takes are 1,024 float32 values and 2,048 bfloat16 ones (a pair to
a word)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from turboprune_tpu.ops import moe
from turboprune_tpu.utils import tracing

TOKENS, BUFFER = 384, 512  # three tiles of destinations, four of buffer rows


def _indices(seed=0):
    """[BUFFER] destinations, negative where a row has none: a tile's rows
    a prefix of pairs and then rows that only align, one token hit 8 times, a
    whole tile of the buffer empty, tokens 128-255 (a whole tile of
    destinations) reached by nothing, and the last stretch part-filled."""
    rng = np.random.default_rng(seed)
    idx = np.full(BUFFER, -1, np.int32)
    idx[:100] = rng.integers(0, 128, 100)
    idx[128:200] = rng.integers(256, TOKENS, 72)
    idx[200:208] = 300  # top_k = 8 pairs of one token
    idx[384:389] = [383, 0, 383, 127, 256]
    idx[450] = 5  # not a prefix: the functions take any order
    return idx


def _operands(dtype, width, seed=0):
    rng = np.random.default_rng(seed)
    idx = _indices(seed)
    src = jnp.asarray(rng.normal(size=(TOKENS, width)), dtype)
    upd = jnp.asarray(rng.normal(size=(BUFFER, width)), dtype)
    weights = jnp.asarray(np.where(idx >= 0, rng.uniform(0.1, 1.0, BUFFER), 0), jnp.float32)
    valid, rows = jnp.asarray(idx >= 0), jnp.asarray(np.maximum(idx, 0))
    return idx, src, upd, weights, valid, rows, moe.by_destination(jnp.asarray(idx), TOKENS, weights)


def _f64(x):
    return np.asarray(x.astype(jnp.float32), np.float64)


def _added(upd, idx, weights=None):
    """``zeros.at[idx].add(upd * weights)`` in float64."""
    out = np.zeros((TOKENS, upd.shape[1]))
    terms = _f64(upd) if weights is None else _f64(upd) * np.asarray(weights, np.float64)[:, None]
    np.add.at(out, idx[idx >= 0], terms[idx >= 0])
    return out


SHAPES = [(jnp.float32, 1024), (jnp.float32, 2048), (jnp.bfloat16, 2048), (jnp.bfloat16, 4096)]
IDS = ["f32_1024", "f32_2048", "bf16_2048", "bf16_4096"]


@pytest.mark.parametrize("dtype, width", SHAPES, ids=IDS)
def test_take_rows_is_the_gather_of_the_rows_asked_for(dtype, width):
    idx, src, _, _, valid, rows, by_dest = _operands(dtype, width)
    got = moe.take_rows(src, rows, valid, by_dest)
    assert got.dtype == dtype and got.shape == (BUFFER, width)
    np.testing.assert_array_equal(_f64(got)[idx >= 0], _f64(src)[idx[idx >= 0]])  # rows are moved, not computed
    # A row nothing asks for: zeros in XLA's form, a copy of the row it points at here.
    assert not _f64(moe.take_rows(src, rows, valid))[idx < 0].any()
    np.testing.assert_array_equal(_f64(got)[idx < 0], _f64(src)[np.asarray(rows)[idx < 0]])


@pytest.mark.parametrize("dtype, width", SHAPES[:2], ids=IDS[:2])
def test_add_rows_is_the_weighted_scatter_add_in_float32(dtype, width):
    idx, _, upd, weights, valid, rows, by_dest = _operands(dtype, width)
    got = moe.add_rows(upd, rows, valid, TOKENS, weights, by_dest)
    assert got.dtype == jnp.float32 and got.shape == (TOKENS, width)
    want = _added(upd, idx, weights)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6 * np.abs(want).max())
    assert not np.asarray(got[128:256]).any()  # the destinations no row reaches
    xla = moe.add_rows(upd, rows, valid, TOKENS, weights)
    np.testing.assert_allclose(got, xla, rtol=0, atol=2e-6 * np.abs(want).max())


@pytest.mark.parametrize("dtype, width", SHAPES, ids=IDS)
def test_each_is_the_others_transpose(dtype, width):
    """``jax.grad`` through ``take_rows`` is ``add_rows`` of the cotangent
    (the kernel: accumulated in float32, cast once to the source's dtype),
    and through ``add_rows`` it is ``take_rows`` of the cotangent, times the
    weights for the rows and summed against the rows for the weights."""
    idx, src, upd, weights, valid, rows, by_dest = _operands(dtype, width, seed=1)
    rng = np.random.default_rng(2)
    ct_rows = jnp.asarray(rng.normal(size=(BUFFER, width)), dtype)
    taken, pull = jax.vjp(lambda s: moe.take_rows(s, rows, valid, by_dest), src)
    (d_src,) = pull(ct_rows)
    assert d_src.dtype == dtype
    want = jnp.asarray(_added(ct_rows, idx), jnp.float32).astype(dtype)  # one rounding, the last
    np.testing.assert_allclose(_f64(d_src), _f64(want), rtol=0, atol=1e-6 * float(jnp.abs(want.astype(jnp.float32)).max()))
    if dtype == jnp.float32:
        ct_tokens = jnp.asarray(rng.normal(size=(TOKENS, width)), jnp.float32)
        _, pull = jax.vjp(lambda u, w: moe.add_rows(u, rows, valid, TOKENS, w, by_dest), upd, weights)
        d_upd, d_weights = pull(ct_tokens)
        back = _f64(moe.take_rows(ct_tokens, rows, valid))  # zeros where no row is asked
        np.testing.assert_allclose(d_upd, back * np.asarray(weights, np.float64)[:, None], rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(d_weights, np.sum(back * _f64(upd), axis=1), rtol=1e-5, atol=1e-4)
        assert not np.asarray(d_upd)[idx < 0].any() and not np.asarray(d_weights)[idx < 0].any()


@pytest.mark.parametrize(
    "dtype, width, rows, tokens, tile, kernels",
    [
        (jnp.bfloat16, 2048, 26624, 16384, 128, True),  # the block-diffusion cell's dispatch, backward
        (jnp.float32, 2048, 13312, 8192, 128, True),  # the convolution-hybrid cell's combine
        (jnp.float32, 1024, 10496, 8192, 128, True),  # the sparse-expert cell's combine
        (jnp.bfloat16, 1024, 10496, 8192, 128, False),  # and its dispatch: half a tile of words a row
        (jnp.float32, 32, 128, 64, 128, False),  # the tiny models' widths
        (jnp.float32, 1024, 128, 64, 128, False),  # tokens that are not whole tiles
        (jnp.float32, 1024, 128, 128, 8, False),  # SMALL_TILE
        (jnp.float16, 2048, 128, 128, 128, False),
    ],
)
def test_rows_move_in_tiles_where_a_row_is_whole_tiles_of_words(dtype, width, rows, tokens, tile, kernels):
    assert moe.rows_move_in_tiles(dtype, width, rows, tokens, tile) == kernels


def test_a_traced_call_counts_its_form():
    """``moe_row_kernel_calls`` / ``moe_row_xla_calls``: one for every call of
    either function traced, by the form it took (the kernel where the caller
    hands it the rows sorted by destination, which ``routed_experts`` does
    where ``rows_move_in_tiles``); a run of the compiled program counts
    nothing."""
    calls = lambda: [tracing.gauges().get(k, 0) for k in ("moe_row_kernel_calls", "moe_row_xla_calls")]
    _, src, upd, weights, valid, rows, by_dest = _operands(jnp.float32, 1024)
    for form, by_dest in enumerate((by_dest, None)):
        both = jax.jit(
            lambda src, upd, weights: (
                moe.take_rows(src, rows, valid, by_dest),
                moe.add_rows(upd, rows, valid, TOKENS, weights, by_dest),
            )
        )
        before = calls()
        both(src, upd, weights)
        after = calls()
        assert [n - m for n, m in zip(after, before)] == [2 * (1 - form), 2 * form]
        both(src, upd, weights)
        assert calls() == after
