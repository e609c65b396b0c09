"""The chunked state-space-dual scan (ops/ssd.py) against the recurrence it
stands for, one token at a time (benchmarks/reference/granite.py), forward
and gradient, with document starts inside chunks, on chunk borders, next to
each other, and with a sequence shorter than a chunk."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import granite as reference
from turboprune_tpu.ops import ssd

ssd_chunked = jax.jit(ssd.ssd_chunked, static_argnums=6)  # op by op it is a hundred compilations

CHUNK = 8
CASES = {
    "starts_inside_chunks": (40, (5, 19, 30)),
    "starts_on_chunk_borders": (40, (8, 16, 32)),
    "starts_next_to_each_other": (40, (7, 8, 9, 24, 25)),
    "one_document": (40, ()),
    "shorter_than_a_chunk": (5, (2,)),
    "a_tail_that_fills_no_chunk": (19, (8, 9)),
}


def _inputs(t, starts, seed=0, batch=2, heads=3, p=4, n=5):
    kx, kdt, ka, kb, kc = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(kx, (batch, t, heads, p))
    dt = jax.nn.softplus(jax.random.normal(kdt, (batch, t, heads)) - 1.0)
    a = -jnp.exp(jax.random.normal(ka, (heads,)))
    b = jax.random.normal(kb, (batch, t, n))
    c = jax.random.normal(kc, (batch, t, n))
    flags = np.zeros((batch, t), np.int32)
    flags[0, list(starts)] = 1  # the second sequence is one document
    seg = jnp.asarray(np.cumsum(flags, axis=1))
    return (x, dt, a, b, c), seg


def _token_by_token(args, seg, train=False):
    start = jnp.concatenate([jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    return _recurrence(*args, start, train)


_recurrence = jax.jit(reference._recurrence, static_argnums=6)


@pytest.mark.parametrize("case", list(CASES))
def test_chunked_equals_token_by_token(case):
    args, seg = _inputs(*CASES[case])
    with jax.default_matmul_precision("highest"):
        want = _token_by_token(args, seg)
        got = ssd_chunked(*args, seg, CHUNK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6)


@pytest.mark.parametrize("case", list(CASES))
def test_gradients_equal_token_by_token(case):
    args, seg = _inputs(*CASES[case], seed=1)
    weigh = lambda y: jnp.sum(jnp.sin(y))
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda *a: weigh(_token_by_token(a, seg, train=True)), argnums=range(5))(*args)
        got = jax.grad(lambda *a: weigh(ssd_chunked(*a, seg, CHUNK)), argnums=range(5))(*args)
    for name, g, w in zip("x dt a b c".split(), got, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5, err_msg=name)


def test_a_document_start_cuts_the_state():
    """What follows a start does not depend on what came before it."""
    args, seg = _inputs(24, (10,))
    x, dt, a, b, c = args
    other = x.at[:, :10].set(7.0)
    y0 = ssd_chunked(x, dt, a, b, c, seg, CHUNK)
    y1 = ssd_chunked(other, dt, a, b, c, seg, CHUNK)
    np.testing.assert_array_equal(np.asarray(y0[0, 10:]), np.asarray(y1[0, 10:]))
    assert not np.allclose(np.asarray(y0[1, 10:]), np.asarray(y1[1, 10:]))  # one document: it does


def test_bf16_operands_keep_a_float32_state():
    args, seg = _inputs(40, (5, 19, 30))
    x, dt, a, b, c = args
    want = _token_by_token(args, seg)
    got = ssd_chunked(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16), c.astype(jnp.bfloat16), seg, CHUNK)
    assert got.dtype == jnp.bfloat16
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    assert float(np.median(err)) < 0.03 and float(err.max()) < 0.5
