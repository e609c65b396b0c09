"""The chunked state-space-dual scan (ops/ssd.py) against the recurrence it
stands for, one token at a time (benchmarks/reference/granite.py), forward
and gradient, with document starts inside chunks, on chunk borders, next to
each other, and with a sequence shorter than a chunk: XLA's form at shapes
the kernels do not take, the kernels (interpreted) at the smallest they do,
and the kernels against XLA's form on the same inputs."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import granite as reference
from turboprune_tpu.ops import ssd
from turboprune_tpu.utils import tracing

ssd_chunked = jax.jit(ssd.ssd_chunked, static_argnums=6)  # op by op it is a hundred compilations

CHUNK = 8
SMALL = dict(heads=3, p=4, n=5)  # XLA's form
TILED = dict(chunk=128, heads=4, p=64, n=128)  # the kernels': one tile a chunk, two heads a lane column
CASES = {
    "starts_inside_chunks": (40, (5, 19, 30), SMALL),
    "starts_on_chunk_borders": (40, (8, 16, 32), SMALL),
    "starts_next_to_each_other": (40, (7, 8, 9, 24, 25), SMALL),
    "one_document": (40, (), SMALL),
    "shorter_than_a_chunk": (5, (2,), SMALL),
    "a_tail_that_fills_no_chunk": (19, (8, 9), SMALL),
    # Three chunks, the last a tail of 44 tokens; starts inside a chunk, on a
    # chunk border, next to each other.
    "kernels": (300, (5, 128, 129, 200), TILED),
    "kernels_two_tiles_a_chunk": (300, (5, 256, 257), dict(TILED, chunk=256, heads=2)),
    "kernels_two_groups": (300, (5, 128, 129, 200), dict(TILED, groups=2)),
}
KERNEL_CASES = [case for case, (_, _, shape) in CASES.items() if "chunk" in shape]


def _inputs(t, starts, shape=SMALL, seed=0, batch=2):
    heads, p, n, groups = shape["heads"], shape["p"], shape["n"], shape.get("groups")
    kx, kdt, ka, kb, kc = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(kx, (batch, t, heads, p))
    dt = jax.nn.softplus(jax.random.normal(kdt, (batch, t, heads)) - 1.0)
    a = -jnp.exp(jax.random.normal(ka, (heads,)))
    bc_shape = (batch, t, n) if groups is None else (batch, t, groups, n)
    width = 1.0 if n < 128 else n**-0.5  # scores of unit size at the kernels' width too
    b = width * jax.random.normal(kb, bc_shape)
    c = width * jax.random.normal(kc, bc_shape)
    flags = np.zeros((batch, t), np.int32)
    flags[0, list(starts)] = 1  # the second sequence is one document
    seg = jnp.asarray(np.cumsum(flags, axis=1))
    if "chunk" in shape:
        assert ssd._head_block(heads // (groups or 1), p, n, shape["chunk"])  # a shape the kernels take
    return (x, dt, a, b, c), seg, shape.get("chunk", CHUNK)


def _token_by_token(args, seg, train=False):
    start = jnp.concatenate([jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    x, dt, a, b, c = args
    if b.ndim == 3:
        return _recurrence(*args, start, train)
    per = x.shape[2] // b.shape[2]  # each group is a scan of its own heads
    heads = lambda g: slice(g * per, (g + 1) * per)
    return jnp.concatenate(
        [
            _recurrence(x[:, :, heads(g)], dt[:, :, heads(g)], a[heads(g)], b[:, :, g], c[:, :, g], start, train)
            for g in range(b.shape[2])
        ],
        axis=2,
    )


def _xla_form(fn, *args):
    """``fn`` traced anew with every shape refused by the kernels."""
    with mock.patch.object(ssd, "_head_block", return_value=0):
        return jax.jit(lambda *a: fn(*a))(*args)


_recurrence = jax.jit(reference._recurrence, static_argnums=6)


@pytest.mark.parametrize("case", list(CASES))
def test_chunked_equals_token_by_token(case):
    args, seg, chunk = _inputs(*CASES[case])
    with jax.default_matmul_precision("highest"):
        want = _token_by_token(args, seg)
        got = ssd_chunked(*args, seg, chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6)


def _gradients(scan, args):
    weigh = lambda y: jnp.sum(jnp.sin(y))
    return jax.grad(lambda *a: weigh(scan(a)), argnums=range(5))(*args)


def _close(got, want, case):
    """``a``'s gradient is one sum a head over every token and lane: at the
    kernels' shapes (600 tokens of 64 lanes) float32 rounds it ten times as
    far as at the small one, in XLA's form as in the kernels."""
    for name, g, w in zip("x dt a b c".split(), got, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        atol = 5e-4 if name == "a" and case in KERNEL_CASES else 5e-5
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=atol, err_msg=name)


@pytest.mark.parametrize("case", list(CASES))
def test_gradients_equal_token_by_token(case):
    args, seg, chunk = _inputs(*CASES[case], seed=1)
    with jax.default_matmul_precision("highest"):
        want = _gradients(lambda a: _token_by_token(a, seg, train=True), args)
        got = _gradients(lambda a: ssd_chunked(*a, seg, chunk), args)
    _close(got, want, case)


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_kernels_equal_xla_form(case):
    """The same inputs through the kernels and through XLA's products, forward
    and the five gradients."""
    args, seg, chunk = _inputs(*CASES[case], seed=2)
    scan = lambda *a: ssd.ssd_chunked(*a, seg, chunk)
    np.testing.assert_allclose(np.asarray(ssd_chunked(*args, seg, chunk)), np.asarray(_xla_form(scan, *args)), atol=5e-6)
    got = jax.jit(lambda *a: _gradients(lambda a: scan(*a), a))(*args)
    want = _xla_form(lambda *a: _gradients(lambda a: scan(*a), a), *args)
    _close(got, want, case)


def test_a_document_start_cuts_the_state():
    """What follows a start does not depend on what came before it."""
    args, seg, _ = _inputs(24, (10,))
    x, dt, a, b, c = args
    other = x.at[:, :10].set(7.0)
    y0 = ssd_chunked(x, dt, a, b, c, seg, CHUNK)
    y1 = ssd_chunked(other, dt, a, b, c, seg, CHUNK)
    np.testing.assert_array_equal(np.asarray(y0[0, 10:]), np.asarray(y1[0, 10:]))
    assert not np.allclose(np.asarray(y0[1, 10:]), np.asarray(y1[1, 10:]))  # one document: it does


@pytest.mark.parametrize("case", ["starts_inside_chunks", "kernels"])
def test_bf16_operands_keep_a_float32_state(case):
    args, seg, chunk = _inputs(*CASES[case])
    x, dt, a, b, c = args
    want = _token_by_token(args, seg)
    got = ssd_chunked(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16), c.astype(jnp.bfloat16), seg, chunk)
    assert got.dtype == jnp.bfloat16
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    assert float(np.median(err)) < 0.03 and float(err.max()) < 0.5


@pytest.mark.parametrize(
    "heads, p, n, chunk, block",
    [
        (64, 64, 128, 256, 16),  # granite-4.0-h-micro: four blocks of 16 heads a chunk
        (16, 64, 128, 128, 16),  # one chip's group of Nemotron-3-Super: all of them
        (128, 64, 128, 128, 32),
        (4, 64, 128, 128, 4),
        (12, 64, 128, 1024, 4),  # all twelve do not fit
        (3, 64, 128, 128, 0),  # two heads a lane column, an odd one left
        (8, 128, 128, 128, 8),  # a head a lane column
        (3, 4, 5, 8, 0),  # these tests' small shape
        (64, 64, 128, 64, 0),  # a chunk shorter than a tile
        (64, 32, 128, 256, 0),
        (64, 64, 64, 256, 0),
    ],
)
def test_the_head_block_follows_the_shape(heads, p, n, chunk, block):
    assert ssd._head_block(heads, p, n, chunk) == block


def test_a_traced_scan_counts_its_form():
    """``ssd_kernel_calls`` / ``ssd_xla_calls``: one for every scan traced, by
    the form it was lowered to; a run of the compiled program counts nothing."""
    calls = lambda: [tracing.gauges().get(k, 0) for k in ("ssd_kernel_calls", "ssd_xla_calls")]
    for case, form in (("kernels", 0), ("starts_inside_chunks", 1), ("kernels_two_groups", 0)):
        args, seg, chunk = _inputs(*CASES[case])
        scan = jax.jit(lambda *a: ssd.ssd_chunked(*a, seg, chunk))
        before = calls()
        scan(*args)
        after = calls()
        assert [n - m for n, m in zip(after, before)] == [1 - form, form], case
        scan(*args)
        assert calls() == after
