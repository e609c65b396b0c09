"""Power retention (ops/retention.py) against the definition it chunks: the
quadratic form, every pair of a sequence with its decayed, squared score and
nothing carried. XLA's chunked form against it at a tiny head; the two
kernels, interpreted, against it at the published head width, forward and
every gradient, the gate's among them; documents that start inside a chunk,
span several and fill one exactly; a tail that fills no chunk."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from turboprune_tpu.ops import retention, ssd
from turboprune_tpu.utils import tracing


def quadratic(q, k, v, lam, seg, eps=1e-16):
    """o [B, H, G, T, d] of q [B, H, G, T, d], k, v [B, H, T, d], lam [B, T, H]."""
    t, d = q.shape[3], q.shape[-1]
    cum = jnp.moveaxis(jnp.cumsum(lam, axis=1), 2, 1)  # [B, H, T]
    at = jnp.arange(t)
    keep = (seg[:, :, None] == seg[:, None, :]) & (at[:, None] >= at[None, :])
    decay = jnp.exp(jnp.where(keep[:, None], cum[..., :, None] - cum[..., None, :], -jnp.inf))
    a = jnp.einsum("bhgid,bhjd->bhgij", q, k)
    s = a * a / d * decay[:, :, None]
    return jnp.einsum("bhgij,bhje->bhgie", s, v) / (jnp.sum(s, axis=-1)[..., None] + eps)


def operands(bsz, heads, group, t, d, starts, seed=0):
    """Seeded operands; ``starts`` [(sequence, token)] are the documents' first tokens."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(bsz, heads, group, t, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(bsz, heads, t, d)), jnp.float32) for _ in range(2))
    horizon = np.exp(rng.uniform(np.log(8), np.log(512), size=(bsz, t, heads)))
    lam = jnp.asarray(np.log1p(-1.0 / horizon), jnp.float32)
    flags = np.zeros((bsz, t), np.int32)
    for b, at in starts:
        flags[b, at] = 1
    return q, k, v, lam, jnp.asarray(np.cumsum(flags, axis=1), jnp.int32)


def agree(ours, theirs, args, tol):
    """Outputs and the gradients of a seeded weighting of them, all four operands'."""
    weigh = jnp.asarray(np.random.default_rng(5).normal(size=args[0].shape), jnp.float32)
    both = lambda f: jax.jit(
        lambda *a: (f(*a), jax.grad(lambda *a: jnp.sum(f(*a) * weigh), argnums=(0, 1, 2, 3))(*a))
    )
    with jax.default_matmul_precision("highest"):
        (out, grads), (ref, ref_grads) = both(ours)(*args), both(theirs)(*args)
    close = lambda x, y: float(jnp.max(jnp.abs(x - y))) <= tol * float(jnp.max(jnp.abs(y)))
    assert close(out, ref)
    for g, w, name in zip(grads, ref_grads, "q k v lam".split()):
        assert float(jnp.max(jnp.abs(w))) > 0 and close(g, w), name


# Chunks of 16: documents of 5 and 11 tokens (a start inside a chunk), one of
# exactly a chunk (16..31), one over two chunks and a half; the other sequence
# one document over every chunk; 50 tokens, so the last chunk is filled out.
STARTS = [(0, 5), (0, 16), (0, 32)]


def test_xlas_chunked_form_is_the_quadratic_form():
    *args, seg = operands(2, 2, 2, 50, 8, STARTS)
    before = tracing.gauges().get("retention_xla_calls", 0)
    ours = lambda q, k, v, lam: retention.power_retention(q, k, v, lam, seg, chunk=16)
    agree(ours, lambda q, k, v, lam: quadratic(q, k, v, lam, seg), args, 5e-5)
    assert tracing.gauges()["retention_xla_calls"] > before
    assert not retention._takes(8, 16) and not retention._takes(128, 64) and retention._takes(128, 512)


def test_the_kernels_are_the_quadratic_form_at_the_published_head_width():
    """Two query heads on one key/value head, 400 tokens in chunks of 128 (a
    filled tail), a document that starts with a chunk and two inside one."""
    *args, seg = operands(1, 1, 2, 400, 128, [(0, 40), (0, 128), (0, 300)], seed=1)
    before = tracing.gauges().get("retention_kernel_calls", 0)
    ours = lambda q, k, v, lam: retention.power_retention(q, k, v, lam, seg, chunk=128)
    agree(ours, lambda q, k, v, lam: quadratic(q, k, v, lam, seg), args, 5e-5)
    assert tracing.gauges()["retention_kernel_calls"] > before


def test_the_kernels_are_xlas_form_on_two_sequences_of_two_heads():
    *args, seg = operands(2, 2, 1, 256, 128, [(0, 100), (1, 128)], seed=2)
    ours = lambda q, k, v, lam: retention.power_retention(q, k, v, lam, seg, chunk=128)
    xla = lambda q, k, v, lam: retention._retention_xla(q, k, v, lam, seg, 128)
    agree(ours, xla, args, 5e-5)


def test_the_scan_and_retention_walk_the_same_chunks():
    """ops/ssd.py's ``chunk_decays`` is what both name: the decays of a chunk
    whose document began in the chunk before, and of one that starts one."""
    lam = jnp.full((1, 2, 4, 1), -0.5)
    seg = jnp.asarray([[[0, 0, 1, 1], [1, 1, 2, 2]]])
    cum, to_end, from_start, carried = ssd.chunk_decays(lam, seg)
    np.testing.assert_allclose(cum[0, :, :, 0], [[-0.5, -1.0, -1.5, -2.0]] * 2)
    np.testing.assert_allclose(to_end[0, 0, :, 0], [0, 0, np.exp(-0.5), 1.0], rtol=1e-6)
    np.testing.assert_allclose(from_start[0, 1, :, 0], [np.exp(-0.5), np.exp(-1.0), 0, 0], rtol=1e-6)
    assert from_start[0, 0].tolist() == [[0.0]] * 4  # nothing precedes the sequence
    assert carried[0, :, 0].tolist() == [0.0, 0.0]  # neither chunk ends in the document it began in
    assert ssd.NO_DOCUMENT == -2 and retention.PADDING is ssd.PADDING
