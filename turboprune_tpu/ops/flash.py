"""First-party Pallas TPU flash attention (forward + backward kernels).

Three entry points over the same recurrence. ``flash_attention``: non-causal
multi-head attention with one key-validity mask shared by the batch (the ViT
path; described first). ``flash_attention_causal``: causal attention inside
packed documents with grouped key/value heads (the next-token language
models' path; its kernels are the second part of this file and say what
differs). ``flash_attention_blockdiff``: the block-diffusion mask over a clean
and a noised copy of every packed sequence (models/sdar.py; the third part).
The two packed families share the three bodies of the recurrence
(``_fwd_step``, ``_dq_step``, ``_dkv_step``), the three kernels around them
and the three calls of those, and differ in which (query, key) pairs a body
is told to keep and which blocks of scores it is run for. Their grids are not
the square of blocks: the inner axis walks the list of (query block, key
block) pairs that run, read from scalar prefetch ("the packed families'
walk", before the second part).

Attention is computed blockwise so the S x S score matrix never materializes in HBM: for each
query block the kernel streams key/value blocks through VMEM, carrying the
online-softmax running max/sum in VMEM scratch across the (sequential)
innermost grid dimension — the flash-attention recurrence on the hardware
it was shaped for (MXU matmuls with fp32 accumulators, VPU for the
exp/max/sum, ~(BLOCK x BLOCK) live scores).

The backward pass is two more Pallas kernels over the same blocks
(recompute-based, flash2-style): residuals are just (o, logsumexp), so
training memory stays O(S) per head instead of O(S^2).

Relationship to the rest of the framework:
  - models/vit.py wires this as ``attention_impl: "flash"`` — single-device
    blockwise attention with the SAME param tree as dense/ring.
  - parallel/ring.py is the multi-device complement (sequence sharded over
    the mesh, K/V rotating by ppermute); flash is the within-device answer.
  - The reference has no analog: its DeiT path runs timm's dense attention
    (materialized scores) and was dead code anyway (SURVEY.md §2.1).

On the CPU backend, and only there, the kernels run in Pallas interpret
mode (exact same program, executed by XLA ops) — which is how the CPU test
suite proves them, including gradients, against a dense jnp oracle. On any
other backend the kernels are compiled by Mosaic, and a lowering failure
raises: nothing here re-routes to interpret mode or to dense attention.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_BIG = -1e30


def _use_interpret() -> bool:
    """Interpret only where no Mosaic compiler exists at all: the CPU
    backend of the test suite. Every other backend compiles or raises."""
    return jax.default_backend() == "cpu"


def _dot(a, b):  # [m, k] @ [k, n] with fp32 accumulation on the MXU
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_t0(a, b):  # contract dim 0 of both: [k, m] x [k, n] -> [m, n]
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_t1(a, b):  # contract dim 1 of both: [m, k] x [n, k] -> [m, n]
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


# ------------------------------------------------------------------ forward
def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, acc, m, l, *,
                scale: float):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, -jnp.inf)
        l[:] = jnp.zeros_like(l)

    q = q_ref[0]  # [Bq, D]
    k = k_ref[0]  # [Bk, D]
    valid = mask_ref[0] > 0  # [Bk]
    s = _dot_t1(q.astype(jnp.float32) * scale, k.astype(jnp.float32))
    s = jnp.where(valid[None, :], s, NEG_BIG)

    m_old = m[:]  # [Bq, 1]
    m_new = jnp.maximum(m_old, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new) * valid[None, :]
    corr = jnp.exp(m_old - m_new)
    l[:] = l[:] * corr + p.sum(axis=1, keepdims=True)
    acc[:] = acc[:] * corr + _dot(p.astype(v_ref.dtype), v_ref[0])
    m[:] = m_new

    @pl.when(ki == nk - 1)
    def _():
        lsafe = jnp.maximum(l[:], 1e-30)
        o_ref[0] = (acc[:] / lsafe).astype(o_ref.dtype)
        lse_ref[0] = m[:] + jnp.log(lsafe)


def _flash_fwd(q, k, v, mask, scale, block_q, block_k, interpret):
    bh, s_len, d = q.shape
    nq, nk = s_len // block_q, s_len // block_k
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k), lambda b, qi, ki: (0, ki)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_len, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s_len, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, mask)
    return o, lse


# ----------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, drow_ref,
               dq_ref, dq_acc, *, scale: float):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    valid = mask_ref[0] > 0
    s = _dot_t1(q * scale, k)
    s = jnp.where(valid[None, :], s, NEG_BIG)
    p = jnp.exp(s - lse_ref[0]) * valid[None, :]  # [Bq, Bk]
    dp = _dot_t1(do_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32))
    ds = p * (dp - drow_ref[0]) * scale  # [Bq, Bk]
    dq_acc[:] = dq_acc[:] + _dot(ds, k)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, drow_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float):
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    valid = mask_ref[0] > 0
    s = _dot_t1(q * scale, k)
    s = jnp.where(valid[None, :], s, NEG_BIG)
    p = jnp.exp(s - lse_ref[0]) * valid[None, :]  # [Bq, Bk]
    dv_acc[:] = dv_acc[:] + _dot_t0(p, do)  # [Bk, D]
    dp = _dot_t1(do, v_ref[0].astype(jnp.float32))
    ds = p * (dp - drow_ref[0]) * scale
    dk_acc[:] = dk_acc[:] + _dot_t0(ds, q)  # [Bk, D]

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# ------------------------------------------------------------------- public
@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7)
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_valid: jax.Array,
    scale: float,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Blockwise (flash) attention. q/k/v: [batch*heads, seq, head_dim];
    ``block_q``/``block_k`` must divide ``seq`` (pad the sequence up to a
    block multiple first — models/vit.py FlashSelfAttention does). kv_valid:
    [1, seq] (0/1) marking real key rows. Returns the same shape as q."""
    o, _ = _fa_fwd(q, k, v, kv_valid, scale, block_q, block_k, interpret)
    return o


def _fa_fwd(q, k, v, kv_valid, scale, block_q, block_k, interpret):
    s_len = q.shape[1]
    if s_len % block_q or s_len % block_k:
        raise ValueError(
            f"flash_attention: seq {s_len} must be a multiple of "
            f"block_q={block_q} and block_k={block_k} — pad the sequence "
            "(the grid floor-divides and would silently drop the tail)"
        )
    if kv_valid.shape != (1, s_len):
        raise ValueError(
            f"flash_attention: kv_valid must have shape (1, {s_len}), got "
            f"{kv_valid.shape} — the mask is shared across the batch "
            "(a per-example mask would be silently ignored)"
        )
    if interpret is None:
        interpret = _use_interpret()
    mask = kv_valid.astype(jnp.float32)
    o, lse = _flash_fwd(q, k, v, mask, scale, block_q, block_k, interpret)
    return o, (q, k, v, mask, o, lse)


def _fa_bwd(scale, block_q, block_k, interpret, residuals, g):
    if interpret is None:
        interpret = _use_interpret()
    q, k, v, mask, o, lse = residuals
    bh, s_len, d = q.shape
    nq, nk = s_len // block_q, s_len // block_k
    # D_i = sum_d do * o — per (row) softmax-derivative correction term.
    drow = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                   keepdims=True)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k), lambda b, qi, ki: (0, ki)),
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, mask, g, lse, drow)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k), lambda b, ki, qi: (0, ki)),
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, ki, qi: (b, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, mask, g, lse, drow)
    return dq, dk, dv, None


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ------------------------------------------------ the packed families' walk
# Both packed families below mask most of the square of (query block, key
# block) pairs whole: the causal order and the documents' extents in one, the
# block-diffusion rule in the other. Their kernels do not visit that square.
# Each rule says on the device which pairs run (``_runs``, ``_interval_runs``:
# a [batch, query blocks, key blocks] matrix), ``_walk`` lists them in the
# order the square grid met them, and the kernels' inner grid axis is that
# list: step ``s`` of a batch row reads its pair from scalar prefetch, every
# BlockSpec's index map reads the same word, and a block of scores that does
# not run is never fetched. The forward and dq kernels walk query-major (a
# query block's key blocks ascending), the dkv kernel key-major (a key block's
# query heads of the group, and each head's query blocks ascending), so a
# block's pairs are accumulated in the order they always were.
#
# A step's word holds the pair and three flags: the pair is the first of its
# outer block (the accumulators are zeroed), the last (the result is written),
# a pair at all. **Every query block and every key block has at least one
# pair** (a row keeps itself under both rules, so the diagonal runs), hence
# every block of every result is zeroed and written once. The inner axis is as
# long as the batch's longest list (a traced grid bound: Mosaic and interpret
# mode both take one), never a guess at the layout; a batch row whose list is
# shorter repeats its last pair's word without the flags, which fetches
# nothing new, writes nothing and skips the body. The words' array is as long
# as the square, which no list can outgrow.
#
# The three ``pallas_call``s are written once (``_packed_fwd``,
# ``_packed_bwd``) over what a family supplies as a ``_Family``: its kernels'
# names, its set-up, its rule and the two operands the rule reads.
_FIRST, _LAST, _RUN = 1 << 28, 1 << 29, 1 << 30
_OUTER_BITS, _INNER_BITS = 12, 16


def _walk(pairs):
    """``pairs`` [B, outer, inner] bool -> (words [B * outer * inner] int32,
    the pairs of each batch row [B] int32). Word ``b * outer * inner + s`` is
    the ``s``-th true entry of ``pairs[b]`` in row-major order, as ``outer <<
    16 | inner`` under the three flags; from the row's count on, the last
    one's without flags."""
    bsz, n_outer, n_inner = pairs.shape
    if n_outer > 1 << _OUTER_BITS or n_inner > 1 << _INNER_BITS:
        raise ValueError(f"flash attention: a grid of {n_outer} x {n_inner} blocks outgrows the walk's word")
    n = n_outer * n_inner
    rank = jnp.cumsum(pairs.reshape(bsz, n), axis=1, dtype=jnp.int32)
    n_run = rank[:, -1]
    s = jnp.arange(n, dtype=jnp.int32)
    # Where the (s + 1)-th pair lies in the square: the entries ranked s or lower.
    at = jax.vmap(functools.partial(jnp.searchsorted, side="right", method="compare_all"))(
        rank, jnp.minimum(s, n_run[:, None] - 1)
    ).astype(jnp.int32)
    outer, run = at // n_inner, s < n_run[:, None]
    first = run & ((s == 0) | (outer != jnp.roll(outer, 1, axis=1)))
    last = run & ((s == n_run[:, None] - 1) | (outer != jnp.roll(outer, -1, axis=1)))
    words = outer << _INNER_BITS | at % n_inner | first * _FIRST | last * _LAST | run * _RUN
    return words.reshape(-1), n_run


def _pair(word):
    return (word >> _INNER_BITS) & ((1 << _OUTER_BITS) - 1), word & ((1 << _INNER_BITS) - 1)


def _reader(rows, n):
    """``(b, s, walk) -> word`` of grid step ``s`` of kernel row ``b``, where
    ``rows`` kernel rows share a batch row's ``n`` words."""
    return lambda b, s, walk: walk[(b // rows) * n + s]


def _query_major(heads, group, n, block_q, block_k, d):
    """For the forward and dq kernels, whose rows are query heads and whose
    words hold (query block, key block): ``at(b, s, walk) -> (batch row, qi,
    ki)`` of a grid step, and the BlockSpecs of the operands that both
    families have: a query block's [Bq, d] and [Bq, 1], a key/value block of
    the head's group."""
    word = _reader(heads, n)
    at = lambda b, s, w: (b // heads, *_pair(word(b, s, w)))
    q_at = lambda b, s, w: (b, at(b, s, w)[1], 0)
    q_rows, q_col = pl.BlockSpec((1, block_q, d), q_at), pl.BlockSpec((1, block_q, 1), q_at)
    kv = pl.BlockSpec((1, block_k, d), lambda b, s, w: (b // group, at(b, s, w)[2], 0))
    return at, q_rows, q_col, kv


def _key_major(kv_heads, group, nq, n, block_q, block_k, d):
    """The same for the dkv kernel, whose rows are key/value heads and whose
    words hold (key block, ``j``): query head ``b * group + j // nq`` (the
    batch-major layouts make that the right batch too), query block ``j % nq``."""
    word = _reader(kv_heads, n)

    def at(b, s, w):
        ki, j = _pair(word(b, s, w))
        return b // kv_heads, j % nq, ki

    def q_at(b, s, w):
        j = _pair(word(b, s, w))[1]
        return b * group + j // nq, j % nq, 0

    q_rows, q_col = pl.BlockSpec((1, block_q, d), q_at), pl.BlockSpec((1, block_q, 1), q_at)
    kv = pl.BlockSpec((1, block_k, d), lambda b, s, w: (b, at(b, s, w)[2], 0))
    return at, q_rows, q_col, kv


def _by_key(pairs, group):
    """[B, nq, nk] -> [B, nk, group * nq]: the dkv kernel's square."""
    return jnp.tile(pairs.transpose(0, 2, 1), (1, 1, group))


def _fwd_step(keep, q_ref, k_ref, v_ref, acc, m, l, scale):
    """One block of scores of the forward recurrence, ``keep`` [Bq, Bk] of it."""
    s = jnp.where(keep, _dot_t1(q_ref[0], k_ref[0]) * scale, NEG_BIG)
    m_old = m[:]
    m_new = jnp.maximum(m_old, s.max(axis=1, keepdims=True))
    p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_old - m_new)
    l[:] = l[:] * corr + p.sum(axis=1, keepdims=True)
    acc[:] = acc[:] * corr + _dot(p.astype(v_ref.dtype), v_ref[0])
    m[:] = m_new


def _dq_step(keep, q_ref, k_ref, v_ref, do_ref, lse_ref, drow_ref, dq_acc, scale):
    k = k_ref[0]
    s = _dot_t1(q_ref[0], k) * scale
    p = jnp.where(keep, jnp.exp(s - lse_ref[0]), 0.0)
    dp = _dot_t1(do_ref[0], v_ref[0])
    ds = p * (dp - drow_ref[0]) * scale
    dq_acc[:] = dq_acc[:] + _dot(ds.astype(k.dtype), k)


def _dkv_step(keep, q_ref, k_ref, v_ref, do_ref, lse_ref, drow_ref, dk_acc, dv_acc, scale):
    q, do = q_ref[0], do_ref[0]
    s = _dot_t1(q, k_ref[0]) * scale
    p = jnp.where(keep, jnp.exp(s - lse_ref[0]), 0.0)
    dv_acc[:] = dv_acc[:] + _dot_t0(p.astype(do.dtype), do)
    dp = _dot_t1(do, v_ref[0])
    ds = p * (dp - drow_ref[0]) * scale
    dk_acc[:] = dk_acc[:] + _dot_t0(ds.astype(q.dtype), q)


def _walk_fwd_kernel(walk, q_ref, k_ref, v_ref, a_ref, b_ref, o_ref, lse_ref, acc, m, l, *,
                     keep, scale, heads, n):
    """``keep(a_ref, b_ref, qi, ki)`` [Bq, Bk] is the family's rule, ``a_ref``
    and ``b_ref`` the two operands it reads."""
    word = _reader(heads, n)(pl.program_id(0), pl.program_id(1), walk)

    @pl.when(word & _FIRST != 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, -jnp.inf)
        l[:] = jnp.zeros_like(l)

    @pl.when(word & _RUN != 0)
    def _():
        _fwd_step(keep(a_ref, b_ref, *_pair(word)), q_ref, k_ref, v_ref, acc, m, l, scale)

    @pl.when(word & _LAST != 0)
    def _():
        o_ref[0] = (acc[:] / l[:]).astype(o_ref.dtype)
        lse_ref[0] = m[:] + jnp.log(l[:])


def _walk_dq_kernel(walk, q_ref, k_ref, v_ref, a_ref, b_ref, do_ref, lse_ref, drow_ref, dq_ref,
                    dq_acc, *, keep, scale, heads, n):
    word = _reader(heads, n)(pl.program_id(0), pl.program_id(1), walk)

    @pl.when(word & _FIRST != 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(word & _RUN != 0)
    def _():
        _dq_step(keep(a_ref, b_ref, *_pair(word)), q_ref, k_ref, v_ref, do_ref, lse_ref, drow_ref,
                 dq_acc, scale)

    @pl.when(word & _LAST != 0)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _walk_dkv_kernel(walk, q_ref, k_ref, v_ref, a_ref, b_ref, do_ref, lse_ref, drow_ref, dk_ref,
                     dv_ref, dk_acc, dv_acc, *, keep, scale, kv_heads, nq, n):
    # A key block's pairs: the group's query heads, and each head's query
    # blocks; they all add into this key block's dk and dv.
    word = _reader(kv_heads, n)(pl.program_id(0), pl.program_id(1), walk)

    @pl.when(word & _FIRST != 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(word & _RUN != 0)
    def _():
        ki, j = _pair(word)
        _dkv_step(keep(a_ref, b_ref, j % nq, ki), q_ref, k_ref, v_ref, do_ref, lse_ref, drow_ref,
                  dk_acc, dv_acc, scale)

    @pl.when(word & _LAST != 0)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


class _Family(NamedTuple):
    """What the two packed families' calls differ by. A family is a value:
    nothing selects one but the entry point that holds it."""

    # The kernels' names begin with it: traces and benchmarks/metrics/ tell the families apart by it.
    name: str
    setup: Callable  # (q, k, *rule, block_q, block_k) -> (heads, kv_heads, group, nq, nk), a, b, pairs
    keep: Callable  # (block_q, block_k) -> the kernels' ``keep(a_ref, b_ref, qi, ki)`` [Bq, Bk]
    specs: Callable  # (at, nk, block_q, block_k) -> the BlockSpecs of ``a`` and ``b``


def _packed_fwd(family, q, k, v, rule, scale, block_q, block_k, interpret):
    """``rule``: the arrays the family's mask is made from, as its entry
    point takes them."""
    if interpret is None:
        interpret = _use_interpret()
    (heads, _, group, nq, nk), a, b, pairs = family.setup(q, k, *rule, block_q, block_k)
    bh, rows, d = q.shape
    walk, n_run = _walk(pairs)
    at, q_rows, q_col, kv = _query_major(heads, group, nq * nk, block_q, block_k, d)
    o, lse = pl.pallas_call(
        functools.partial(_walk_fwd_kernel, scale=scale, heads=heads, n=nq * nk,
                          keep=family.keep(block_q, block_k)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, n_run.max()),
            in_specs=[q_rows, kv, kv, *family.specs(at, nk, block_q, block_k)],
            out_specs=[q_rows, q_col],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, rows, d), q.dtype),
            jax.ShapeDtypeStruct((bh, rows, 1), jnp.float32),
        ],
        interpret=interpret,
        name=f"{family.name}_fwd",
    )(walk, q, k, v, a, b)
    return o, (q, k, v, *rule, o, lse)


def _packed_bwd(family, scale, block_q, block_k, interpret, residuals, g):
    if interpret is None:
        interpret = _use_interpret()
    q, k, v, *rule, o, lse = residuals
    (heads, kv_heads, group, nq, nk), a, b, pairs = family.setup(q, k, *rule, block_q, block_k)
    d = q.shape[2]
    keep = family.keep(block_q, block_k)
    drow = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                   keepdims=True)

    walk, n_run = _walk(pairs)
    at, q_rows, q_col, kv = _query_major(heads, group, nq * nk, block_q, block_k, d)
    dq = pl.pallas_call(
        functools.partial(_walk_dq_kernel, scale=scale, heads=heads, n=nq * nk, keep=keep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(q.shape[0], n_run.max()),
            in_specs=[q_rows, kv, kv, *family.specs(at, nk, block_q, block_k), q_rows, q_col, q_col],
            out_specs=q_rows,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=f"{family.name}_dq",
    )(walk, q, k, v, a, b, g, lse, drow)

    n = group * nq * nk
    walk, n_run = _walk(_by_key(pairs, group))
    at, q_rows, q_col, kv = _key_major(kv_heads, group, nq, n, block_q, block_k, d)
    dk, dv = pl.pallas_call(
        functools.partial(_walk_dkv_kernel, scale=scale, kv_heads=kv_heads, nq=nq, n=n, keep=keep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k.shape[0], n_run.max()),
            in_specs=[q_rows, kv, kv, *family.specs(at, nk, block_q, block_k), q_rows, q_col, q_col],
            out_specs=[kv, kv],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
        name=f"{family.name}_dkv",
    )(walk, q, k, v, a, b, g, lse, drow)
    return (dq, dk, dv, *[None] * len(rule))


# ------------------------------------------------- causal, packed, grouped
# Attention of packed documents: a query sees the keys of its own document
# at or before its own position. Query head ``h`` of ``Hq`` reads key/value
# head ``h // (Hq / Hkv)``; the kernels never materialise the repeated heads.
#
# Which pairs run: ``_block_ranges`` gives each block of tokens the smallest
# and largest segment id it holds, and a (query block, key block) pair runs
# only where the key block starts at or before the query block's last row and
# the two ranges of ids overlap (``_runs``). The test is exact for documents
# stored one after the other, and conservative (never wrong) for any other
# ids. Every query sees itself, so the diagonal pair always runs: no row's sum
# is empty, and every query block and every key block is in the walk.
#
# Operands go to the MXU in the dtype they come in (bf16 on the chip) and
# accumulate in float32.


def _block_ranges(segment_ids, block):
    """(min, max) [B, T / block] int32 of the segment ids in each block of
    ``block`` tokens."""
    blocks = segment_ids.astype(jnp.int32).reshape(segment_ids.shape[0], -1, block)
    return blocks.min(axis=2), blocks.max(axis=2)


def _runs(qmin, qmax, kmin, kmax, block_q, block_k):
    """[B, nq, nk]: the pairs of blocks that run."""
    qi = jnp.arange(qmin.shape[1], dtype=jnp.int32)[:, None]
    ki = jnp.arange(kmin.shape[1], dtype=jnp.int32)[None, :]
    causal = ki * block_k <= qi * block_q + (block_q - 1)
    return causal & (kmax[:, None, :] >= qmin[:, :, None]) & (kmin[:, None, :] <= qmax[:, :, None])


def _keep(qseg_ref, kseg_ref, qi, ki, block_q, block_k):
    """[Bq, Bk]: same document, key at or before the query."""
    shape = (block_q, block_k)
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (qseg_ref[0] == kseg_ref[0]) & (qpos >= kpos)


def _segment_specs(at, block_q, block_k):
    """BlockSpecs of the segment ids as ``_keep`` takes them: the query
    block's as a column, the key block's as a row."""

    def column(b, s, w):
        row, qi, _ = at(b, s, w)
        return row, qi, 0

    def line(b, s, w):
        row, _, ki = at(b, s, w)
        return row, 0, ki

    return [pl.BlockSpec((1, block_q, 1), column), pl.BlockSpec((1, 1, block_k), line)]


def _causal_setup(q, k, segment_ids, block_q, block_k):
    """Checks the shapes; returns (heads, kv_heads, group, nq, nk), the
    segment ids as the kernels' two tiles take them, and the pairs of blocks
    that run [B, nq, nk]."""
    (bh, s_len, _), bsz = q.shape, segment_ids.shape[0]
    if segment_ids.shape != (bsz, s_len) or bh % bsz or k.shape[0] % bsz:
        raise ValueError(
            f"flash_attention_causal: q {q.shape}, k {k.shape} and "
            f"segment_ids {segment_ids.shape} do not share a batch and a length"
        )
    heads, kv_heads = bh // bsz, k.shape[0] // bsz
    if heads % kv_heads:
        raise ValueError(
            f"flash_attention_causal: {heads} query heads are not a multiple "
            f"of {kv_heads} key/value heads"
        )
    if s_len % block_q or s_len % block_k:
        raise ValueError(
            f"flash_attention_causal: seq {s_len} must be a multiple of "
            f"block_q={block_q} and block_k={block_k}"
        )
    seg = segment_ids.astype(jnp.int32)
    pairs = _runs(*_block_ranges(seg, block_q), *_block_ranges(seg, block_k), block_q, block_k)
    dims = (heads, kv_heads, heads // kv_heads, s_len // block_q, s_len // block_k)
    return dims, seg[:, :, None], seg[:, None, :], pairs


_CAUSAL = _Family(
    "flash_causal",
    _causal_setup,
    lambda block_q, block_k: functools.partial(_keep, block_q=block_q, block_k=block_k),
    lambda at, nk, block_q, block_k: _segment_specs(at, block_q, block_k),
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention_causal(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: jax.Array,
    scale: float,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal blockwise attention inside packed documents. q: [batch *
    heads, seq, head_dim]; k, v: [batch * kv_heads, seq, head_dim], heads a
    multiple of kv_heads (batch-major both: row ``b * heads + h``);
    segment_ids: [batch, seq] ints, constant along a document.
    ``block_q``/``block_k`` must divide ``seq``. Returns the shape of q."""
    o, _ = _fac_fwd(q, k, v, segment_ids, scale, block_q, block_k, interpret)
    return o


def _fac_fwd(q, k, v, segment_ids, *static):
    return _packed_fwd(_CAUSAL, q, k, v, (segment_ids,), *static)


flash_attention_causal.defvjp(_fac_fwd, functools.partial(_packed_bwd, _CAUSAL))


# ------------------------------------------- block diffusion, packed, grouped
# Block-diffusion training (Arriola et al., arXiv:2503.09573) runs every
# packed sequence of T tokens twice in one pass, as 2T rows: rows < T the
# clean copy, rows >= T the noised copy of the same tokens, which share their
# document ``doc`` and the ordinal ``blk`` of their block inside it (blocks of
# ``block_length`` tokens counted from the document's first; its last may be
# short). keep(q, k) = doc(q) == doc(k) and
#
#     clean q, clean k:    blk(k) <= blk(q)      block-causal
#     noised q, clean k:   blk(k) <  blk(q)      the strictly earlier blocks
#     noised q, noised k:  blk(k) == blk(q)      its own block
#     clean q, noised k:   never
#
# Every row keeps itself, so no row's sum is empty. Documents lie one after
# the other and a block is a run of tokens, so what a query keeps of the clean
# keys is one interval of key rows, and of the noised keys another:
# ``_blockdiff_bounds`` gives each query row the two (lo, hi), the kernels get
# the pair that belongs to the key block's half through the index map, and
# ``_interval_keep`` is two comparisons.
#
# Which pairs run: those whose key block lies between the smallest lo and the
# largest hi of the query block's rows, in the key block's half
# (``_interval_runs``). In the clean-onto-noised quadrant nothing runs, in the
# noised-onto-noised one the diagonal and, where a block of tokens lies across
# a kernel block's edge, its neighbour; but with one block of tokens as long
# as its document that quadrant runs whole, and so can the other two: no
# length short of three quarters of the square is safe for every ``doc`` and
# ``blk``, which is why the walk's length is the lists' own. A clean query
# block runs its own clean key block and a noised one its own noised key
# block, so every query block and every key block is in the walk.
#
# The three bodies, the three kernels around them and the three calls
# (``_packed_fwd``, ``_packed_bwd``) are both families'.


def _blockdiff_bounds(doc, blk):
    """(lo, hi) [B, 2, 2T, 1] int32: the key rows (of the 2T) that query row
    ``[b, :, i]`` keeps, ``[:, 0]`` among the clean keys and ``[:, 1]`` among
    the noised ones; an empty interval is (2T, -1)."""
    doc, blk = doc.astype(jnp.int32), blk.astype(jnp.int32)
    t = doc.shape[1]
    at = jnp.arange(t, dtype=jnp.int32)[None]
    before = lambda x: jnp.pad(x, ((0, 0), (1, 0)), constant_values=-1)[:, :t]
    new_doc = doc != before(doc)
    new_blk = new_doc | (blk != before(blk))
    doc_start = jax.lax.cummax(jnp.where(new_doc, at, 0), axis=1)
    blk_start = jax.lax.cummax(jnp.where(new_blk, at, 0), axis=1)
    # A block ends where the next begins, or the sequence does.
    last = jnp.pad(new_blk[:, 1:], ((0, 0), (0, 1)), constant_values=True)
    blk_end = jax.lax.cummin(jnp.where(last, at, t), axis=1, reverse=True)
    none_lo, none_hi = jnp.full_like(at + doc, 2 * t), jnp.full_like(at + doc, -1)
    first = blk_start == doc_start  # a noised row of a document's first block: no clean key
    lo = jnp.stack(
        [
            jnp.concatenate([doc_start, jnp.where(first, none_lo, doc_start)], axis=1),
            jnp.concatenate([none_lo, t + blk_start], axis=1),
        ],
        axis=1,
    )
    hi = jnp.stack(
        [
            jnp.concatenate([blk_end, jnp.where(first, none_hi, blk_start - 1)], axis=1),
            jnp.concatenate([none_hi, t + blk_end], axis=1),
        ],
        axis=1,
    )
    return lo[..., None], hi[..., None]


def _interval_runs(lo_blk, hi_blk, nk):
    """[B, nq, nk]: whether key block ``ki`` lies inside what query block
    ``qi`` spans of the key block's half. ``lo_blk``, ``hi_blk``: [B, 2, nq]
    key blocks, the clean half's and the noised half's."""
    bsz, _, nq = lo_blk.shape
    ki = jnp.arange(nk, dtype=jnp.int32).reshape(2, 1, nk // 2)
    runs = (lo_blk[..., None] <= ki) & (ki <= hi_blk[..., None])  # [B, 2, nq, nk / 2]
    return runs.transpose(0, 2, 1, 3).reshape(bsz, nq, nk)


def _interval_keep(lo_ref, hi_ref, ki, block_q, block_k):
    """[Bq, Bk]: the key row lies in the query row's interval."""
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return (kpos >= lo_ref[0, 0]) & (kpos <= hi_ref[0, 0])


def _bound_specs(at, half, block_q):
    """BlockSpecs of (lo, hi) as ``_interval_keep`` takes them: the query
    block's rows' bounds in the key block's half."""

    def bound(b, s, w):
        row, qi, ki = at(b, s, w)
        return row, ki // half, qi, 0

    return [pl.BlockSpec((1, 1, block_q, 1), bound)] * 2


def _blockdiff_pairs(doc, blk, block_q, block_k):
    """The rows' bounds (lo, hi) and the pairs of blocks that run [B, nq, nk]."""
    bsz, t = doc.shape
    if blk.shape != doc.shape or t % block_q or t % block_k:
        raise ValueError(
            f"flash_attention_blockdiff: doc {doc.shape} and blk {blk.shape} must be alike, "
            f"and seq {t} a multiple of block_q={block_q} and block_k={block_k}"
        )
    lo, hi = _blockdiff_bounds(doc, blk)
    nq = 2 * t // block_q
    lo_blk = lo.reshape(bsz, 2, nq, block_q).min(axis=3) // block_k
    hi_blk = hi.reshape(bsz, 2, nq, block_q).max(axis=3) // block_k
    return lo, hi, _interval_runs(lo_blk, hi_blk, 2 * t // block_k)


def blockdiff_walk_counts(doc, blk, block_q: int = 512, block_k: int = 512):
    """(pairs run, steps walked) int32 of ``flash_attention_blockdiff`` on
    this batch, a query head and kernel: the (query block, key block) pairs
    its rule runs, and the grid steps of the walk, every batch row as long as
    the longest. The dkv kernel's are these times the group's heads."""
    n_run = _blockdiff_pairs(doc, blk, block_q, block_k)[2].sum(axis=(1, 2), dtype=jnp.int32)
    return n_run.sum(), n_run.shape[0] * n_run.max()


def _blockdiff_setup(q, k, doc, blk, block_q, block_k):
    """Checks the shapes; returns (heads, kv_heads, group, nq, nk), the rows'
    bounds and the pairs of blocks that run."""
    (bh, rows, _), (bsz, t) = q.shape, doc.shape
    if rows != 2 * t or bh % bsz or k.shape[0] % bsz:
        raise ValueError(
            f"flash_attention_blockdiff: q {q.shape} and k {k.shape} are not the clean and "
            f"the noised copy of doc {doc.shape}, blk {blk.shape}"
        )
    heads, kv_heads = bh // bsz, k.shape[0] // bsz
    if heads % kv_heads:
        raise ValueError(
            f"flash_attention_blockdiff: {heads} query heads are not a multiple "
            f"of {kv_heads} key/value heads"
        )
    lo, hi, pairs = _blockdiff_pairs(doc, blk, block_q, block_k)
    return (heads, kv_heads, heads // kv_heads, rows // block_q, rows // block_k), lo, hi, pairs


_BLOCKDIFF = _Family(
    "flash_blockdiff",
    _blockdiff_setup,
    lambda block_q, block_k: lambda lo, hi, qi, ki: _interval_keep(lo, hi, ki, block_q, block_k),
    lambda at, nk, block_q, block_k: _bound_specs(at, nk // 2, block_q),
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def flash_attention_blockdiff(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    doc: jax.Array,
    blk: jax.Array,
    scale: float,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Blockwise attention under the block-diffusion mask. q: [batch * heads,
    2 * seq, head_dim], rows < seq the clean copy and the rest the noised
    one; k, v: [batch * kv_heads, 2 * seq, head_dim] likewise (batch-major,
    heads a multiple of kv_heads); doc, blk: [batch, seq] ints, the document
    of each token and its block's ordinal inside it, documents one after the
    other. ``block_q``/``block_k`` must divide ``seq``. Returns the shape of q."""
    o, _ = _fab_fwd(q, k, v, doc, blk, scale, block_q, block_k, interpret)
    return o


def _fab_fwd(q, k, v, doc, blk, *static):
    return _packed_fwd(_BLOCKDIFF, q, k, v, (doc, blk), *static)


flash_attention_blockdiff.defvjp(_fab_fwd, functools.partial(_packed_bwd, _BLOCKDIFF))
