"""First-party Pallas TPU flash attention (forward + backward kernels).

Three entry points over the same recurrence. ``flash_attention``: non-causal
multi-head attention with one key-validity mask shared by the batch (the ViT
path; described first). ``flash_attention_causal``: causal attention inside
packed documents with grouped key/value heads (the next-token language
models' path; its kernels are the second part of this file and say what
differs). ``flash_attention_blockdiff``: the block-diffusion mask over a clean
and a noised copy of every packed sequence (models/sdar.py; the third part).
The two packed families share the three bodies of the recurrence
(``_fwd_step``, ``_dq_step``, ``_dkv_step``) and differ in which (query, key)
pairs a body is told to keep and which blocks of scores it is run for.

Attention is computed blockwise so the S x S score matrix never materializes in HBM: for each
query block the kernel streams key/value blocks through VMEM, carrying the
online-softmax running max/sum in VMEM scratch across the (sequential)
innermost grid dimension — the flash-attention recurrence on the hardware
it was shaped for (MXU matmuls with fp32 accumulators, VPU for the
exp/max/sum, ~(BLOCK x BLOCK) live scores).

The backward pass is two more Pallas kernels over the same block grid
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
from typing import Optional

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


# ------------------------------------------------- causal, packed, grouped
# Attention of packed documents: a query sees the keys of its own document
# at or before its own position. Query head ``h`` of ``Hq`` reads key/value
# head ``h // (Hq / Hkv)``; the kernels never materialise the repeated heads.
#
# A block of scores that the causal order or the documents' extents mask
# whole is skipped: ``_block_ranges`` gives each block of tokens the smallest
# and largest segment id it holds, the kernels get them as scalars before the
# body runs (scalar prefetch), and a (query block, key block) pair runs only
# where the key block starts at or before the query block's last row and the
# two ranges of ids overlap. The test is exact for documents stored one after
# the other, and conservative (never wrong) for any other ids. Every query
# sees itself, so the diagonal block always runs and no row's sum is empty.
#
# Operands go to the MXU in the dtype they come in (bf16 on the chip) and
# accumulate in float32.


def _block_ranges(segment_ids, block):
    """(min, max) of the segment ids in each block of ``block`` tokens, each
    flattened to [B * T / block] int32."""
    blocks = segment_ids.astype(jnp.int32).reshape(segment_ids.shape[0], -1, block)
    return blocks.min(axis=2).reshape(-1), blocks.max(axis=2).reshape(-1)


def _runs(qmin, qmax, kmin, kmax, batch, qi, ki, nq, nk, block_q, block_k):
    q, k = batch * nq + qi, batch * nk + ki
    causal = ki * block_k <= qi * block_q + (block_q - 1)
    return causal & (kmax[k] >= qmin[q]) & (kmin[k] <= qmax[q])


def _keep(qseg_ref, kseg_ref, qi, ki, block_q, block_k):
    """[Bq, Bk]: same document, key at or before the query."""
    shape = (block_q, block_k)
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (qseg_ref[0] == kseg_ref[0]) & (qpos >= kpos)


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


def _causal_fwd_kernel(qmin, qmax, kmin, kmax, q_ref, k_ref, v_ref, qseg_ref,
                       kseg_ref, o_ref, lse_ref, acc, m, l, *, scale, heads,
                       block_q, block_k):
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, -jnp.inf)
        l[:] = jnp.zeros_like(l)

    @pl.when(_runs(qmin, qmax, kmin, kmax, bh // heads, qi, ki, nq, nk,
                   block_q, block_k))
    def _():
        keep = _keep(qseg_ref, kseg_ref, qi, ki, block_q, block_k)
        _fwd_step(keep, q_ref, k_ref, v_ref, acc, m, l, scale)

    @pl.when(ki == nk - 1)
    def _():
        o_ref[0] = (acc[:] / l[:]).astype(o_ref.dtype)
        lse_ref[0] = m[:] + jnp.log(l[:])


def _causal_dq_kernel(qmin, qmax, kmin, kmax, q_ref, k_ref, v_ref, qseg_ref,
                      kseg_ref, do_ref, lse_ref, drow_ref, dq_ref, dq_acc, *,
                      scale, heads, block_q, block_k):
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_runs(qmin, qmax, kmin, kmax, bh // heads, qi, ki, nq, nk,
                   block_q, block_k))
    def _():
        keep = _keep(qseg_ref, kseg_ref, qi, ki, block_q, block_k)
        _dq_step(keep, q_ref, k_ref, v_ref, do_ref, lse_ref, drow_ref, dq_acc, scale)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _causal_dkv_kernel(qmin, qmax, kmin, kmax, q_ref, k_ref, v_ref, qseg_ref,
                       kseg_ref, do_ref, lse_ref, drow_ref, dk_ref, dv_ref,
                       dk_acc, dv_acc, *, scale, kv_heads, nq, block_q,
                       block_k):
    # The innermost axis walks the group's query heads, and each head's
    # query blocks: they all add into this key block's dk and dv.
    bh, ki, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk, last = pl.num_programs(1), pl.num_programs(2) - 1
    qi = j % nq

    @pl.when(j == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_runs(qmin, qmax, kmin, kmax, bh // kv_heads, qi, ki, nq, nk,
                   block_q, block_k))
    def _():
        keep = _keep(qseg_ref, kseg_ref, qi, ki, block_q, block_k)
        _dkv_step(keep, q_ref, k_ref, v_ref, do_ref, lse_ref, drow_ref, dk_acc, dv_acc, scale)

    @pl.when(j == last)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _causal_setup(q, k, segment_ids, block_q, block_k):
    """Checks the shapes; returns (batch, heads, kv_heads, group, nq, nk),
    the segment ids as the kernels' two tiles take them, and the blocks'
    ranges for the scalar prefetch."""
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
    ranges = _block_ranges(seg, block_q) + _block_ranges(seg, block_k)
    dims = (bsz, heads, kv_heads, heads // kv_heads, s_len // block_q, s_len // block_k)
    return dims, seg[:, :, None], seg[:, None, :], ranges


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


def _fac_fwd(q, k, v, segment_ids, scale, block_q, block_k, interpret):
    if interpret is None:
        interpret = _use_interpret()
    (_, heads, _, group, nq, nk), qseg, kseg, ranges = _causal_setup(
        q, k, segment_ids, block_q, block_k
    )
    bh, s_len, d = q.shape
    o, lse = pl.pallas_call(
        functools.partial(_causal_fwd_kernel, scale=scale, heads=heads,
                          block_q=block_q, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, qi, ki, *_: (b, qi, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, qi, ki, *_: (b // group, ki, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, qi, ki, *_: (b // group, ki, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, qi, ki, *_: (b // heads, qi, 0)),
                pl.BlockSpec((1, 1, block_k), lambda b, qi, ki, *_: (b // heads, 0, ki)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, qi, ki, *_: (b, qi, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, qi, ki, *_: (b, qi, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_len, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s_len, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_causal_fwd",
    )(*ranges, q, k, v, qseg, kseg)
    return o, (q, k, v, segment_ids, o, lse)


def _fac_bwd(scale, block_q, block_k, interpret, residuals, g):
    if interpret is None:
        interpret = _use_interpret()
    q, k, v, segment_ids, o, lse = residuals
    (_, heads, kv_heads, group, nq, nk), qseg, kseg, ranges = _causal_setup(
        q, k, segment_ids, block_q, block_k
    )
    bh, _, d = q.shape
    drow = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                   keepdims=True)

    dq = pl.pallas_call(
        functools.partial(_causal_dq_kernel, scale=scale, heads=heads,
                          block_q=block_q, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, qi, ki, *_: (b, qi, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, qi, ki, *_: (b // group, ki, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, qi, ki, *_: (b // group, ki, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, qi, ki, *_: (b // heads, qi, 0)),
                pl.BlockSpec((1, 1, block_k), lambda b, qi, ki, *_: (b // heads, 0, ki)),
                pl.BlockSpec((1, block_q, d), lambda b, qi, ki, *_: (b, qi, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, qi, ki, *_: (b, qi, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, qi, ki, *_: (b, qi, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki, *_: (b, qi, 0)),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_causal_dq",
    )(*ranges, q, k, v, qseg, kseg, g, lse, drow)

    # Rows of q for key/value row ``b``: head ``b * group + j // nq`` (the
    # batch-major layouts make that the right batch too), block ``j % nq``.
    q_row = lambda b, ki, j, *_: (b * group + j // nq, j % nq, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_causal_dkv_kernel, scale=scale, kv_heads=kv_heads,
                          nq=nq, block_q=block_q, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k.shape[0], nk, group * nq),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_row),
                pl.BlockSpec((1, block_k, d), lambda b, ki, j, *_: (b, ki, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, ki, j, *_: (b, ki, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, ki, j, *_: (b // kv_heads, j % nq, 0)),
                pl.BlockSpec((1, 1, block_k), lambda b, ki, j, *_: (b // kv_heads, 0, ki)),
                pl.BlockSpec((1, block_q, d), q_row),
                pl.BlockSpec((1, block_q, 1), q_row),
                pl.BlockSpec((1, block_q, 1), q_row),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda b, ki, j, *_: (b, ki, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, ki, j, *_: (b, ki, 0)),
            ],
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
        name="flash_causal_dkv",
    )(*ranges, q, k, v, qseg, kseg, g, lse, drow)
    return dq, dk, dv, None


flash_attention_causal.defvjp(_fac_fwd, _fac_bwd)


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
# ``_interval_keep`` is two comparisons. A block of scores runs where the
# smallest lo and the largest hi of the query block's rows span the key block
# (scalar prefetch, as the causal family): in the clean-onto-noised quadrant
# nothing runs, in the noised-onto-noised one the diagonal and, where a block
# of tokens lies across a kernel block's edge, its neighbour.
#
# The three bodies are the causal family's.


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


def _interval_runs(lo_blk, hi_blk, batch, qi, ki, nq, nk):
    """Whether key block ``ki`` lies inside what query block ``qi`` spans of
    its half. ``lo_blk``, ``hi_blk``: [B * 2 * nq] key blocks."""
    i = (batch * 2 + ki // (nk // 2)) * nq + qi
    return (lo_blk[i] <= ki) & (ki <= hi_blk[i])


def _interval_keep(lo_ref, hi_ref, ki, block_q, block_k):
    """[Bq, Bk]: the key row lies in the query row's interval."""
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return (kpos >= lo_ref[0, 0]) & (kpos <= hi_ref[0, 0])


def _blockdiff_fwd_kernel(lo_blk, hi_blk, q_ref, k_ref, v_ref, lo_ref, hi_ref,
                          o_ref, lse_ref, acc, m, l, *, scale, heads, block_q,
                          block_k):
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, -jnp.inf)
        l[:] = jnp.zeros_like(l)

    @pl.when(_interval_runs(lo_blk, hi_blk, bh // heads, qi, ki, nq, nk))
    def _():
        keep = _interval_keep(lo_ref, hi_ref, ki, block_q, block_k)
        _fwd_step(keep, q_ref, k_ref, v_ref, acc, m, l, scale)

    @pl.when(ki == nk - 1)
    def _():
        o_ref[0] = (acc[:] / l[:]).astype(o_ref.dtype)
        lse_ref[0] = m[:] + jnp.log(l[:])


def _blockdiff_dq_kernel(lo_blk, hi_blk, q_ref, k_ref, v_ref, lo_ref, hi_ref,
                         do_ref, lse_ref, drow_ref, dq_ref, dq_acc, *, scale,
                         heads, block_q, block_k):
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_interval_runs(lo_blk, hi_blk, bh // heads, qi, ki, nq, nk))
    def _():
        keep = _interval_keep(lo_ref, hi_ref, ki, block_q, block_k)
        _dq_step(keep, q_ref, k_ref, v_ref, do_ref, lse_ref, drow_ref, dq_acc, scale)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _blockdiff_dkv_kernel(lo_blk, hi_blk, q_ref, k_ref, v_ref, lo_ref, hi_ref,
                          do_ref, lse_ref, drow_ref, dk_ref, dv_ref, dk_acc,
                          dv_acc, *, scale, kv_heads, nq, block_q, block_k):
    # The innermost axis as in the causal family: the group's query heads,
    # and each head's query blocks.
    bh, ki, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk, last = pl.num_programs(1), pl.num_programs(2) - 1

    @pl.when(j == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_interval_runs(lo_blk, hi_blk, bh // kv_heads, j % nq, ki, nq, nk))
    def _():
        keep = _interval_keep(lo_ref, hi_ref, ki, block_q, block_k)
        _dkv_step(keep, q_ref, k_ref, v_ref, do_ref, lse_ref, drow_ref, dk_acc, dv_acc, scale)

    @pl.when(j == last)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _blockdiff_setup(q, k, doc, blk, block_q, block_k):
    """Checks the shapes; returns (heads, kv_heads, group, nq, nk), the rows'
    bounds and the query blocks' spans for the scalar prefetch."""
    (bh, rows, _), (bsz, t) = q.shape, doc.shape
    if rows != 2 * t or blk.shape != doc.shape or bh % bsz or k.shape[0] % bsz:
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
    if t % block_q or t % block_k:
        raise ValueError(
            f"flash_attention_blockdiff: seq {t} must be a multiple of "
            f"block_q={block_q} and block_k={block_k}"
        )
    lo, hi = _blockdiff_bounds(doc, blk)
    nq, nk = rows // block_q, rows // block_k
    lo_blk = lo.reshape(bsz, 2, nq, block_q).min(axis=3) // block_k
    hi_blk = hi.reshape(bsz, 2, nq, block_q).max(axis=3) // block_k
    dims = (heads, kv_heads, heads // kv_heads, nq, nk)
    return dims, lo, hi, (lo_blk.reshape(-1), hi_blk.reshape(-1))


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


def _fab_fwd(q, k, v, doc, blk, scale, block_q, block_k, interpret):
    if interpret is None:
        interpret = _use_interpret()
    (heads, _, group, nq, nk), lo, hi, spans = _blockdiff_setup(q, k, doc, blk, block_q, block_k)
    bh, rows, d = q.shape
    half = nk // 2
    bound = pl.BlockSpec((1, 1, block_q, 1), lambda b, qi, ki, *_: (b // heads, ki // half, qi, 0))
    o, lse = pl.pallas_call(
        functools.partial(_blockdiff_fwd_kernel, scale=scale, heads=heads,
                          block_q=block_q, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, qi, ki, *_: (b, qi, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, qi, ki, *_: (b // group, ki, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, qi, ki, *_: (b // group, ki, 0)),
                bound,
                bound,
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, qi, ki, *_: (b, qi, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, qi, ki, *_: (b, qi, 0)),
            ],
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
        name="flash_blockdiff_fwd",
    )(*spans, q, k, v, lo, hi)
    return o, (q, k, v, doc, blk, o, lse)


def _fab_bwd(scale, block_q, block_k, interpret, residuals, g):
    if interpret is None:
        interpret = _use_interpret()
    q, k, v, doc, blk, o, lse = residuals
    (heads, kv_heads, group, nq, nk), lo, hi, spans = _blockdiff_setup(
        q, k, doc, blk, block_q, block_k
    )
    bh, _, d = q.shape
    half = nk // 2
    drow = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                   keepdims=True)

    bound = pl.BlockSpec((1, 1, block_q, 1), lambda b, qi, ki, *_: (b // heads, ki // half, qi, 0))
    dq = pl.pallas_call(
        functools.partial(_blockdiff_dq_kernel, scale=scale, heads=heads,
                          block_q=block_q, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, qi, ki, *_: (b, qi, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, qi, ki, *_: (b // group, ki, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, qi, ki, *_: (b // group, ki, 0)),
                bound,
                bound,
                pl.BlockSpec((1, block_q, d), lambda b, qi, ki, *_: (b, qi, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, qi, ki, *_: (b, qi, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, qi, ki, *_: (b, qi, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki, *_: (b, qi, 0)),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_blockdiff_dq",
    )(*spans, q, k, v, lo, hi, g, lse, drow)

    q_row = lambda b, ki, j, *_: (b * group + j // nq, j % nq, 0)
    bound = pl.BlockSpec(
        (1, 1, block_q, 1), lambda b, ki, j, *_: (b // kv_heads, ki // half, j % nq, 0)
    )
    dk, dv = pl.pallas_call(
        functools.partial(_blockdiff_dkv_kernel, scale=scale, kv_heads=kv_heads,
                          nq=nq, block_q=block_q, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k.shape[0], nk, group * nq),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_row),
                pl.BlockSpec((1, block_k, d), lambda b, ki, j, *_: (b, ki, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, ki, j, *_: (b, ki, 0)),
                bound,
                bound,
                pl.BlockSpec((1, block_q, d), q_row),
                pl.BlockSpec((1, block_q, 1), q_row),
                pl.BlockSpec((1, block_q, 1), q_row),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda b, ki, j, *_: (b, ki, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, ki, j, *_: (b, ki, 0)),
            ],
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
        name="flash_blockdiff_dkv",
    )(*spans, q, k, v, lo, hi, g, lse, drow)
    return dq, dk, dv, None, None


flash_attention_blockdiff.defvjp(_fab_fwd, _fab_bwd)
