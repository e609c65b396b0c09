"""Power retention: the gated, degree-2 linear attention of Gelada, Buckman,
Zhang & Bach, "Scaling Context Requires Rethinking Attention"
(arXiv:2507.04239), in its chunked form over packed documents.

For one key/value head and the ``G`` query heads that read it, with
``lam_t <= 0`` the log of the retention a token and ``d`` the head's width:

    a_ts = exp(sum_{s < r <= t} lam_r) * (q_t . k_s)^2 / d     s <= t, same document
    o_t  = sum_s a_ts v_s / (sum_s a_ts + eps)

(``eps`` guards 0 / 0 and nothing else: 1e-16, whose square float32 still
holds. A document's first token has
one weight, its own, and ``o = v a / (a + eps)`` is a step of width ``eps``
in ``a``: at 1e-6 that step lies where a token in a thousand falls, bf16
cannot say on which side, and its gradient, up to ``1 / (2 sqrt(eps))``, was
measured at fifteen times all the other tokens' together. PERF.md section 6,
PR 44.)

which is the recurrence (``phi`` the symmetric square, ``phi(q) . phi(k) = (q . k)^2``)

    S_t = e^{lam_t} S_{t-1} + phi(k_t) v_t^T / d     z_t = e^{lam_t} z_{t-1} + phi(k_t) / d
    o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

with ``S = 0, z = 0`` entering a document's first token. Chunked at ``Q``
tokens it is ops/ssd.py's walk (that file's ``chunk_decays``: the cumulative
sums, the masked decays to a chunk's end and from its start, float32, XLA's
on ``[B, C, Q, H]``) with two differences: within a chunk the scores of the
``d``-wide ``q`` and ``k`` are squared, and what is carried is ``S`` and the
normaliser ``z``, a state of ``d (d + 1) / 2`` features by ``d + 1``.

**The features.** ``phi`` is never written to HBM, in any pass. The kernels
lay the unordered pairs ``{i, j}`` out by their distance ``r = (i - j) mod
d``: block ``r`` of the features is ``x * roll(x, r)``, one lane rotation and
one product of a ``[rows, d]`` tile, and ``r = 0 .. d / 2`` holds every pair
(``r`` and ``d - r`` are the same pairs; blocks 1 .. d/2 - 1 count twice, 0
and d/2 once: the weight goes into the state as it is written). 65 blocks of
128: 8,320 features, the 8,256 symmetric ones and block 64's 64 twice. The
state of a key/value head is ``[65, 128, 128]`` float32 (4.06 MiB) and ``z``
``[65, 128]``, in VMEM from one chunk to the next. The loop over ``r`` rotates
by one lane a step, so every rotation is static.

**Two kernels** under a ``custom_vjp``, grid (batch x key/value heads,
chunks), the chunks in order (``retention_fwd``) and from the last
(``retention_bwd``). A grid step takes the chunk's ``k``, ``v`` ``[Q, d]``
and the group's ``q`` ``[G, Q, d]``: the masked decay ``[Q, Q]`` is formed
once and the group's heads reuse it; the carry's products stack the group's
heads as rows (``[G Q, d] x [d, d]`` a block of features), so the state is
read once for all of them. The forward under differentiation also writes the
state entering every chunk (``T / Q`` x 4.06 MiB a key/value head: 260 MiB at
32,768 tokens and Q = 512, alive from the layer's rebuilt forward to its
backward and no longer, since every layer is a ``jax.checkpoint`` whose
``SAVED`` does not name it); the plain forward does not. The backward is
linear attention's with ``[v | 1]`` as the values and ``[d num | d den]`` as
their cotangent: XLA makes ``d den = -(d o . o) / (den + eps)`` from the
saved ``o`` and ``den``, and ``d num = d o / (den + eps)`` is never formed:
the kernel's products take ``d o`` as it came and scale their rows in
float32 afterwards, so the two terms of ``d a_ts = d num_t . v_s + d den_t``
cancel as they do in exact arithmetic (a row of one weight has no gradient). The gate's gradient needs nothing of the kernels but ``d k``: with
``M_ts = a_ts * d a_ts``, ``d lam_r = sum_{s < r <= t} M_ts = sum_{t >= r}
(R_t - C_t)`` inside ``r``'s document, ``R_t = sum_s M_ts = (d o_t . o_t) eps
/ (den_t + eps)`` (a row's weights cancel in ``o``) and ``C_s = sum_t M_ts =
k_s . d k_s / 2`` (``a`` is of degree 2 in ``k_s``).

**Decided on the chip** (one layer of the cell, 5 + 1 heads of 128, 32,768
tokens, bf16; PERF.md section 6, PR 44, call 1). The chunk is 512: the plain
forward read 4.1 / 4.6 / 4.8 ms and the forward with its states and the
backward 13.0 / 13.5 / 14.0 ms at chunks of 256 / 512 / 1,024. A token's
carry costs the same whatever the chunk and its within-chunk square, computed
whole and masked, grows with it, so the time hardly moves; the kept states
halve with every doubling (520 / 260 / 130 MiB a layer) and the square's
float32 temporaries in VMEM grow fourfold: 512 is the middle. States kept,
not a reverse walk: dividing a state by a decay that may be 2^-100 is not an
inverse. Features by rotation (the symmetric half), not the 16,384 square:
half the products, and no lane is ever broadcast. Against XLA's form in
float32 at 2,048 tokens the bf16 kernels read 0.23 % off in the output and
2.1 / 2.1 / 0.33 / 1.0 % in the gradients of q, k, v and the gate.

At every other shape (a head that is not 128 wide, a chunk that is no
multiple of 128: the tiny models) the whole of it is XLA's
(``_retention_xla``, the same chunked mathematics with ``phi`` as the full
square), which is also the kernels' oracle in the tests. On the CPU the
kernels run in interpret mode. Gauges: ``retention_kernel_calls`` /
``retention_xla_calls``, which form each traced call took.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from turboprune_tpu.ops.flash import _dot, _dot_t0, _dot_t1, _use_interpret
from turboprune_tpu.ops.ssd import PADDING, _decay, chunk_decays
from turboprune_tpu.utils import tracing

EPS = 1e-16  # ``eps`` above: beside the normaliser, a guard of 0 / 0 alone
HEAD_DIM = 128  # the one head width the kernels take: a feature block is one lane tile
VMEM_BYTES = 96 * 2**20  # of a v5e core's 128 MiB
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=VMEM_BYTES
)
# Lanes of ``cols`` [T, 128] and sublanes of ``rows`` [8, Q]: what every
# token is scaled by, as a column and as a row.
CUM, TO_END, FROM_START, SEG = range(4)
# ``rows`` times a [Q, d] tile: row 0 is the sum over to_end. Row ``ROW_CARRIED``
# says in every lane what the state entering the chunk is scaled by at its end.
ROW_TO_END, ROW_CUM, ROW_SEG, ROW_CARRIED = range(4)


def _takes(d: int, chunk: int) -> bool:
    """Whether the kernels take the shape."""
    return d == HEAD_DIM and chunk % 128 == 0


# ------------------------------------------------------------------ kernels
def _masked_decay(cols, rows):
    """[Q, Q] float32: exp(cum_i - cum_j) where j <= i lie in one document, else 0."""
    q = cols.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    keep = (cols[:, SEG : SEG + 1] == rows[ROW_SEG : ROW_SEG + 1]) & (i >= j)
    return _decay(cols[:, CUM : CUM + 1] - rows[ROW_CUM : ROW_CUM + 1], keep)


def _weight(r, d):
    """What block ``r`` of the features counts for, over ``d``."""
    return jnp.where((r == 0) | (2 * r == d), 1.0 / d, 2.0 / d).astype(jnp.float32)


def _fwd_kernel(cols_ref, rows_ref, q_ref, k_ref, v_ref, o_ref, den_ref, *rest, keep):
    if keep:
        enter_s_ref, enter_z_ref, *rest = rest
    s_ref, z_ref, qf_ref, rq_ref, rk_ref, num_ref, dacc_ref = rest
    heads, c, d = q_ref.shape
    blocks, f32, dtype = s_ref.shape[0], jnp.float32, q_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    cols, rows = cols_ref[...], rows_ref[...]
    from_start = cols[:, FROM_START : FROM_START + 1]
    carried = rows[ROW_CARRIED : ROW_CARRIED + 1, :d]
    k, v = k_ref[...], v_ref[...]
    kf = k.astype(f32)
    vt = (v.astype(f32) * cols[:, TO_END : TO_END + 1]).astype(dtype)
    sums = rows.astype(dtype)

    # The carry: what the state entering the chunk gives the group's heads,
    # and the state leaving it, a block of features at a time.
    qf_ref[...] = q_ref[...].reshape(heads * c, d).astype(f32)
    rq_ref[...] = qf_ref[...]
    rk_ref[...] = kf
    num_ref[...] = jnp.zeros_like(num_ref)
    dacc_ref[...] = jnp.zeros_like(dacc_ref)

    def block(r, _):
        rq, rk = rq_ref[...], rk_ref[...]
        fq = qf_ref[...] * rq
        sr, zr = s_ref[r], z_ref[r]
        if keep:
            enter_s_ref[r] = sr
            enter_z_ref[r] = zr
        num_ref[...] += _dot(fq.astype(dtype), sr.astype(dtype))
        dacc_ref[...] += fq * zr[0:1]
        fk = (kf * rk).astype(dtype)
        w = _weight(r, d)
        s_ref[r] = carried * sr + w * _dot_t0(fk, vt)
        z_ref[r] = carried * zr + w * _dot(sums, fk)[ROW_TO_END : ROW_TO_END + 1]
        rq_ref[...] = pltpu.roll(rq, 1, 1)
        rk_ref[...] = pltpu.roll(rk, 1, 1)
        return 0

    jax.lax.fori_loop(0, blocks, block, 0)

    decay = _masked_decay(cols, rows) * (1.0 / d)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, den_ref.shape[1]), 1)
    dens = jnp.zeros(den_ref.shape, f32)
    for h in range(heads):
        here = slice(h * c, (h + 1) * c)
        a = _dot_t1(q_ref[h], k)
        s = a * a * decay
        num = _dot(s.astype(dtype), v) + from_start * num_ref[here]
        den = jnp.sum(s, axis=1, keepdims=True) + from_start * jnp.sum(
            dacc_ref[here], axis=1, keepdims=True
        )
        o_ref[h] = (num / (den + EPS)).astype(o_ref.dtype)
        dens = jnp.where(lane == h, den, dens)
    den_ref[...] = dens


def _bwd_kernel(cols_ref, rows_ref, q_ref, k_ref, v_ref, enter_s_ref, enter_z_ref, do_ref,
                ddcols_ref, ddrows_ref, dq_ref, dk_ref, dv_ref, gs_ref, gz_ref, qf_ref, rq_ref,
                rk_ref, wf_ref, ddf_ref, dqa_ref, dqb_ref, dka_ref, dkb_ref, dvt_ref):
    """The chunks from the last to the first; ``gs_ref``, ``gz_ref`` the
    cotangents of the state leaving the chunk. ``d num = d o / (den + eps)``
    is never rounded: a product takes ``d o`` as it came and its rows are
    scaled after it (or the other operand's before), so that ``d num . v``
    and ``d den`` cancel as they do in exact arithmetic where a row has one
    weight that counts. The features of block ``r`` are ``x * roll(x, r)``,
    so a block's cotangent ``f`` gives ``x`` ``f * roll(x, r) + roll(f * x,
    -r)``; the blocks run from the last to block 0 and the second terms are
    summed as ``acc = roll(acc, -1) + f * x``."""
    heads, c, d = q_ref.shape
    blocks, f32, dtype = gs_ref.shape[0], jnp.float32, q_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        gs_ref[...] = jnp.zeros_like(gs_ref)
        gz_ref[...] = jnp.zeros_like(gz_ref)

    cols, rows = cols_ref[...], rows_ref[...]
    from_start = cols[:, FROM_START : FROM_START + 1]
    carried = rows[ROW_CARRIED : ROW_CARRIED + 1, :d]
    to_end = cols[:, TO_END : TO_END + 1]
    k, v = k_ref[...], v_ref[...]
    kf = k.astype(f32)
    vt = (v.astype(f32) * to_end).astype(dtype)
    to_end_wide = jnp.broadcast_to(to_end, (c, d))
    ddcols = ddcols_ref[...]  # lane h: d den of head h; lane heads + h: its 1 / (den + eps)
    ddsums = ddrows_ref[...].astype(dtype)  # row 0: d den * from_start, the heads one after the other

    qf_ref[...] = q_ref[...].reshape(heads * c, d).astype(f32)
    for h in range(heads):
        here = slice(h * c, (h + 1) * c)
        wf_ref[here] = jnp.broadcast_to(ddcols[:, heads + h : heads + h + 1] * from_start, (c, d))
        ddf_ref[here] = jnp.broadcast_to(ddcols[:, h : h + 1] * from_start, (c, d))
    last = blocks - 1
    rq_ref[...] = pltpu.roll(qf_ref[...], last, 1)
    rk_ref[...] = pltpu.roll(kf, last, 1)
    for ref in (dqa_ref, dqb_ref, dka_ref, dkb_ref, dvt_ref):
        ref[...] = jnp.zeros_like(ref)

    def block(step, _):
        r = last - step
        rq, rk, qf = rq_ref[...], rk_ref[...], qf_ref[...]
        do, wf = do_ref[...].reshape(heads * c, d), wf_ref[...]
        fq = qf * rq
        g, gz = gs_ref[r], gz_ref[r]
        gb = g.astype(dtype)
        # What the chunk's queries read of the state entering it.
        dfq = _dot_t1(do, enter_s_ref[r].astype(dtype)) * wf + ddf_ref[...] * enter_z_ref[r][0:1]
        dqa_ref[...] += dfq * rq
        dqb_ref[...] = pltpu.roll(dqb_ref[...], d - 1, 1) + dfq * qf
        # What the chunk's keys and values wrote into the state leaving it.
        fk = (kf * rk).astype(dtype)
        w = _weight(r, d)
        dfk = w * (_dot_t1(vt, gb) + to_end_wide * gz[0:1])
        dvt_ref[...] += w * _dot(fk, gb)
        dka_ref[...] += dfk * rk
        dkb_ref[...] = pltpu.roll(dkb_ref[...], d - 1, 1) + dfk * kf
        gs_ref[r] = carried * g + _dot_t0((fq * wf).astype(dtype), do)
        gz_ref[r] = carried * gz + _dot(ddsums, fq.astype(dtype))[0:1]
        rq_ref[...] = pltpu.roll(rq, d - 1, 1)
        rk_ref[...] = pltpu.roll(rk, d - 1, 1)
        return 0

    jax.lax.fori_loop(0, blocks, block, 0)

    decay = _masked_decay(cols, rows) * (1.0 / d)
    dk = dka_ref[...] + dkb_ref[...]
    dv = dvt_ref[...] * to_end
    for h in range(heads):
        here = slice(h * c, (h + 1) * c)
        qh, do, whole = q_ref[h], do_ref[h], ddcols[:, heads + h : heads + h + 1]
        a = _dot_t1(qh, k)
        al = a * decay
        ds = _dot_t1(do, v) * whole + ddcols[:, h : h + 1]
        dv += _dot_t0((a * al * whole).astype(dtype), do)
        da = (2.0 * ds * al).astype(dtype)
        dq_ref[h] = _dot(da, k) + dqa_ref[here] + dqb_ref[here]
        dk += _dot_t0(da, qh)
    dk_ref[...] = dk
    dv_ref[...] = dv


def _operands(q, k, v, lam, seg, chunk):
    """What both kernels read, the leading axis batch x key/value heads:
    (cols [N, T, 128], rows [N, C, 8, Q], q [N, G, T, d], k, v [N, T, d])
    and, as a function of the chunk's place, the blocks of one grid step:
    (a chunk of cols, of rows, of q, of k or v)."""
    bsz, hkv, g, t, d = q.shape
    nc, f32 = t // chunk, jnp.float32
    segc = seg.reshape(bsz, nc, chunk)
    cum, to_end, from_start, carried = chunk_decays(lam.reshape(bsz, nc, chunk, hkv), segc)
    flat = lambda x: jnp.moveaxis(x, 3, 1).reshape(bsz * hkv, nc, chunk)  # [B, C, Q, H] -> [N, C, Q]
    segf = jnp.broadcast_to(segc.astype(f32)[:, :, :, None], cum.shape)
    kinds = [flat(x) for x in (cum, to_end, from_start, segf)]
    cols = jnp.stack(kinds, axis=-1).reshape(bsz * hkv, t, len(kinds))
    cols = jnp.pad(cols, ((0, 0), (0, 0), (0, 128 - len(kinds))))
    wide = flat(jnp.broadcast_to(carried[:, :, None], cum.shape))
    rows = jnp.stack([kinds[TO_END], kinds[CUM], kinds[SEG], wide], axis=2)
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, 8 - rows.shape[2]), (0, 0)))
    n = bsz * hkv
    arrays = (cols, rows, q.reshape(n, g, t, d), k.reshape(n, t, d), v.reshape(n, t, d))

    def specs(at):
        token = lambda width: pl.BlockSpec((None, chunk, width), lambda ni, ci: (ni, at(ci), 0))
        return (
            token(128),
            pl.BlockSpec((None, None, 8, chunk), lambda ni, ci: (ni, at(ci), 0, 0)),
            pl.BlockSpec((None, g, chunk, d), lambda ni, ci: (ni, 0, at(ci), 0)),
            token(d),
        )

    return arrays, specs


def _state_specs(blocks, d, at):
    return [
        pl.BlockSpec((None, None, blocks, d, d), lambda ni, ci: (ni, at(ci), 0, 0, 0)),
        pl.BlockSpec((None, None, blocks, 8, d), lambda ni, ci: (ni, at(ci), 0, 0, 0)),
    ]


@functools.partial(jax.jit, static_argnames=("chunk", "keep"))
def _forward(q, k, v, lam, seg, *, chunk, keep):
    """(o [B, H, G, T, d], den [N, T, 128] float32 (lane ``g``: head ``g``'s
    normaliser), the states entering the chunks or ()) of whole chunks."""
    bsz, hkv, g, t, d = q.shape
    n, nc, blocks, f32 = bsz * hkv, t // chunk, d // 2 + 1, jnp.float32
    arrays, specs = _operands(q, k, v, lam, seg, chunk)
    at = lambda ci: ci
    cols_spec, rows_spec, q_spec, kv_spec = specs(at)
    out_specs = [q_spec, cols_spec]
    out_shape = [jax.ShapeDtypeStruct((n, g, t, d), q.dtype), jax.ShapeDtypeStruct((n, t, 128), f32)]
    if keep:
        out_specs += _state_specs(blocks, d, at)
        out_shape += [
            jax.ShapeDtypeStruct((n, nc, blocks, d, d), f32),
            jax.ShapeDtypeStruct((n, nc, blocks, 8, d), f32),
        ]
    stacked = pltpu.VMEM((g * chunk, d), f32)
    o, den, *states = pl.pallas_call(
        functools.partial(_fwd_kernel, keep=keep),
        grid=(n, nc),
        in_specs=[cols_spec, rows_spec, q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((blocks, d, d), f32), pltpu.VMEM((blocks, 8, d), f32), stacked, stacked,
            pltpu.VMEM((chunk, d), f32), stacked, stacked,
        ],  # fmt: skip
        compiler_params=_COMPILER_PARAMS,
        interpret=_use_interpret(),
        name="retention_fwd",
    )(*arrays)
    return o.reshape(q.shape), den, tuple(states)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _backward(q, k, v, lam, seg, o, den, states, do, *, chunk):
    bsz, hkv, g, t, d = q.shape
    n, nc, blocks, f32 = bsz * hkv, t // chunk, d // 2 + 1, jnp.float32
    arrays, specs = _operands(q, k, v, lam, seg, chunk)
    cols = arrays[0]
    # d o -> (d num, d den) for o = num / (den + eps): d num = d o * whole
    # (the kernel's to form, a product at a time), d den = -(d o . o) * whole.
    whole = 1.0 / (jnp.moveaxis(den[:, :, :g], 2, 1) + EPS)  # [N, G, T]
    do = do.reshape(n, g, t, d)
    inner = jnp.sum(do.astype(f32) * o.reshape(n, g, t, d).astype(f32), axis=-1)  # [N, G, T]
    dden = -inner * whole
    ddcols = jnp.moveaxis(jnp.concatenate([dden, whole], axis=1), 1, 2)  # [N, T, 2 G]
    ddcols = jnp.pad(ddcols, ((0, 0), (0, 0), (0, 128 - 2 * g)))
    ddf = dden * cols[:, None, :, FROM_START]  # [N, G, T]
    ddrows = jnp.moveaxis(ddf.reshape(n, g, nc, chunk), 2, 1).reshape(n, nc, 1, g * chunk)
    ddrows = jnp.pad(ddrows, ((0, 0), (0, 0), (0, 7), (0, 0)))

    at = lambda ci: nc - 1 - ci
    cols_spec, rows_spec, q_spec, kv_spec = specs(at)
    stacked = pltpu.VMEM((g * chunk, d), f32)  # the group's heads as rows
    one = pltpu.VMEM((chunk, d), f32)
    dq, dk, dv = pl.pallas_call(
        _bwd_kernel,
        grid=(n, nc),
        in_specs=[cols_spec, rows_spec, q_spec, kv_spec, kv_spec]
        + _state_specs(blocks, d, at)
        + [
            q_spec,
            cols_spec,
            pl.BlockSpec((None, None, 8, g * chunk), lambda ni, ci: (ni, at(ci), 0, 0)),
        ],
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, g, t, d), f32),
            jax.ShapeDtypeStruct((n, t, d), f32),
            jax.ShapeDtypeStruct((n, t, d), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blocks, d, d), f32), pltpu.VMEM((blocks, 8, d), f32), stacked, stacked, one,
            stacked, stacked, stacked, stacked, one, one, one,
        ],  # fmt: skip
        compiler_params=_COMPILER_PARAMS,
        interpret=_use_interpret(),
        name="retention_bwd",
    )(*arrays, *states, do, ddcols, ddrows)

    # The gate: d lam_r = sum over t >= r of r's document of (R_t - C_t).
    rows_sum = jnp.sum(inner * EPS * whole, axis=1)  # R [N, T]
    cols_sum = 0.5 * jnp.sum(arrays[3].astype(f32) * dk, axis=-1)  # C [N, T]
    x = (rows_sum - cols_sum).reshape(bsz, hkv, t)
    upto = jnp.cumsum(x, axis=-1)
    ends = jnp.concatenate([seg[:, 1:] != seg[:, :-1], jnp.ones((bsz, 1), bool)], axis=1)
    end_at = jax.lax.cummin(jnp.where(ends, jnp.arange(t), t), axis=1, reverse=True)  # [B, T]
    at_end = jnp.take_along_axis(upto, jnp.broadcast_to(end_at[:, None], upto.shape), axis=-1)
    dlam = jnp.moveaxis(at_end - upto + x, 1, 2)  # [B, T, H]
    return (
        dq.reshape(q.shape).astype(q.dtype),
        dk.reshape(k.shape).astype(k.dtype),
        dv.reshape(v.shape).astype(v.dtype),
        dlam,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _retention_kernels(q, k, v, lam, seg, chunk):
    """Whole chunks, as the kernels compute them. q [B, H, G, T, d]; k, v
    [B, H, T, d]; lam [B, T, H] float32; seg [B, T]. Returns o like q."""
    return _forward(q, k, v, lam, seg, chunk=chunk, keep=False)[0]


def _kernels_fwd(q, k, v, lam, seg, chunk):
    o, den, states = _forward(q, k, v, lam, seg, chunk=chunk, keep=True)
    return o, (q, k, v, lam, seg, o, den, states)


def _kernels_bwd(chunk, residuals, do):
    return (*_backward(*residuals, do, chunk=chunk), None)


_retention_kernels.defvjp(_kernels_fwd, _kernels_bwd)


# ------------------------------------------------------------------- public
def power_retention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    log_decay: jax.Array,
    segment_ids: jax.Array,
    *,
    chunk: int = 512,
) -> jax.Array:
    """``o`` of the equations above, by head.

    q [B, H, G, T, d]: the ``G`` query heads of each of the ``H`` key/value
    heads; k, v [B, H, T, d]; log_decay [B, T, H] (``lam``, float32, <= 0);
    segment_ids [B, T] non-negative ints, constant along a document. Returns
    [B, H, G, T, d] in ``q``'s dtype. T need not be a multiple of ``chunk``:
    the tail is filled with tokens of no document that change nothing before
    them."""
    bsz, hkv, g, t, d = q.shape
    kernels = _takes(d, chunk)
    tracing.count("retention_kernel_calls" if kernels else "retention_xla_calls")
    pad = (-t) % chunk
    if pad:
        fill = lambda x, axis, value=0: jnp.pad(
            x, [(0, pad if a == axis else 0) for a in range(x.ndim)], constant_values=value
        )
        q, k, v = fill(q, 3), fill(k, 2), fill(v, 2)
        log_decay, segment_ids = fill(log_decay, 1), fill(segment_ids, 1, PADDING)
    lam = log_decay.astype(jnp.float32)
    if kernels:
        o = _retention_kernels(q, k, v, lam, segment_ids, chunk)
    else:
        o = _retention_xla(q, k, v, lam, segment_ids, chunk)
    return o[:, :, :, :t]


def _retention_xla(q, k, v, lam, seg, chunk):
    """The same chunks as XLA's products, ``phi`` the full square of ``d *
    d`` features: the form of every shape the kernels do not take, and their
    oracle. Operands as ``_retention_kernels`` takes them."""
    bsz, hkv, g, t, d = q.shape
    nc, dtype, f32 = t // chunk, q.dtype, jnp.float32
    segc = seg.reshape(bsz, nc, chunk)
    cum, to_end, from_start, carried = (
        jnp.moveaxis(x, -1, 1)  # the head before the chunk: [B, H, C, Q] and [B, H, C]
        for x in chunk_decays(lam.reshape(bsz, nc, chunk, hkv), segc)
    )
    q = q.reshape(bsz, hkv, g, nc, chunk, d)
    k, v = (x.reshape(bsz, hkv, nc, chunk, d) for x in (k, v))
    phi = lambda x: (x[..., :, None] * x[..., None, :]).reshape(x.shape[:-1] + (d * d,))

    # Within a chunk.
    same = segc[:, :, :, None] == segc[:, :, None, :]  # [B, C, i, j]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    a = jnp.einsum("bhgcid,bhcjd->bhgcij", q, k, preferred_element_type=f32)
    decay = _decay(cum[..., :, None] - cum[..., None, :], (same & causal)[:, None])
    s = a * a * (decay / d)[:, :, None]  # [B, H, G, C, i, j]
    num = jnp.einsum("bhgcij,bhcje->bhgcie", s.astype(dtype), v, preferred_element_type=f32)
    den = jnp.sum(s, axis=-1)

    # Each chunk's own contribution to the state at its end.
    fk = phi(k.astype(f32)) * (to_end[..., None] / d)  # [B, H, C, Q, D]
    own_s = jnp.einsum("bhcjn,bhcje->bhcne", fk.astype(dtype), v, preferred_element_type=f32)
    own_z = jnp.sum(fk, axis=3)

    def chunk_step(state, inp):
        keep, s_own, z_own = inp
        s_in, z_in = state
        return (keep[..., None, None] * s_in + s_own, keep[..., None] * z_in + z_own), state

    zero = (jnp.zeros((bsz, hkv, d * d, d), f32), jnp.zeros((bsz, hkv, d * d), f32))
    _, (enter_s, enter_z) = jax.lax.scan(
        chunk_step, zero, tuple(jnp.moveaxis(x, 2, 0) for x in (carried, own_s, own_z))
    )
    enter_s, enter_z = jnp.moveaxis(enter_s, 0, 2), jnp.moveaxis(enter_z, 0, 2)

    # What the carried state adds inside the chunk.
    fq = phi(q.astype(f32))  # [B, H, G, C, Q, D]
    num = num + from_start[:, :, None, :, :, None] * jnp.einsum(
        "bhgcin,bhcne->bhgcie", fq.astype(dtype), enter_s.astype(dtype), preferred_element_type=f32
    )
    den = den + from_start[:, :, None] * jnp.einsum("bhgcin,bhcn->bhgci", fq, enter_z)
    return (num / (den[..., None] + EPS)).reshape(bsz, hkv, g, t, d).astype(dtype)
