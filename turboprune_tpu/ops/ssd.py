"""The Mamba-2 recurrence in its chunked, state-space-dual form (Dao & Gu
2024, arXiv:2405.21060, section 6), over packed documents.

The recurrence, for one head with state ``h [P, N]``:

    h_t = exp(dt_t * a) * h_{t-1} + dt_t * x_t (outer) B_t,    y_t = h_t C_t,

with ``h = 0`` entering a document's first token. Token by token that is T
dependent steps of elementwise work; chunked at ``Q`` tokens it is four block
matrix products a chunk and one carried state:

    within a chunk   Y  = (L o (C B^T)) (dt o X),   L[i, j] = exp(sum a over j < k <= i)
    a chunk's state  S  = sum_j L[end, j] * dt_j x_j (outer) B_j
    across chunks    h  = exp(sum a over the chunk) * h + S     (a scan over T / Q)
    from the carry   Y += exp(sum a over k <= i) * C_i h

A document start between ``j`` and ``i`` cuts the product: ``L[i, j]`` and the
two carry terms are zero wherever the two ends lie in different documents.
Documents are contiguous, so "different ``segment_ids``" says exactly that.

Decays, cumulative sums and the carried state are float32; the block products
take operands in ``x``'s dtype and accumulate in float32. A masked decay is
``exp(-inf)``, never a large positive exponent multiplied by zero.

At the shapes a chip can tile (``_head_block``) the four products are two
Pallas kernels under a ``custom_vjp``, forward and backward, that walk the
chunks in order with the state in VMEM. ``x``, ``y`` and their gradients are
read and written where they lie, as columns of ``[B, T, H * P]``, once a pass;
a chunk's scores ``C B^T`` are computed once and reused over blocks of heads;
a head's decay and weights exist one ``[128, 128]`` tile at a time (the tiles
wholly above the diagonal are never formed); the backward recomputes them, so
no ``[Q, Q]`` tensor is written to HBM in any pass, and nothing ``[.., H, P]``
is ever scaled head by head outside a kernel (XLA lays such tensors out anew
for every product: measured, that cost more than the ``[Q, Q]`` tensors had).
What every (token, head) is scaled by (``dt``, the cumulative sums, the masked
decays to a chunk's end and from its start) stays XLA's, on ``[B, T, H]``
tensors, and so does its differentiation. At every other shape the whole scan
is XLA's (``_scan_xla``), which is also the kernels' oracle in the tests. On
the CPU the kernels run in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from turboprune_tpu.ops.flash import _dot, _dot_t0, _dot_t1, _use_interpret
from turboprune_tpu.utils import tracing

NO_DOCUMENT = -2  # what precedes a sequence's first token
PADDING = -1  # tokens added to fill the last chunk
TILE = 128  # the kernels' [TILE, TILE] piece of a chunk's [Q, Q]: one MXU pass, 16 float32 registers
HEAD_BLOCK_BYTES = 2**20  # of float32 [Q, heads * P] a grid step
VMEM_BYTES = 32 * 2**20  # a kernel's limit: at that block the backward takes 16.25 MiB, a v5e core has 128


def _decay(log_decay: jax.Array, keep: jax.Array) -> jax.Array:
    return jnp.exp(jnp.where(keep, log_decay, -jnp.inf))


def chunk_decays(log_decay: jax.Array, seg: jax.Array):
    """What scales every (token, head) of a chunked recurrence whose state is
    multiplied by ``exp(log_decay)`` a token and cut at document starts
    (ops/retention.py walks the same chunks). log_decay [B, C, Q, H] float32,
    seg [B, C, Q]. Returns the inclusive sums ``cum`` [B, C, Q, H], the masked
    decays from a token to its chunk's end (``to_end``) and from the chunk's
    start to the token (``from_start``), both [B, C, Q, H], and what the state
    entering a chunk is scaled by at its end (``carried`` [B, C, H])."""
    cum = jnp.cumsum(log_decay, axis=2)  # inclusive
    total = cum[:, :, -1]  # [B, C, H]
    last_seg = seg[:, :, -1]  # [B, C]
    before = jnp.concatenate(
        [jnp.full((seg.shape[0], 1), NO_DOCUMENT, seg.dtype), last_seg[:, :-1]], axis=1
    )
    to_end = _decay(total[:, :, None] - cum, (seg == last_seg[..., None])[..., None])
    from_start = _decay(cum, (seg == before[..., None])[..., None])
    carried = _decay(total, (last_seg == before)[..., None])  # [B, C, H]
    return cum, to_end, from_start, carried


def _head_block(heads: int, p: int, n: int, chunk: int) -> int:
    """How many heads a grid step of the kernels takes, from the shapes alone;
    0 where the kernels do not take the shape. They tile a chunk by ``TILE``,
    read ``x`` as whole 128-lane columns (two heads of 64 side by side, or one
    head of a multiple of 128) and ``B``, ``C`` as whole rows; a block is as
    many heads as ``HEAD_BLOCK_BYTES`` holds."""
    if chunk % TILE or n % 128 or not (p == 64 or p % 128 == 0):
        return 0
    lane_heads = max(1, 128 // p)
    fits = [
        hb
        for hb in range(lane_heads, heads + 1, lane_heads)
        if heads % hb == 0 and 4 * chunk * hb * p <= HEAD_BLOCK_BYTES
    ]
    return max(fits, default=0)


# ------------------------------------------------------------------ kernels
# Grid (batch, chunk, head block), chunks in order (the backward from the
# last) and the head blocks innermost: the first block of a chunk computes the
# chunk's scores and mask into scratch and the others reuse them; every block
# keeps its heads' state [N, hb * P] in scratch from one chunk to the next.
# Refs, a grid step: seg as a column [Q, 1] and as a row [1, Q]; C, B [Q, N];
# ``rows`` [4 * hb, Q], what every (token, head) is scaled by, the four kinds
# below one after the other, each the block's heads in order (so HBM holds
# whole lanes; the kernels transpose a block to have a head's tokens as a
# column too); ``carried`` [1, hb * P], what the state entering the chunk is
# scaled by at its end, said once a lane; x (and y, dy, dx) [Q, hb * P]; the
# state entering the chunk [N, hb * P].
DT, CUM, TO_END, FROM_START = range(4)  # dt; cum; masked exp(total - cum); masked exp(cum)


def _columns(rows_ref):
    """[Q, R]: ``rows_ref`` transposed, its rows filled up to R, a multiple of 128."""
    rows = rows_ref[...]
    fill = (-rows.shape[0]) % 128
    return jnp.concatenate([rows] + [jnp.zeros((fill, rows.shape[1]), rows.dtype)] * (fill > 0)).T


def _span(i):
    return slice(i * TILE, (i + 1) * TILE)


def _tiles(q):
    """The [TILE, TILE] tiles of [Q, Q] on or below the diagonal, (row, column)."""
    return [(r, k) for r in range(q // TILE) for k in range(r + 1)]


def _scores_and_mask(segc_ref, segr_ref, c_ref, b_ref, s_ref, bias_ref):
    q = s_ref.shape[0]
    s_ref[...] = _dot_t1(c_ref[...], b_ref[...])
    i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    keep = (segc_ref[...] == segr_ref[...]) & (i >= j)
    bias_ref[...] = jnp.where(keep, 0.0, -jnp.inf)


def _tile_weights(s_ref, bias_ref, rows_ref, cols, head, r, k):
    """(L, scores o L) of one head on tile (r, k), float32; L is exactly 0
    where masked, so neither needs the mask again."""
    at = CUM * (rows_ref.shape[0] // 4) + head
    exponent = cols[_span(r), at : at + 1] - rows_ref[at : at + 1, _span(k)]
    decay = jnp.exp(exponent + bias_ref[_span(r), _span(k)])
    return decay, s_ref[_span(r), _span(k)] * decay


def _lane_heads(q, p):
    """How a [Q, hb * P] ref is walked: in columns of (width) lanes, each of
    (side) heads side by side, with a mask [Q, width] a head, or None where a
    head is the whole column."""
    if p >= 128:
        return p, 1, [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, 128), 1)
    return 128, 128 // p, [(lane >= i * p) & (lane < (i + 1) * p) for i in range(128 // p)]


def _only(mask, v):
    return v if mask is None else jnp.where(mask, v, jnp.zeros_like(v))


def _add(acc, part):
    return part if acc is None else acc + part


def _over_lanes(cols, first, heads, masks):
    """[Q, width]: every lane holds its own head's column of ``cols``, the
    block's heads being columns ``first`` onwards."""
    out = cols[:, first + heads[0] : first + heads[0] + 1]
    for head, mask in zip(heads[1:], masks[1:]):
        out = jnp.where(mask, cols[:, first + head : first + head + 1], out)
    return out


def _scan_fwd_kernel(segc_ref, segr_ref, c_ref, b_ref, rows_ref, carried_ref, x_ref,
                     y_ref, enter_ref, s_ref, bias_ref, h_ref, *, p):
    chunk_i, block_i = pl.program_id(1), pl.program_id(2)

    @pl.when(block_i == 0)
    def _():
        _scores_and_mask(segc_ref, segr_ref, c_ref, b_ref, s_ref, bias_ref)

    @pl.when(chunk_i == 0)
    def _():
        h_ref[block_i] = jnp.zeros(h_ref.shape[1:], h_ref.dtype)

    q, lanes = x_ref.shape
    width, side, masks = _lane_heads(q, p)
    dtype, f32 = x_ref.dtype, jnp.float32
    cols, hb = _columns(rows_ref), rows_ref.shape[0] // 4
    for col in range(lanes // width):
        here, heads = slice(col * width, (col + 1) * width), range(col * side, (col + 1) * side)
        xdt = (x_ref[:, here].astype(f32) * _over_lanes(cols, DT * hb, heads, masks)).astype(dtype)
        # A head's product over the whole column, the other head's lanes
        # zero: the MXU pass is 128 wide either way, and nothing is shifted.
        xs = [_only(m, xdt) for m in masks]
        within = [None] * (q // TILE)
        for r, k in _tiles(q):
            for i, head in enumerate(heads):
                _, w = _tile_weights(s_ref, bias_ref, rows_ref, cols, head, r, k)
                within[r] = _add(within[r], _dot(w.astype(dtype), xs[i][_span(k)]))
        enter = h_ref[block_i, :, here]
        enter_ref[:, here] = enter
        carry = _dot(c_ref[...], enter.astype(dtype)) * _over_lanes(cols, FROM_START * hb, heads, masks)
        for r in range(q // TILE):
            y_ref[_span(r), here] = (within[r] + carry[_span(r)]).astype(dtype)
        to_end = (xdt.astype(f32) * _over_lanes(cols, TO_END * hb, heads, masks)).astype(dtype)
        h_ref[block_i, :, here] = carried_ref[:, here] * enter + _dot_t0(b_ref[...], to_end)


def _scan_bwd_kernel(segc_ref, segr_ref, c_ref, b_ref, rows_ref, carried_ref, x_ref,
                     enter_ref, dy_ref, dx_ref, drows_ref, dcarried_ref, dc_ref, db_ref,
                     s_ref, bias_ref, ds_ref, dcb_ref, g_ref, *, p):
    """The chunks from the last to the first, ``g_ref`` the cotangent of the
    state leaving the chunk. Within a chunk, with W = scores o L of a head:
    d xdt = W^T dy; dW = dy xdt^T; d scores = sum over the heads of dW o L,
    and from it C's and B's part at the chunk's last head block;
    d cum_i = sum_j (dW o W)_ij - sum_j (dW o W)_ji = dy_i . y_i - xdt_i . d xdt_i
    with y the within-chunk product, recomputed here: two [Q, P] products in
    place of two reductions over [Q, Q]. A head's sums over its P lanes are
    gathered as columns and leave transposed, as ``rows_ref`` came."""
    chunk_i, block_i, last = pl.program_id(1), pl.program_id(2), pl.num_programs(2) - 1

    @pl.when(block_i == 0)
    def _():
        _scores_and_mask(segc_ref, segr_ref, c_ref, b_ref, s_ref, bias_ref)
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dcb_ref[...] = jnp.zeros_like(dcb_ref)

    @pl.when(chunk_i == 0)
    def _():
        g_ref[block_i] = jnp.zeros(g_ref.shape[1:], g_ref.dtype)

    q, lanes = x_ref.shape
    width, side, masks = _lane_heads(q, p)
    dtype, f32 = x_ref.dtype, jnp.float32
    cols, hb = _columns(rows_ref), rows_ref.shape[0] // 4
    column = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    dcols = jnp.zeros(cols.shape, f32)

    def head_sums(dcols, which, v):  # the sum of v [Q, width] over each head's lanes, into its column
        for head, mask in zip(heads, masks):
            mine = jnp.sum(_only(mask, v), axis=1, keepdims=True)
            dcols = jnp.where(column == which * hb + head, mine, dcols)
        return dcols

    for col in range(lanes // width):
        here, heads = slice(col * width, (col + 1) * width), range(col * side, (col + 1) * side)
        x, dy = x_ref[:, here].astype(f32), dy_ref[:, here]
        dt = _over_lanes(cols, DT * hb, heads, masks)
        xdt = (x * dt).astype(dtype)
        xs, dys = [_only(m, xdt) for m in masks], [_only(m, dy) for m in masks]
        y, dxdt = [None] * (q // TILE), [None] * (q // TILE)
        for r, k in _tiles(q):
            for i, head in enumerate(heads):
                decay, w = _tile_weights(s_ref, bias_ref, rows_ref, cols, head, r, k)
                w = w.astype(dtype)
                y[r] = _add(y[r], _dot(w, xs[i][_span(k)]))
                dxdt[k] = _add(dxdt[k], _dot_t0(w, dys[i][_span(r)]))
                ds_ref[_span(r), _span(k)] += _dot_t1(dys[i][_span(r)], xdt[_span(k)]) * decay
        y, dxdt, dy = jnp.concatenate(y), jnp.concatenate(dxdt), dy.astype(f32)
        dcols = head_sums(dcols, CUM, dy * y - xdt.astype(f32) * dxdt)

        # The carried state's term, y += from_start o (C enter).
        enter, from_start = enter_ref[:, here], _over_lanes(cols, FROM_START * hb, heads, masks)
        dcols = head_sums(dcols, FROM_START, dy * _dot(c_ref[...], enter.astype(dtype)))
        dz = (dy * from_start).astype(dtype)
        dcb_ref[0] += _dot_t1(dz, enter.astype(dtype))
        # The state: leaving = carried o enter + B^T (to_end o xdt).
        g = g_ref[block_i, :, here]
        dcarried_ref[:, here] = jnp.sum(g * enter, axis=0, keepdims=True)
        g_ref[block_i, :, here] = carried_ref[:, here] * g + _dot_t0(c_ref[...], dz)
        to_end = _over_lanes(cols, TO_END * hb, heads, masks)
        dcb_ref[1] += _dot_t1((xdt.astype(f32) * to_end).astype(dtype), g.astype(dtype))
        d_scaled = _dot(b_ref[...], g.astype(dtype))
        dcols = head_sums(dcols, TO_END, d_scaled * xdt.astype(f32))
        dxdt = dxdt + d_scaled * to_end
        dcols = head_sums(dcols, DT, dxdt * x)
        dx_ref[:, here] = (dxdt * dt).astype(dtype)
    drows_ref[...] = dcols.T[: drows_ref.shape[0]]

    @pl.when(block_i == last)
    def _():
        ds = ds_ref[...].astype(b_ref.dtype)
        dc_ref[...] = (dcb_ref[0] + _dot(ds, b_ref[...])).astype(dc_ref.dtype)
        db_ref[...] = (dcb_ref[1] + _dot_t0(ds, c_ref[...])).astype(db_ref.dtype)


def _scan_call(kernel, name, backward, operands, more, outs, scratch):
    """One of the two kernels over (batch, chunk, head block). ``operands``
    as ``_scan_kernels`` takes them; ``more`` further inputs as (array, kind)
    and ``outs`` the outputs as (kind, dtype), a kind one of ``like`` below."""
    x, rows, carried, b, c, segment_ids = operands
    (bsz, t, lanes), (_, nc, blocks, _, chunk), n = x.shape, rows.shape, b.shape[-1]
    width = lanes // blocks
    at = (lambda ci: nc - 1 - ci) if backward else (lambda ci: ci)
    like = {  # kind: (shape, the block of one grid step)
        "x": (x.shape, pl.BlockSpec((None, chunk, width), lambda bi, ci, hi: (bi, at(ci), hi))),
        "states": (
            (bsz, nc, n, lanes),
            pl.BlockSpec((None, None, n, width), lambda bi, ci, hi: (bi, at(ci), 0, hi)),
        ),
        "rows": (
            rows.shape,
            pl.BlockSpec((None, None, None) + rows.shape[3:], lambda bi, ci, hi: (bi, at(ci), hi, 0, 0)),
        ),
        "carried": (
            carried.shape,
            pl.BlockSpec((None, None, 1, width), lambda bi, ci, hi: (bi, at(ci), 0, hi)),
        ),
        "b": (b.shape, pl.BlockSpec((None, chunk, n), lambda bi, ci, hi: (bi, at(ci), 0))),
    }
    seg = segment_ids.astype(jnp.int32)
    inputs = [
        (seg[:, :, None], pl.BlockSpec((None, chunk, 1), lambda bi, ci, hi: (bi, at(ci), 0))),
        (seg[:, None, :], pl.BlockSpec((None, 1, chunk), lambda bi, ci, hi: (bi, 0, at(ci)))),
        (c, like["b"][1]),
        (b, like["b"][1]),
        (rows, like["rows"][1]),
        (carried, like["carried"][1]),
        (x, like["x"][1]),
    ] + [(v, like[kind][1]) for v, kind in more]
    square = pltpu.VMEM((chunk, chunk), jnp.float32)
    return pl.pallas_call(
        functools.partial(kernel, p=width * 4 // rows.shape[3]),
        grid=(bsz, nc, blocks),
        in_specs=[spec for _, spec in inputs],
        out_specs=[like[kind][1] for kind, _ in outs],
        out_shape=[jax.ShapeDtypeStruct(like[kind][0], dtype) for kind, dtype in outs],
        scratch_shapes=[square, square] + scratch + [pltpu.VMEM((blocks, n, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"), vmem_limit_bytes=VMEM_BYTES
        ),
        interpret=_use_interpret(),
        name=name,
    )(*[v for v, _ in inputs])


@jax.custom_vjp
def _scan_kernels(x, rows, carried, b, c, segment_ids):
    """The scan of whole chunks as the kernels compute it, the operands as
    their blocks take them. x [B, T, H * P]; rows [B, T / Q, H / hb, 4 * hb, Q]
    float32: ``DT``, ``CUM``, ``TO_END``, ``FROM_START`` of a block's heads,
    one kind after the other; carried [B, T / Q, 1, H * P] float32, a head's
    value in each of its lanes; b, c [B, T, N]; segment_ids [B, T]. Returns y
    like x."""
    return _scan_fwd(x, rows, carried, b, c, segment_ids)[0]


# Both jitted: the nine layers of a model then share one traced and lowered
# kernel a pass (tracing a kernel's unrolled body takes about a second).
@jax.jit
def _scan_fwd(*operands):
    x = operands[0]
    y, entering = _scan_call(
        _scan_fwd_kernel, "ssd_scan_fwd", False, operands, [], [("x", x.dtype), ("states", jnp.float32)], []
    )
    return y, operands + (entering,)


@jax.jit
def _scan_bwd(residuals, g):
    *operands, entering = residuals
    x, rows, _, b, c, _ = operands
    chunk, f32 = rows.shape[-1], jnp.float32
    dx, drows, dcarried, dc, db = _scan_call(
        _scan_bwd_kernel, "ssd_scan_bwd", True, operands, [(entering, "states"), (g, "x")],
        [("x", x.dtype), ("rows", f32), ("carried", f32), ("b", c.dtype), ("b", b.dtype)],
        [pltpu.VMEM((chunk, chunk), f32), pltpu.VMEM((2, chunk, b.shape[-1]), f32)],
    )
    return dx, drows, dcarried, db, dc, None


_scan_kernels.defvjp(_scan_fwd, _scan_bwd)


# ------------------------------------------------------------------- public
def ssd_chunked(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    segment_ids: jax.Array,
    chunk: int = 256,
) -> jax.Array:
    """``y_t = h_t C_t`` of the recurrence above.

    x [B, T, H, P]; dt [B, T, H] (after softplus, float32); a [H] (negative,
    float32); b, c [B, T, N] (one group, shared by the heads) or [B, T, G, N]
    (head ``i`` reads group ``i // (H / G)``: the groups are independent scans
    of H / G heads each); segment_ids [B, T] non-negative ints, constant along
    a document. Returns [B, T, H, P] in ``x``'s dtype. T need not be a
    multiple of ``chunk``: the tail is filled with tokens of no document that
    change nothing before them."""
    bsz, t, heads, p = x.shape
    if b.ndim == 4:
        groups = b.shape[2]
        if groups == 1:
            return ssd_chunked(x, dt, a, b[:, :, 0], c[:, :, 0], segment_ids, chunk)
        # The group first: a vmapped kernel keeps tokens and lanes as the last two axes of its blocks.
        split = lambda v: jnp.moveaxis(v.reshape(v.shape[:2] + (groups, heads // groups) + v.shape[3:]), 2, 0)
        per_group = jax.vmap(lambda x, dt, a, b, c: ssd_chunked(x, dt, a, b, c, segment_ids, chunk))
        y = per_group(split(x), split(dt), a.reshape(groups, -1), jnp.moveaxis(b, 2, 0), jnp.moveaxis(c, 2, 0))
        return jnp.moveaxis(y, 0, 2).reshape(x.shape)
    hb = _head_block(heads, p, b.shape[-1], chunk)
    tracing.count("ssd_kernel_calls" if hb else "ssd_xla_calls")
    pad = (-t) % chunk
    if pad:
        fill = lambda v, value=0: jnp.pad(
            v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2), constant_values=value
        )
        x, dt, b, c = fill(x), fill(dt), fill(b), fill(c)
        segment_ids = fill(segment_ids, PADDING)
    nc = (t + pad) // chunk
    f32 = jnp.float32

    seg = segment_ids.reshape(bsz, nc, chunk)
    dtc = dt.astype(f32).reshape(bsz, nc, chunk, heads)
    cum, to_end, from_start, carried = chunk_decays(dtc * a.astype(f32), seg)

    # The four kinds a head, [B, C, 4, H, Q]: whole lanes of tokens.
    rows = jnp.stack([v.transpose(0, 1, 3, 2) for v in (dtc, cum, to_end, from_start)], axis=2)
    x = x.reshape(bsz, nc * chunk, heads * p)
    if hb:
        blocks = heads // hb
        rows = rows.reshape(bsz, nc, 4, blocks, hb, chunk).transpose(0, 1, 3, 2, 4, 5)
        rows = rows.reshape(bsz, nc, blocks, 4 * hb, chunk)  # a block's four kinds together
        y = _scan_kernels(x, rows, jnp.repeat(carried, p, axis=-1)[:, :, None], b, c, segment_ids)
    else:
        y = _scan_xla(x, rows, carried, b, c, seg)
    return y.reshape(bsz, nc * chunk, heads, p)[:, :t]


def _scan_xla(x, rows, carried, b, c, seg):
    """The same scan as XLA's products: the form of every shape the kernels
    do not take, and their oracle. x [B, T, H * P]; rows [B, C, 4, H, Q];
    carried [B, C, H]; seg [B, C, Q]. Returns y like x."""
    (bsz, nc, _, heads, chunk), dtype, f32 = rows.shape, x.dtype, jnp.float32
    dt, cum, to_end, from_start = (rows[:, :, kind] for kind in (DT, CUM, TO_END, FROM_START))
    # Chunked, heads before tokens: the products batch over (B, chunk, H).
    x = x.reshape(bsz, nc, chunk, heads, -1).transpose(0, 1, 3, 2, 4)  # [B, C, H, Q, P]
    xdt = (x.astype(f32) * dt[..., None]).astype(dtype)
    bc = b.reshape(bsz, nc, chunk, -1)
    cc = c.reshape(bsz, nc, chunk, -1)

    # Within a chunk.
    same = seg[:, :, :, None] == seg[:, :, None, :]  # [B, C, i, j]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    scores = jnp.einsum("bcin,bcjn->bcij", cc, bc, preferred_element_type=f32)
    decay = _decay(cum[..., :, None] - cum[..., None, :], (same & causal)[:, :, None])
    weights = (scores[:, :, None] * decay).astype(dtype)  # [B, C, H, i, j]
    y = jnp.einsum("bchij,bchjp->bchip", weights, xdt, preferred_element_type=f32)

    # Each chunk's own contribution to the state at its end.
    states = jnp.einsum(
        "bchjp,bcjn->bchpn",
        (xdt.astype(f32) * to_end[..., None]).astype(dtype),
        bc,
        preferred_element_type=f32,
    )

    def chunk_step(h, inp):
        keep, own = inp
        return keep[..., None, None] * h + own, h

    h0 = jnp.zeros((bsz, heads, x.shape[-1], bc.shape[-1]), f32)
    _, entering = jax.lax.scan(
        chunk_step, h0, (jnp.moveaxis(carried, 1, 0), jnp.moveaxis(states, 1, 0))
    )
    entering = jnp.moveaxis(entering, 0, 1)  # [B, C, H, P, N]: the state entering each chunk

    # What the carried state adds inside the chunk.
    y = y + from_start[..., None] * jnp.einsum(
        "bcin,bchpn->bchip", cc, entering.astype(dtype), preferred_element_type=f32
    )
    return y.transpose(0, 1, 3, 2, 4).reshape(bsz, nc * chunk, -1).astype(dtype)
