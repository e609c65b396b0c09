"""Routed experts as one chip of an expert-parallel deployment runs them.

A router scores every expert of the layer and picks ``top_k`` of them for
each token; a chip holds ``experts_here`` of the experts, those from
``expert_offset`` on, and computes the (token, expert) pairs whose expert it
holds. What the other experts would add is not computed here and not stood
in for: the result is this chip's part of the layer's sum.

    part = sum over i in top, i held here, of  w_i * f_i(z)

Two routers give (top, w), both float32 over all experts:

    ``route``           s = sigmoid(logits);  top = the top_k largest of s + bias
                        (bias: no gradient);  w_i = scaling * s_i / (sum_top s + eps)
    ``route_softmax``   p = softmax(logits);  top = the top_k largest of p;
                        w_i = p_i / sum_top p

and an expert is what its stacked kernels say (``routed_experts``'s
``kernels``): two of them, ``f(z) = W2 relu(W1 z)^2``; three, gated,
``f(z) = W_down (silu(W_gate z) * W_up z)``. Everything else (the sort, the
buffer, the grouped products, the further rounds, the scatter-add, the
counters) is one path for both: which router and which expert a model has is
read off what it passes, at trace time, and the program of a model that
passes two kernels is what it was before there were three
(tests/test_sdar.py holds its text to the commit before).

The pairs held here are a data-dependent number. What the configuration
fixes is the memory and the programs: the pairs are sorted by expert and laid
into a buffer of ``capacity`` rows (``pair_capacity``: half as many pairs
again as a uniform router sends here, and a tile for each expert), every
expert's rows starting on a multiple of ``tile``, so that a tile of rows
belongs to one expert, and no shape depends on the routing, so no seed
compiles anything. What follows the routing is the tiles the products run.
They are grouped matrix products (the Pallas kernels of
``jax.experimental.pallas.ops.tpu.megablox``, a tile of rows against its
expert's kernel) whose group sizes are the rows the round's pairs fill, each
expert's on whole tiles, and nothing else: the kernels' grid is as long as
the groups' tiles (a scalar they read), so they stop at the last pair's tile
and run no tile of the buffer's slack (``moe_rows_run`` counts the rows they
are handed; a step costs what its routing's rows cost). **The rows past the
last group are written by no product**: memory nobody initialised (NaN under
the interpreter, whatever was there on a chip). Whatever reads such a row
selects and never multiplies: the activation between the products is
elementwise (a row's garbage stays in its row, read only by the next
product, which stops where the first did); ``tgmm`` visits a group's tiles
and masks them by a select; ``add_rows`` by the kernel never visits a row
that is not ``valid``, by XLA takes ``where(valid)`` before the weight; the
weights' gradient is a ``where(valid)``; the counters read the plan, not the
buffer (each reader says so where it reads). Within a group the rows no pair
fills (an expert's last, part-filled tile) are zeros (or, where ``add_rows``
is the kernel, a copy of a token's row under a weight of zero): computed, and
adding nothing to the result or to a gradient. No
pair is ever dropped: pairs that outgrow the buffer run through the same
round again, once for each further ``capacity`` rows they fill (at most the
worst case, every token choosing every held expert), so an imbalance costs
time and never changes the result. ``moe_dropped_pairs`` is counted, not
assumed: the pairs routed here less the rows of them handed to the products.

What moves the rows between the token array and the buffer: out of the
token array, XLA's gather (``take_rows``), which runs at the memory's pace
(0.18 ms for the block-diffusion cell's 26,624 bfloat16 rows of 2,048); into
it, whatever their shape allows, decided at trace time
(``rows_move_in_tiles``). A row that is whole (8, 128) tiles of 32-bit words
(1,024 float32 values, 2,048 bfloat16 ones, and any multiple) in a buffer of
``TILE``-row tiles is added by a Pallas kernel (``add_rows``): the
scatter-add read as a gather, a tile of 128 tokens fetching the rows that add
to it (from one sort of the buffer's rows by destination, made once a round)
by one copy each, a chunk of 128 started together and waited together, and
adding them in float32 in VMEM, so that no row of HBM is read, added to and
written back and no collision is serialised. The rows no pair fills are never
visited. Both scatter-adds of a round are that kernel: the combine's, and
the gather's transpose in a backward pass (accumulated in float32, cast once).
Such a row travels under a view in which it is whole tiles, which costs XLA
one pass over the kernel's source (a reshape). Any other shape (the small
tile, the tiny models' widths, the sparse-expert cell's bfloat16 rows of
1,024) is added by XLA's scatter-add, row by row, as every shape was before.
``moe_row_kernel_calls`` and ``moe_row_xla_calls`` (utils/tracing.py) count
the traced calls of the two functions by the form they took.

Named scopes: ``moe/dispatch`` (sort, group sizes, the sort by destination,
the gather of the pairs' rows and, in a backward pass, ``add_rows`` of their
gradients), ``moe/experts`` (the grouped products and the activation between
them, nothing else), ``moe/combine`` (``add_rows``, the weighted scatter-add
back to the tokens, and in a backward pass the gather of its cotangent).

Two values are tagged for a ``jax.checkpoint`` policy (ops/remat.py): ``router_top``,
the experts chosen, in ``route`` before anything reads them, and
``moe_order``, the pairs' sorted order, in ``routed_experts``: 0.7 MB each
at 8,192 tokens choosing 22, against a ``top_k`` and a sort of 180,224 keys
to rebuild them. Nothing inside ``_every_round`` is tagged: a backward pass
runs the grouped products' forward again.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from ..utils import tracing
from .flash import _use_interpret

# What a layer of routed experts counts a step (summed over the layers by
# train/steps.py and over the steps by ``scan_chunk``): the pairs routed to
# experts held here, those of them no product computed, the fullest held
# expert's pairs, and the rows of the buffer the grouped products ran (the
# pairs and what aligns each expert's last tile, over every round).
COUNTERS = ("moe_pairs", "moe_dropped_pairs", "moe_load_max", "moe_rows_run")
CAPACITY_FACTOR = 1.5
# The rows a product takes at a time, and what an expert's rows are aligned
# to: the matrix unit's 128 where an expert expects as many, else a sublane.
TILE, SMALL_TILE = 128, 8
# A product's block of an expert's kernel stays under this in VMEM (twice, for
# the two buffers a block has).
KERNEL_BLOCK_BYTES = 3 * 2**20
# A row travels as 32-bit words, 128 to a lane row; a copy moves whole
# (8, 128) tiles of them.
LANES, SUBLANES = 128, 8


# What stands between an expert's first kernels' products and its last
# kernel, by how many come first: ``relu(up)^2``, or gated, ``silu(gate) * up``.
BETWEEN = {
    1: lambda up: jnp.square(jax.nn.relu(up)),
    2: lambda gate, up: jax.nn.silu(gate) * up,
}


def route(logits: jax.Array, bias: jax.Array, top_k: int, scaling: float, eps: float = 1e-20):
    """(top [N, K] int32, weights [N, K] float32) of ``logits`` [N, E]; ``eps`` as the model's source has it."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, top = lax.top_k(s + lax.stop_gradient(bias.astype(jnp.float32)), top_k)
    # Before its first use: tagged on return, the gather below still reads
    # the ``top_k``'s own result and a backward pass sorts again.
    top = checkpoint_name(top, "router_top")
    chosen = jnp.take_along_axis(s, top, axis=-1)
    return top, scaling * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + eps)


def route_softmax(logits: jax.Array, top_k: int):
    """(top [N, K] int32, weights [N, K] float32) of ``logits`` [N, E]: the
    softmax over all experts, its ``top_k`` largest, renormalised over them."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, top = lax.top_k(p, top_k)
    top = checkpoint_name(top, "router_top")  # before its first use, as in ``route``
    chosen = jnp.take_along_axis(p, top, axis=-1)
    return top, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def pair_tile(tokens: int, top_k: int, experts: int) -> int:
    """``TILE`` where a uniform router sends an expert that many rows."""
    return TILE if tokens * top_k >= TILE * experts else SMALL_TILE


def pair_capacity(tokens: int, top_k: int, experts: int, experts_here: int) -> int:
    """Rows of the pair buffer: half as many pairs again as a uniform router
    sends here and a tile for each expert's last, part-filled one; at most
    the worst case (every token at every held expert it can choose, each
    expert's last tile all but empty); whole tiles; of the configuration
    alone."""
    tile = pair_tile(tokens, top_k, experts)
    expected = tokens * top_k * experts_here / experts
    rows = math.ceil(CAPACITY_FACTOR * expected / tile) * tile + experts_here * tile
    worst = tokens * min(top_k, experts_here) + experts_here * (tile - 1)
    return min(rows, math.ceil(worst / tile) * tile)


def rounds(top: jax.Array, expert_offset: int, held: int, capacity: int, tile: int) -> jax.Array:
    """How many rounds of ``capacity`` rows ``routed_experts`` takes for
    ``top`` [N, K]: one, and one more for each further buffer the held pairs
    fill, each expert's rows on whole tiles (int32 scalar; a counter for a
    model that wants it, beside ``COUNTERS``)."""
    local = top.reshape(-1) - expert_offset
    sizes = jnp.sum(local[:, None] == jnp.arange(held, dtype=local.dtype), axis=0, dtype=jnp.int32)
    rows = jnp.sum(-(-sizes // tile) * tile)
    return jnp.maximum(-(-rows // capacity), 1).astype(jnp.int32)


def _kernel_block(k: int, n: int, itemsize: int) -> tuple[int, int]:
    """(tk, tn) of a [k, n] kernel: the whole contraction, so that an
    expert's block is fetched once for all its tiles of rows, and the most
    columns (a divisor of n in 128s) that ``KERNEL_BLOCK_BYTES`` hold."""
    if n % 128:
        return k, n
    fits = [d * 128 for d in range(1, n // 128 + 1) if n % (d * 128) == 0
            and k * d * 128 * itemsize <= KERNEL_BLOCK_BYTES]  # fmt: skip
    return k, max(fits, default=128)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_product(x, kernels, sizes, tile, out_dtype):
    """``x`` [R, K] against ``kernels`` [G, K, N] by groups of rows: rows
    ``sum(sizes[:g]) .. sum(sizes[:g + 1])`` meet ``kernels[g]``. ``sizes``
    (int32) are multiples of ``tile`` and sum to at most R: the kernel's grid
    is ``sum(sizes) / tile`` tiles of rows long, and the rows past them are
    neither read nor written. Returns [R, N], those rows uninitialised."""
    k, n = kernels.shape[1:]
    return gmm(
        x, kernels, sizes, out_dtype, (tile, *_kernel_block(k, n, kernels.dtype.itemsize)),
        interpret=_use_interpret(),
    )  # fmt: skip


def _grouped_fwd(x, kernels, sizes, tile, out_dtype):
    return grouped_product(x, kernels, sizes, tile, out_dtype), (x, kernels, sizes)


def _grouped_bwd(tile, out_dtype, saved, dy):
    del out_dtype
    x, kernels, sizes = saved
    k, n = kernels.shape[1:]
    dy = dy.astype(x.dtype)
    # Past the last group ``dx`` is as uninitialised as the forward's result there.
    dx = gmm(
        dy, kernels, sizes, x.dtype, (tile, *_kernel_block(n, k, kernels.dtype.itemsize)),
        transpose_rhs=True, interpret=_use_interpret(),
    )  # fmt: skip
    # A block of the kernel's gradient is accumulated in float32. Rows no
    # product wrote (``x`` where it is the activation, ``dy`` where it is the
    # next product's ``dx``) are safe here: ``tgmm`` walks each group's tiles,
    # none past the last group, and masks a tile's rows by a select.
    dk = tgmm(
        x.swapaxes(0, 1), dy, sizes, kernels.dtype, (tile, *_kernel_block(k, n, 4)),
        num_actual_groups=kernels.shape[0], interpret=_use_interpret(),
    )  # fmt: skip
    return dx, dk, None


grouped_product.defvjp(_grouped_fwd, _grouped_bwd)


# ------------------------------------------------------------- rows in tiles
# A row copy moves whole (8, 128) tiles of 32-bit words: Mosaic refuses a
# slice of one row of a tiled [R, L] array, whatever its dtype. So a row
# travels under a view in which it IS whole tiles, ``[R * L / 128, 128]``,
# its ``L / 128`` lane rows a multiple of 8 (float32) or 16 (bfloat16, whose
# lane rows lie in pairs, 2k and 2k + 1 in the halves of one row of words).
# The view is one pass of XLA's over the kernel's source (a reshape that moves
# data: the tiles of ``[R, L]`` hold 8 rows' chunks, the view's 8 chunks of
# one row); the result comes out of the kernel in its own layout.


def _whole_tiles(dtype, width: int) -> bool:
    """Whether a row of ``width`` values is whole tiles of 32-bit words."""
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False
    return width % (LANES * SUBLANES * 4 // jnp.dtype(dtype).itemsize) == 0


def rows_move_in_tiles(dtype, width: int, rows: int, rows_out: int, tile: int) -> bool:
    """Whether the rows of a ``[rows, width]`` buffer are added to
    ``[rows_out, width]`` tokens by the Pallas kernel: decided on shape, at
    trace time."""
    return tile == TILE and rows % TILE == 0 and rows_out % TILE == 0 and _whole_tiles(dtype, width)


def _halves(words):
    """The two bfloat16 values of each word (low half, high half), as float32."""
    value = lambda bits: lax.bitcast_convert_type(bits, jnp.float32)
    return value(words << 16), value(words & jnp.uint32(0xFFFF0000))


def _row_copy(src, dst, sem, q, i, r):
    """Row ``i`` of ``src`` to row ``r`` of ``dst``, ``q`` lane rows each."""
    at = lambda n: pl.ds(pl.multiple_of(n * q, q), q)
    return pltpu.make_async_copy(src.at[at(i)], dst.at[at(r)], sem)


def _wait_rows(src, dst, sem, q, n):
    """Until ``n`` row copies on ``sem`` have landed (each counts the same bytes)."""

    def wait(_, carry):
        _row_copy(src, dst, sem, q, 0, 0).wait()
        return carry

    lax.fori_loop(0, n, wait, 0)


def _add_kernel(order_ref, bounds_ref, dest_ref, w_ref, upd_ref, out_ref, rows, acc, sems, fetches, *, q, packed, one_pass):
    tile = out_ref.shape[0]
    t, tiles = pl.program_id(0), pl.num_programs(0)
    here = lax.broadcasted_iota(jnp.int32, (tile, TILE), 0) + t * tile
    at = lax.broadcasted_iota(jnp.int32, (TILE, 1), 0)

    def stretch(t):
        """Tile ``t``'s stretch of the sorted order, and the chunks of 128
        sorted rows it lies in: at least one, so that every tile has a first
        fetch for the tile before it to start."""
        lo, hi = bounds_ref[t], bounds_ref[t + 1]
        return lo, hi, lo // TILE, jnp.maximum((hi + TILE - 1) // TILE, lo // TILE + 1)

    def span(lo, hi, c):
        a = jnp.maximum(lo, c * TILE)
        return a, jnp.maximum(jnp.minimum(hi, (c + 1) * TILE), a)

    def fetch(lo, hi, c, slot):
        a, b = span(lo, hi, c)

        def start(j, carry):
            _row_copy(upd_ref, rows.at[slot], sems.at[slot], q, order_ref[j], j - c * TILE).start()
            return carry

        lax.fori_loop(a, b, start, 0)

    lo, hi, c0, c1 = stretch(t)

    @pl.when(t == 0)
    def _():
        fetches[0] = 0
        fetch(lo, hi, c0, 0)

    acc[...] = jnp.zeros(acc.shape, acc.dtype)

    def chunk(c, carry):
        """The stretch's part of sorted rows ``c * 128 ..``, fetched while
        the chunk before it was added: added by one product with the
        [tile, 128] matrix that holds row ``j``'s weight at (its destination,
        ``j``), zeros elsewhere (also for the chunk's rows of the neighbouring
        tiles, which are not fetched)."""
        slot = fetches[0] % 2
        fetches[0] += 1

        # The next chunk's copies fly while this one's rows are added: this
        # tile's next, or the next tile's first.
        @pl.when(c + 1 < c1)
        def _():
            fetch(lo, hi, c + 1, 1 - slot)

        @pl.when((c + 1 == c1) & (t + 1 < tiles))
        def _():
            fetch(*stretch(t + 1)[:3], 1 - slot)

        a, b = span(lo, hi, c)
        _wait_rows(upd_ref, rows.at[slot], sems.at[slot], q, b - a)
        # bfloat16 lane rows 2k and 2k + 1 are the halves of row k of 32-bit words.
        words = rows.at[slot].bitcast(jnp.uint32) if packed else rows.at[slot]

        @pl.when(b > a)
        def _():
            fetched = (at >= a - c * TILE) & (at < b - c * TILE)
            # bfloat16 values under weights of one: a single pass is exact.
            operand = (lambda v: v.astype(jnp.bfloat16)) if one_pass else (lambda v: v)
            precision = None if one_pass else lax.Precision.HIGHEST
            place = operand(jnp.where(here == dest_ref[pl.ds(c, 1), :], w_ref[pl.ds(c, 1), :], 0.0))
            per_word = 2 if packed else 1
            for s in range(q // per_word):
                part = jnp.where(fetched, words[pl.ds(s, TILE, stride=q // per_word), :], 0)
                for k, values in enumerate(_halves(part) if packed else (part,)):
                    cols = slice((per_word * s + k) * LANES, (per_word * s + k + 1) * LANES)
                    acc[:, cols] += jnp.dot(
                        place, operand(values), precision=precision, preferred_element_type=jnp.float32
                    )

        return carry

    lax.fori_loop(c0, c1, chunk, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def _add_call(upd, by_dest, weights, rows_out, out_dtype):
    order, dest, bounds, sorted_weights = by_dest
    q = upd.shape[1] // LANES
    packed = upd.dtype == jnp.bfloat16
    w = jnp.ones(order.shape, jnp.float32) if weights is None else sorted_weights
    resident = pl.BlockSpec((order.shape[0] // TILE, TILE), lambda t, *_: (0, 0))
    return pl.pallas_call(
        functools.partial(_add_kernel, q=q, packed=packed, one_pass=packed and weights is None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows_out // TILE,),
            in_specs=[resident, resident, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((TILE, upd.shape[1]), lambda t, *_: (t, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, TILE * q, LANES), upd.dtype),  # a chunk's rows, and the next one's on their way
                pltpu.VMEM((TILE, upd.shape[1]), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),  # chunks fetched so far: its parity is the slot
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows_out, upd.shape[1]), out_dtype),
        interpret=_use_interpret(),
        name="moe_add_rows",
    )(order, bounds, dest.reshape(-1, TILE), w.reshape(-1, TILE), upd.reshape(-1, LANES))


def by_destination(idx: jax.Array, rows_out: int, weights: jax.Array):
    """What ``add_rows`` walks: the buffer's rows sorted by the row of the
    token array they add to (``idx``; negative: none, sorted last), as
    (order [R], their destinations [R], [rows_out / 128 + 1] bounds of each
    tile of 128 destinations' stretch of the order, their ``weights`` [R]
    float32): one sort, and no gather of the weights after it."""
    dest = jnp.where(idx >= 0, idx, rows_out).astype(jnp.int32)
    rows = jnp.arange(idx.shape[0], dtype=jnp.int32)
    dest, order, weights = lax.sort((dest, rows, weights.astype(jnp.float32)), num_keys=1)
    tiles = jnp.arange(0, rows_out + 1, TILE, dtype=jnp.int32)
    return order, dest, jnp.searchsorted(dest, tiles, method="compare_all").astype(jnp.int32), weights


@jax.custom_vjp
def _take_rows(src, rows, valid, by_dest):
    return src[rows]


def _take_fwd(src, rows, valid, by_dest):
    return src[rows], (rows, valid, by_dest, src.shape[0])


def _take_bwd(saved, d):
    rows, valid, by_dest, rows_out = saved
    # ``d`` past the last group is uninitialised: the kernel never visits a row that is not ``valid``.
    return _add_rows(d, rows, valid, by_dest, None, rows_out, d.dtype), None, None, None


_take_rows.defvjp(_take_fwd, _take_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _add_rows(upd, rows, valid, by_dest, weights, rows_out, out_dtype):
    return _add_call(upd, by_dest, weights, rows_out, out_dtype)


def _add_fwd(upd, rows, valid, by_dest, weights, rows_out, out_dtype):
    return _add_call(upd, by_dest, weights, rows_out, out_dtype), (upd, rows, valid, weights)


def _add_bwd(rows_out, out_dtype, saved, d):
    upd, rows, valid, weights = saved
    if weights is None:
        return jnp.where(valid[:, None], d[rows], 0).astype(upd.dtype), None, None, None, None
    # A row without a pair reads the row it points at, under its weight of zero:
    # ``taken`` is the cotangent's, real in every row. ``upd`` past the last
    # group is not (no product wrote it), and only ``d_weights`` reads it: by a select.
    taken = d[rows].astype(jnp.float32)
    d_weights = jnp.where(valid, jnp.sum(taken * upd.astype(jnp.float32), axis=-1), 0)
    return (taken * weights[:, None]).astype(upd.dtype), None, None, None, d_weights.astype(weights.dtype)


_add_rows.defvjp(_add_fwd, _add_bwd)


def take_rows(src: jax.Array, rows: jax.Array, valid: jax.Array, by_dest=None) -> jax.Array:
    """[R, L] in ``src``'s dtype: ``src[rows[i]]`` where ``valid[i]``. XLA's
    gather whatever the shape: it moves these rows at the memory's pace
    (PERF.md section 6, PR 41). With ``by_dest`` (``by_destination`` of the
    same rows, at a shape that ``rows_move_in_tiles``) its transpose is ``add_rows``'s
    kernel, accumulated in float32 and cast once to ``src``'s dtype, which
    never visits a row that is not ``valid``; such a row of the result is
    then left as the copy of ``src[rows[i]]`` it was fetched as (a pass over
    the buffer saved: its weight is zero, so nothing of it reaches the sum or
    a gradient). Else the rows that are not ``valid`` are zeros and the
    transpose is XLA's scatter-add in ``src``'s dtype."""
    if by_dest is None:
        tracing.count("moe_row_xla_calls")
        # Its transpose is this select's: a cotangent's uninitialised rows are never multiplied.
        return jnp.where(valid[:, None], src[rows], 0)
    tracing.count("moe_row_kernel_calls")
    return _take_rows(src, rows, valid, by_dest)


def add_rows(upd: jax.Array, rows: jax.Array, valid: jax.Array, rows_out: int, weights: jax.Array, by_dest=None):
    """[rows_out, L] float32: row ``i`` of ``upd`` times ``weights[i]``
    added to row ``rows[i]``, for the ``i`` that are ``valid`` (the others'
    weights are zero). With ``by_dest`` (of these rows and weights, for a shape
    that ``rows_move_in_tiles``), the scatter-add read as a gather, a Pallas kernel
    over tiles of 128 destinations: each walks its stretch of the buffer's
    rows sorted by destination, fetches them from HBM by one copy a row, a
    chunk of 128 started together and waited together, adds a chunk to the
    tile's float32 block in VMEM by one product that places each row at its
    destination under its weight, and writes the block once: no
    read-modify-write of HBM, no collision, and the rows without a
    destination are never visited. Its transpose is XLA's gather. Else XLA's
    scatter-add. Rows of ``upd`` that are not ``valid`` may be uninitialised
    (no product wrote them): they are selected away, not multiplied by zero."""
    if by_dest is None:
        tracing.count("moe_row_xla_calls")
        # NaN times a weight of zero is NaN: the select comes first.
        upd = jnp.where(valid[:, None], upd, 0) * weights[:, None]
        return jnp.zeros((rows_out, upd.shape[1]), jnp.float32).at[rows].add(upd)
    tracing.count("moe_row_kernel_calls")
    return _add_rows(upd, rows, valid, by_dest, weights, rows_out, jnp.float32)


def routed_experts(
    z: jax.Array,
    top: jax.Array,
    weights: jax.Array,
    kernels: tuple,
    expert_offset: int,
    capacity: int,
    tile: int = TILE,
):
    """This chip's part of the routed sum, and the layer's counters.

    z [N, L] (the experts' input); top [N, K] expert ids over all experts;
    weights [N, K] float32; ``kernels`` (up [H, L, F], down [H, F, L]) or,
    gated, (gate [H, L, F], up [H, L, F], down [H, F, L]), the H experts
    ``expert_offset .. expert_offset + H`` (already masked); ``capacity``
    rows a round, whole ``tile``s.
    Returns ([N, L] float32, {counter: int32 scalar})."""
    held = kernels[0].shape[0]
    if capacity % tile:
        raise ValueError(f"a buffer of {capacity} rows is not whole tiles of {tile}")
    with jax.named_scope("moe/dispatch"):
        local = top.reshape(-1) - expert_offset
        key = jnp.where((local >= 0) & (local < held), local, held)
        sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=key.dtype), axis=0, dtype=key.dtype)
        aligned = -(-sizes // tile) * tile  # each expert's rows start on a whole tile
        plan = {
            # The pairs held here first, by expert.
            "order": checkpoint_name(jnp.argsort(key, stable=True), "moe_order"),
            "sizes": sizes,
            "starts": jnp.cumsum(sizes) - sizes,
            "aligned_starts": jnp.cumsum(aligned) - aligned,
            "aligned_ends": jnp.cumsum(aligned),
        }
    out, (computed, run) = _every_round(
        z, weights.reshape(-1), tuple(kernels), plan, top.shape[1], capacity, tile
    )
    counters = {
        "moe_pairs": jnp.sum(sizes),
        "moe_dropped_pairs": jnp.sum(sizes) - computed,
        "moe_load_max": jnp.max(sizes),
        "moe_rows_run": run,
    }
    return out, {name: jnp.asarray(counters[name], jnp.int32) for name in COUNTERS}


@functools.partial(jax.jit, static_argnames=("k", "capacity", "tile"))
def _one_round(lo, z, flat_weights, kernels, plan, k, capacity, tile):
    """Rows ``lo .. lo + capacity`` of the aligned order: their part of the
    sum [N, L] float32, and (how many of them are pairs, how many the
    products ran), both from the plan. A traced program of
    its own: the scopes below then reach the device trace under their own
    names, where ``jax.vjp`` of plain code would write ``jvp(moe/experts)``."""
    held = kernels[0].shape[0]
    starts, ends = plan["aligned_starts"], plan["aligned_ends"]
    with jax.named_scope("moe/dispatch"):
        at = lo + jnp.arange(capacity, dtype=ends.dtype)
        expert = jnp.minimum(jnp.searchsorted(ends, at, side="right"), held - 1)
        within = at - starts[expert]
        valid = within < plan["sizes"][expert]  # else a row that aligns, or lies past every pair
        pair = plan["order"][jnp.where(valid, plan["starts"][expert] + within, 0)]
        rows, w = pair // k, jnp.where(valid, flat_weights[pair], 0)
        # Which of the round's two scatter-adds the row kernel runs: the
        # combine's (float32 rows) and the gather's transpose (rows in z's
        # dtype, whole tiles only where float32 ones are). One sort of the
        # buffer's rows by destination serves both.
        in_tiles = [rows_move_in_tiles(dtype, z.shape[1], capacity, z.shape[0], tile) for dtype in (jnp.float32, z.dtype)]
        to_tokens = by_destination(jnp.where(valid, rows, -1), z.shape[0], w) if in_tiles[0] else None
        x = take_rows(z, rows, valid, to_tokens if in_tiles[1] else None)
        # Each expert's rows of this round, whole tiles; the rows past the last
        # pair's tile are no group's, and no product reads or writes them.
        group = jnp.clip(jnp.minimum(ends, lo + capacity) - jnp.maximum(starts, lo), 0).astype(jnp.int32)
    with jax.named_scope("moe/experts"):
        *first, down = kernels
        # Elementwise: an uninitialised row (and its gradient) stays in its
        # row, read only by the next product, which stops where these did.
        h = BETWEEN[len(first)](*(grouped_product(x, kernel, group, tile, z.dtype) for kernel in first))
        y = grouped_product(h, down, group, tile, jnp.float32)
    with jax.named_scope("moe/combine"):
        part = add_rows(y, rows, valid, z.shape[0], w, to_tokens)
    return part, (jnp.sum(valid, dtype=jnp.int32), jnp.sum(group))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _every_round(z, flat_weights, kernels, plan, k, capacity, tile):
    """One round, and while pairs lie past it (an imbalance) another: a loop
    whose length is the routing's, so its gradient is written out below: the
    first round's as ``jax.vjp`` gives it, each further round's rebuilt from
    the round's input and added."""
    return _every_round_fwd(z, flat_weights, kernels, plan, k, capacity, tile)[0]


def _every_round_fwd(z, flat_weights, kernels, plan, k, capacity, tile):
    operands = (z, flat_weights, kernels)
    first = lambda *operands: _one_round(0, *operands, plan, k, capacity, tile)
    part, pull, counted = jax.vjp(first, *operands, has_aux=True)

    def further(state):
        lo, part, counted = state
        more = _one_round(lo, *operands, plan, k, capacity, tile)
        return lo + capacity, part + more[0], jax.tree.map(jnp.add, counted, more[1])

    pending = lambda state: state[0] < plan["aligned_ends"][-1]
    _, part, counted = lax.while_loop(pending, further, (jnp.int32(capacity), part, counted))
    return (part, counted), (pull, operands, plan)


def _every_round_bwd(k, capacity, tile, saved, cotangents):
    pull, operands, plan = saved
    d_part = cotangents[0]

    def further(state):
        lo, grads = state
        one = lambda *operands: _one_round(lo, *operands, plan, k, capacity, tile)[0]
        return lo + capacity, jax.tree.map(jnp.add, grads, jax.vjp(one, *operands)[1](d_part))

    pending = lambda state: state[0] < plan["aligned_ends"][-1]
    _, grads = lax.while_loop(pending, further, (jnp.int32(capacity), pull(d_part)))
    return (*grads, None)


_every_round.defvjp(_every_round_fwd, _every_round_bwd)
