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

The pairs held here are a data-dependent number; the work is not. They are
sorted by expert and laid into a buffer of ``capacity`` rows, a size the
configuration fixes (``pair_capacity``: half as many pairs again as a uniform
router sends here, and a tile for each expert), every expert's rows starting on a
multiple of ``tile``, so that a tile of rows belongs to one expert. The two
products run over the whole buffer as grouped matrix products (the Pallas
kernels of ``jax.experimental.pallas.ops.tpu.megablox``, a tile of rows
against its expert's kernel): the rows no pair fills are zeros, add nothing
to the result or to a gradient, and are computed all the same, so a step
costs what the configuration says and not what the seed's routing says. No
pair is ever dropped: pairs that outgrow the buffer run through the same
round again, once for each further ``capacity`` rows they fill (at most the
worst case, every token choosing every held expert), so an imbalance costs
time and never changes the result. ``moe_dropped_pairs`` is counted, not
assumed: the pairs routed here less the rows of them handed to the products.

Named scopes: ``moe/dispatch`` (sort, group sizes, the gather of the pairs'
rows), ``moe/experts`` (the two grouped products and the activation between
them), ``moe/combine`` (the weighted scatter-add back to the tokens).

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
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from .flash import _use_interpret

# What a layer of routed experts counts a step (summed over the layers by
# train/steps.py and over the steps by ``scan_chunk``): the pairs routed to
# experts held here, those of them no product computed, and the fullest held
# expert's pairs.
COUNTERS = ("moe_pairs", "moe_dropped_pairs", "moe_load_max")
CAPACITY_FACTOR = 1.5
# The rows a product takes at a time, and what an expert's rows are aligned
# to: the matrix unit's 128 where an expert expects as many, else a sublane.
TILE, SMALL_TILE = 128, 8
# A product's block of an expert's kernel stays under this in VMEM (twice, for
# the two buffers a block has).
KERNEL_BLOCK_BYTES = 3 * 2**20


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
    (int32) sum to R and are multiples of ``tile``. Returns [R, N]."""
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
    dx = gmm(
        dy, kernels, sizes, x.dtype, (tile, *_kernel_block(n, k, kernels.dtype.itemsize)),
        transpose_rhs=True, interpret=_use_interpret(),
    )  # fmt: skip
    # A block of the kernel's gradient is accumulated in float32.
    dk = tgmm(
        x.swapaxes(0, 1), dy, sizes, kernels.dtype, (tile, *_kernel_block(k, n, 4)),
        num_actual_groups=kernels.shape[0], interpret=_use_interpret(),
    )  # fmt: skip
    return dx, dk, None


grouped_product.defvjp(_grouped_fwd, _grouped_bwd)


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
    out, computed = _every_round(
        z, weights.reshape(-1), tuple(kernels), plan, top.shape[1], capacity, tile
    )
    counters = {
        "moe_pairs": jnp.sum(sizes),
        "moe_dropped_pairs": jnp.sum(sizes) - computed,
        "moe_load_max": jnp.max(sizes),
    }
    return out, {name: jnp.asarray(counters[name], jnp.int32) for name in COUNTERS}


@functools.partial(jax.jit, static_argnames=("k", "capacity", "tile"))
def _one_round(lo, z, flat_weights, kernels, plan, k, capacity, tile):
    """Rows ``lo .. lo + capacity`` of the aligned order: their part of the
    sum [N, L] float32, and how many of them are pairs. A traced program of
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
        x = jnp.where(valid[:, None], z[rows], 0)
        group = jnp.clip(jnp.minimum(ends, lo + capacity) - jnp.maximum(starts, lo), 0)
        # The rows past the last pair are the last expert's: zeros it multiplies.
        group = group.at[-1].add(capacity - jnp.sum(group)).astype(jnp.int32)
    with jax.named_scope("moe/experts"):
        *first, down = kernels
        h = BETWEEN[len(first)](*(grouped_product(x, kernel, group, tile, z.dtype) for kernel in first))
        y = grouped_product(h, down, group, tile, jnp.float32)
    with jax.named_scope("moe/combine"):
        part = jnp.zeros(z.shape, jnp.float32).at[rows].add(y * w[:, None])
    return part, jnp.sum(valid, dtype=jnp.int32)


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
    part, pull, rows = jax.vjp(first, *operands, has_aux=True)

    def further(state):
        lo, part, rows = state
        more = _one_round(lo, *operands, plan, k, capacity, tile)
        return lo + capacity, part + more[0], rows + more[1]

    pending = lambda state: state[0] < plan["aligned_ends"][-1]
    _, part, rows = lax.while_loop(pending, further, (jnp.int32(capacity), part, rows))
    return (part, rows), (pull, operands, plan)


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
