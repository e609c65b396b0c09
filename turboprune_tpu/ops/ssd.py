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
``exp(-inf)``, never a large positive exponent multiplied by zero, so the
backward pass that ``jax.grad`` derives stays finite. The model puts each
block under ``jax.checkpoint``, so the [Q, Q] decay matrices live only while
one block's backward runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NO_DOCUMENT = -2  # what precedes a sequence's first token
PADDING = -1  # tokens added to fill the last chunk


def _decay(log_decay: jax.Array, keep: jax.Array) -> jax.Array:
    return jnp.exp(jnp.where(keep, log_decay, -jnp.inf))


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
        per_group = jax.vmap(
            lambda x, dt, a, b, c: ssd_chunked(x, dt, a, b, c, segment_ids, chunk),
            in_axes=(2, 2, 0, 2, 2),
            out_axes=2,
        )
        y = per_group(
            x.reshape(bsz, t, groups, heads // groups, p),
            dt.reshape(bsz, t, groups, heads // groups),
            a.reshape(groups, heads // groups),
            b,
            c,
        )
        return y.reshape(x.shape)
    dtype = x.dtype
    pad = (-t) % chunk
    if pad:
        fill = lambda v, value=0: jnp.pad(
            v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2), constant_values=value
        )
        x, dt, b, c = fill(x), fill(dt), fill(b), fill(c)
        segment_ids = fill(segment_ids, PADDING)
    nc = (t + pad) // chunk
    f32 = jnp.float32

    # Chunked, heads before tokens: the products batch over (B, chunk, H).
    xdt = (x.astype(f32) * dt[..., None]).astype(dtype)
    xdt = xdt.reshape(bsz, nc, chunk, heads, p).transpose(0, 1, 3, 2, 4)  # [B, C, H, Q, P]
    bc = b.reshape(bsz, nc, chunk, -1)
    cc = c.reshape(bsz, nc, chunk, -1)
    seg = segment_ids.reshape(bsz, nc, chunk)
    log_a = (dt.astype(f32) * a.astype(f32)).reshape(bsz, nc, chunk, heads)
    cum = jnp.cumsum(log_a, axis=2).transpose(0, 1, 3, 2)  # [B, C, H, Q], inclusive
    total = cum[..., -1]  # [B, C, H]

    last_seg = seg[:, :, -1]  # [B, C]
    before = jnp.concatenate(
        [jnp.full((bsz, 1), NO_DOCUMENT, seg.dtype), last_seg[:, :-1]], axis=1
    )

    # Within a chunk.
    same = seg[:, :, :, None] == seg[:, :, None, :]  # [B, C, i, j]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    scores = jnp.einsum("bcin,bcjn->bcij", cc, bc, preferred_element_type=f32)
    decay = _decay(cum[..., :, None] - cum[..., None, :], (same & causal)[:, :, None])
    weights = (scores[:, :, None] * decay).astype(dtype)  # [B, C, H, i, j]
    y = jnp.einsum("bchij,bchjp->bchip", weights, xdt, preferred_element_type=f32)

    # Each chunk's own contribution to the state at its end.
    to_end = _decay(total[..., None] - cum, (seg == last_seg[..., None])[:, :, None])
    states = jnp.einsum(
        "bchjp,bcjn->bchpn",
        (xdt.astype(f32) * to_end[..., None]).astype(dtype),
        bc,
        preferred_element_type=f32,
    )
    carried = _decay(total, (last_seg == before)[..., None])  # [B, C, H]

    def chunk_step(h, inp):
        keep, own = inp
        return keep[..., None, None] * h + own, h

    h0 = jnp.zeros((bsz, heads, p, bc.shape[-1]), f32)
    _, entering = jax.lax.scan(
        chunk_step, h0, (jnp.moveaxis(carried, 1, 0), jnp.moveaxis(states, 1, 0))
    )
    entering = jnp.moveaxis(entering, 0, 1)  # [B, C, H, P, N]: the state entering each chunk

    # What the carried state adds inside the chunk.
    from_start = _decay(cum, (seg == before[..., None])[:, :, None])  # [B, C, H, Q]
    y = y + from_start[..., None] * jnp.einsum(
        "bcin,bchpn->bchip", cc, entering.astype(dtype), preferred_element_type=f32
    )
    y = y.transpose(0, 1, 3, 2, 4).reshape(bsz, nc * chunk, heads, p)
    return y[:, :t].astype(dtype)
