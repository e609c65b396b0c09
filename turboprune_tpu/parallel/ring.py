"""Ring attention — sequence/context parallelism over the mesh ``model`` axis.

The reference has no sequence dimension to scale (fixed 197-token DeiT,
SURVEY.md §5 "Long-context"), but this framework treats long-context as
first-class: attention over a sequence sharded across devices, computed
blockwise with the K/V shards rotating around the ring via
``jax.lax.ppermute`` (Ring Attention, Liu et al. 2023) while the running
softmax is accumulated online (the flash-attention max/sum recurrence). Peak
memory per device is O(S/n · S/n) score blocks instead of O(S²), and each
hop overlaps with the next block's compute on TPU — the collective rides
ICI neighbor links, exactly what ``ppermute`` lowers to on a torus.

Written shard_map-first: the kernel below is the per-device program; the
public wrapper places it on a (data, model) mesh with batch sharded on
``data`` and sequence on ``model``. With ``model`` axis size 1 it degrades
to plain blockwise attention, so the same model code runs any mesh.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import DATA_AXIS, MODEL_AXIS

_NEG_BIG = -1e30  # additive mask for padded K rows; exp(-1e30 - m) == 0


def _ring_attention_shard(q, k, v, kv_valid, *, axis_name: str):
    """Per-device ring attention step (runs inside shard_map).

    q, k, v: [batch, seq_local, heads, head_dim] — this device's sequence
    shard. kv_valid: [seq_local] bool — False for padding rows (sequence
    lengths that don't divide the ring size are padded by the caller).

    The two matmuls run in the INPUT dtype on the MXU (bf16 operands stay
    bf16) with fp32 accumulation via ``preferred_element_type``; only the
    online-softmax max/sum/exp recurrence is materialized in fp32.
    """
    n = jax.lax.psum(1, axis_name)
    b, s_q, h, hd = q.shape
    qs = q * jnp.asarray(1.0 / np.sqrt(hd), q.dtype)

    def accumulate(o, m, l, k, v, valid):
        # scores: [b, h, q, k] for this K/V block
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", qs, k, preferred_element_type=jnp.float32
        )
        s = jnp.where(valid[None, None, None, :], s, _NEG_BIG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # Re-zero masked columns explicitly: when EVERY column so far is
        # masked, s - m_new == 0 and exp would resurrect them as weight 1.
        p = jnp.exp(s - m_new[..., None]) * valid[None, None, None, :]
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd",
            p.astype(v.dtype),
            v,
            preferred_element_type=jnp.float32,
        )
        return o, m_new, l

    # Local block first, then n-1 rotate-and-accumulate hops: the ring stops
    # after the LAST foreign block lands — no dead final ppermute.
    o0 = jnp.zeros((b, h, s_q, hd), jnp.float32)
    m0 = jnp.full((b, h, s_q), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s_q), jnp.float32)
    o, m, l = accumulate(o0, m0, l0, k, v, kv_valid)

    def step(carry, _):
        k, v, valid, o, m, l = carry
        # Pull the next block one hop around the ring (ICI neighbor link).
        perm = [(i, (i + 1) % n) for i in range(n)]
        k, v, valid = (
            jax.lax.ppermute(x, axis_name, perm=perm) for x in (k, v, valid)
        )
        o, m, l = accumulate(o, m, l, k, v, valid)
        return (k, v, valid, o, m, l), None

    (_, _, _, o, _, l), _ = jax.lax.scan(
        step, (k, v, kv_valid, o, m, l), None, length=n - 1
    )
    out = o / jnp.maximum(l[..., None], 1e-30)  # padded-q rows: garbage, sliced
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [b, s, h, hd]


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_valid: jax.Array,
    mesh: Mesh,
    seq_axis: str = MODEL_AXIS,
    data_axis: str = DATA_AXIS,
) -> jax.Array:
    """Sequence-parallel self-attention on a (data, model) mesh.

    q/k/v: GLOBAL [batch, seq, heads, head_dim]; ``seq`` must divide the
    ``seq_axis`` mesh size (pad first — see models/vit.py RingSelfAttention).
    kv_valid: [seq] bool marking real (non-padding) rows. Batch stays
    sharded on ``data_axis``; sequence is sharded on ``seq_axis`` and the
    K/V blocks ring around it.
    """
    # Batch stays on the data axis when it divides it; otherwise replicate
    # the batch dim (correct, just redundant across the data axis). The
    # undivisible case is flax ``init`` running the module with a
    # batch-of-1 dummy — the real jitted step always has a full batch.
    batch_dim = data_axis if q.shape[0] % mesh.shape[data_axis] == 0 else None
    spec = P(batch_dim, seq_axis, None, None)
    fn = shard_map(
        partial(_ring_attention_shard, axis_name=seq_axis),
        mesh=mesh,
        in_specs=(spec, spec, spec, P(seq_axis)),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v, kv_valid)
