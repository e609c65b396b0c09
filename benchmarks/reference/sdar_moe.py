"""Plain forward and loss of the ``sdar_moe`` decoder (SDAR-30B-A3B-Chat's
``config.json``; the ``qwen3_moe`` block) under block-diffusion training
(Arriola et al., arXiv:2503.09573, section 3 and its training algorithm).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision. It
reads a parameter tree laid out as the program's checkpoints are and imports
nothing of the program; the norm, the product, the masks and the float8
operand it takes from ``reference/granite.py``:

    embedding [V, D];  lm_head/kernel [D, V];  final_norm/scale
    layers_<i>/input_norm/scale, layers_<i>/post_attention_norm/scale
    layers_<i>/attn:  q_proj/kernel [D, Hq d], k_proj/kernel, v_proj/kernel
                      [D, Hkv d], o_proj/kernel [Hq d, D], q_norm/scale,
                      k_norm/scale [d]
    layers_<i>/mlp:   router/weight [D, E]          E: ALL the layer's experts
                      experts/kernel_gate, kernel_up [Eh, D, F], kernel_down
                      [Eh, F, D]     the Eh experts ``expert_offset ..`` held here

and a ``spec``: ``rms_norm_eps``, ``num_attention_heads`` and
``num_key_value_heads`` AS HELD, ``head_dim``, ``rope_theta``,
``num_experts_per_tok``, ``expert_offset``, ``mask_row_scale``.

The input is ``tokens`` [B, 5, T] int32: the clean ids ``x_0``, the document
of each token, the ordinal ``blk`` of its block inside the document, its
index ``pos`` inside the document, the noised ids ``x_t``. The equations, a
sequence as ``2T`` rows, rows ``< T`` the clean copy and rows ``>= T`` the
noised copy, both with the same ``doc``, ``blk`` and ``pos``:

    x = E[x_0 ; x_t], the mask's row (the last of E) times ``mask_row_scale``
    h = x + Attn(rmsnorm(x));   y = h + MoE(rmsnorm(h))
    logits = rmsnorm(y[T:]) W_head                        the noised rows alone
    Attn: q = u W_q, k = u W_k, v = u W_v;  a head at a time q <- rmsnorm(q) g_q,
          k <- rmsnorm(k) g_k;  rotary at ``pos`` over the whole head
          dimension, theta ``rope_theta``, frequency j = theta^(-2 j / d), the
          halves (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin);  scores
          q k^T / sqrt(d);  query head i reads key/value head i // (Hq / Hkv);
          softmax over the keys keep(q, k) keeps;  W_o
    keep(q, k) = doc(q) == doc(k) and
          clean q, clean k:   blk(k) <= blk(q)
          noised q, clean k:  blk(k) <  blk(q)
          noised q, noised k: blk(k) == blk(q)
          clean q, noised k:  never
    MoE:  p = softmax(u W_r) in R^E;  top = the k largest;  w_i = p_i / sum_top p
          out = sum_{i in top, i held} w_i (silu(u W_gate_i) * u W_up_i) W_down_i
    loss = (1 / tokens) sum_i weight_i * -log softmax(logits_i)[target_i]
          over the targets that are not the padding label; the batch gives
          ``weight_i = 1 / t`` of the token's block where it was masked

The mask is a dense boolean ``[rows, 2T]`` built from the rule a block of
query rows at a time; attention a dense softmax; the experts a plain loop
over the experts held, every row through every one of them, with weight zero
where the router did not choose it.

Departures from the source, all of them cuts the configuration's file lists,
or what it lists under ``assumed``:

- the tree holds one chip's share of a deployment: some query heads with the
  key/value heads they read, the experts from ``expert_offset`` on, a slice of
  the vocabulary whose last id is the mask's. The router scores all experts
  and normalises over all it chose; what absent heads and experts would add is
  left out, and the partial sum goes on;
- the depth is whatever the tree holds (``layers_0`` ... in order);
- the head-wise query/key norm is ``qwen3_moe``'s, whose keys ``sdar_moe`` has;
- the mask's embedding row is read through a fixed multiplier (``assumed``:
  the source's config has no such key; at 1 it is the plain lookup);
- the block length, the noise levels and the weights are the batch's: this
  file draws nothing;
- in training mode every block is a ``jax.checkpoint``, as is each expert of
  the loop and each block of query rows.

``quantize`` is for the control only: both operands of every projection, of
attention's two products, of the experts' products and of the head; never
the router, whose stated precision is float32.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.granite import _mm, fp8_operand, masked, rmsnorm  # noqa: F401

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512
CLEAN, DOC, BLK, POS, NOISED = range(5)  # rows of ``tokens``


def keep_rows(doc, blk, rows):
    """keep [B, len(rows), 2T] of the rule for the query rows ``rows`` (of the
    2T) against every key row; ``doc``, ``blk`` [B, T]."""
    t = doc.shape[1]
    doc2, blk2 = jnp.concatenate([doc, doc], axis=1), jnp.concatenate([blk, blk], axis=1)
    noised = jnp.arange(2 * t) >= t
    qd, qb, qn = doc2[:, rows, None], blk2[:, rows, None], noised[rows][None, :, None]
    kd, kb, kn = doc2[:, None, :], blk2[:, None, :], noised[None, None, :]
    by_block = jnp.where(qn, jnp.where(kn, kb == qb, kb < qb), ~kn & (kb <= qb))
    return (qd == kd) & by_block


def rotary(x, pos, theta):
    """``x`` [B, R, H, d] at positions ``pos`` [B, R]."""
    half = x.shape[-1] // 2
    freq = jnp.power(jnp.float32(theta), -2.0 * jnp.arange(half, dtype=jnp.float32) / x.shape[-1])
    angle = pos.astype(jnp.float32)[:, :, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * jnp.cos(angle) - x2 * jnp.sin(angle), x2 * jnp.cos(angle) + x1 * jnp.sin(angle)], axis=-1
    )


def attention(u, doc, blk, pos, p, spec, quantize=None, train=False):
    """u [B, 2T, D] float32 -> [B, 2T, D]."""
    bsz, rows, _ = u.shape
    hq, hkv, d = spec["num_attention_heads"], spec["num_key_value_heads"], spec["head_dim"]
    group, eps = hq // hkv, spec["rms_norm_eps"]
    both = jnp.concatenate([pos, pos], axis=1)
    q = _mm(u, p["q_proj"]["kernel"], quantize).reshape(bsz, rows, hq, d)
    k = _mm(u, p["k_proj"]["kernel"], quantize).reshape(bsz, rows, hkv, d)
    v = _mm(u, p["v_proj"]["kernel"], quantize).reshape(bsz, rows, hkv, d)
    q = rotary(rmsnorm(q, p["q_norm"]["scale"], eps), both, spec["rope_theta"])
    k = rotary(rmsnorm(k, p["k_norm"]["scale"], eps), both, spec["rope_theta"])
    q = q.reshape(bsz, rows, hkv, group, d)
    if quantize is not None:
        k = quantize(k)
    block = QUERY_BLOCK if rows % QUERY_BLOCK == 0 else rows

    def queries(args):
        q_blk, at = args  # [B, Q, Hkv, G, d], [Q]
        if quantize is not None:
            q_blk = quantize(q_blk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k, precision=HIGHEST) / math.sqrt(d)
        s = jnp.where(keep_rows(doc, blk, at)[:, None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        vv = v
        if quantize is not None:
            w, vv = quantize(w), quantize(v)
        return jnp.einsum("bkgqs,bskd->bqkgd", w, vv, precision=HIGHEST)

    if train:
        queries = jax.checkpoint(queries)
    n = rows // block
    out = lax.map(
        queries,
        (jnp.moveaxis(q.reshape(bsz, n, block, hkv, group, d), 1, 0), jnp.arange(rows).reshape(n, block)),
    )
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, rows, hq * d)
    return _mm(out, p["o_proj"]["kernel"], quantize)


def route(h, router, spec):
    """(top [N, K] expert ids, weights [N, K]) of normed inputs ``h`` [N, D]."""
    logits = jnp.einsum("nd,de->ne", h, router["weight"].astype(jnp.float32), precision=HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    _, top = lax.top_k(p, spec["num_experts_per_tok"])
    chosen = jnp.take_along_axis(p, top, axis=-1)
    return top, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def routing(h, layer, spec):
    """The experts the router chooses for the residual stream ``h`` [..., D]
    entering a layer's MoE (whatever its dtype): [N, K], sorted."""
    h = h.astype(jnp.float32).reshape(-1, h.shape[-1])
    u = rmsnorm(h, layer["post_attention_norm"]["scale"], spec["rms_norm_eps"])
    return jnp.sort(route(u, layer["mlp"]["router"], spec)[0], axis=-1)


def sparse_moe(u, p, spec, quantize=None, train=False):
    """u [B, R, D] float32 -> [B, R, D]."""
    h = u.reshape(-1, u.shape[-1])
    top, weights = route(h, p["router"], spec)
    experts, held = p["router"]["weight"].shape[1], p["experts"]["kernel_up"].shape[0]
    # [N, E]: a row's weight for every expert, zero where not chosen.
    dense = jnp.zeros((h.shape[0], experts), jnp.float32)
    dense = dense.at[jnp.arange(h.shape[0])[:, None], top].set(weights)
    here = lax.dynamic_slice_in_dim(dense, spec["expert_offset"], held, axis=1)

    def one(total, expert):
        gate, up, down, w = expert
        inner = jax.nn.silu(_mm(h, gate, quantize)) * _mm(h, up, quantize)
        return total + w[:, None] * _mm(inner, down, quantize), None

    one = jax.checkpoint(one) if train else one
    e = p["experts"]
    out, _ = lax.scan(one, jnp.zeros_like(h), (e["kernel_gate"], e["kernel_up"], e["kernel_down"], here.T))
    return out.reshape(u.shape)


def block(x, doc, blk, pos, p, spec, quantize=None, train=False):
    eps = spec["rms_norm_eps"]
    h = x + attention(rmsnorm(x, p["input_norm"]["scale"], eps), doc, blk, pos, p["attn"], spec, quantize, train)
    return h + sparse_moe(rmsnorm(h, p["post_attention_norm"]["scale"], eps), p["mlp"], spec, quantize, train)


def forward(
    params: dict,
    spec: dict,
    tokens: jax.Array,
    quantize: Optional[Callable] = None,
    train: bool = False,
    masks: Optional[dict] = None,
    upto: Optional[int] = None,
) -> jax.Array:
    """Logits [B, T, V] in float32 of the noised rows of ``tokens`` [B, 5, T].
    With ``masks`` (a tree like ``params``, None where nothing is pruned)
    every layer and the head run on ``w * m``, the product formed inside the
    layer's ``jax.checkpoint``. ``upto`` stops before that layer and returns
    the residual stream [B, 2T, D] entering it."""
    doc, blk, pos = tokens[:, DOC], tokens[:, BLK], tokens[:, POS]
    ids = jnp.concatenate([tokens[:, CLEAN], tokens[:, NOISED]], axis=1)
    x = params["embedding"].astype(jnp.float32)[ids]
    mask_id = params["embedding"].shape[0] - 1
    x = jnp.where((ids == mask_id)[..., None], spec["mask_row_scale"] * x, x)
    layer = 0
    while (name := f"layers_{layer}") in params and layer != upto:
        run = lambda x, p, m: block(x, doc, blk, pos, masked(p, m), spec, quantize, train)
        x = (jax.checkpoint(run) if train else run)(
            x, params[name], None if masks is None else masks[name]
        )
        layer += 1
    if upto is not None:
        return x
    x = rmsnorm(x[:, doc.shape[1] :], params["final_norm"]["scale"], spec["rms_norm_eps"])
    head = masked(params["lm_head"], None if masks is None else masks["lm_head"])
    return _mm(x, head["kernel"], quantize)


def weighted_losses(logits: jax.Array, targets: jax.Array, weights: jax.Array) -> jax.Array:
    """``weight * CE`` of every position, float32; 0 where the target is the
    padding label (negative)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    rows = -jnp.take_along_axis(logp, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.where(targets >= 0, weights * rows, 0.0)


def loss(logits: jax.Array, targets: jax.Array, weights: jax.Array) -> jax.Array:
    """The weighted sum over the targets, divided by the tokens (a place with
    no token has weight -1)."""
    return jnp.sum(weighted_losses(logits, targets, weights)) / jnp.sum(weights >= 0)
