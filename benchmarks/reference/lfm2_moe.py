"""Plain forward of the ``lfm2_moe`` decoder (LFM2-8B-A1B's ``config.json``)
over packed documents: a doubly gated short convolution or rotary
grouped-query attention, then a dense SwiGLU MLP (the first layers) or
sigmoid-routed SwiGLU experts, pre-norm, a tied head.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision. It
reads a parameter tree laid out as the program's checkpoints are and imports
nothing of the program; the norm, the product, the document rule, the masks,
the loss and the float8 operand it takes from ``reference/granite.py``, the
rotation from ``reference/sdar_moe.py``:

    embedding [V, D] (the head too);  final_norm/scale
    layers_<i>/operator_norm/scale, layers_<i>/ffn_norm/scale
    layers_<i>/mixer, a short convolution (told apart by ``conv_taps``):
        in_proj/kernel [D, 3 C]   columns [B | C | u], C channels each
        conv_taps [K, C];  out_proj/kernel [C, D]
    or attention (``q_proj``):
        q_proj/kernel [D, Hq d], k_proj/kernel, v_proj/kernel [D, Hkv d],
        o_proj/kernel [Hq d, D], q_norm/scale, k_norm/scale [d]
    layers_<i>/mlp, dense (``in_proj``):
        in_proj/kernel [D, 2 F]  columns [gate | value];  out_proj/kernel [F, D]
    or routed (``router``):
        router/weight [D, E], router/bias [E]       E: ALL the layer's experts
        experts/kernel_gate, kernel_up [Eh, D, F], kernel_down [Eh, F, D]
                                   the Eh experts ``expert_offset ..`` held here

and a ``spec``: ``norm_eps``, ``num_attention_heads`` and
``num_key_value_heads`` AS HELD, ``head_dim``, ``rope_theta``,
``num_experts_per_tok``, ``routed_scaling_factor``, ``expert_offset``.

The equations, for hidden states ``x [T, D]`` of one packed sequence whose
token ``t`` belongs to document ``seg[t]`` and is its ``pos[t]``-th:

    x = E[ids];   h = x + Op(rmsnorm(x));   y = h + FF(rmsnorm(h))
    logits = rmsnorm(y_last) E^T
    conv:  [B | C | u] = n W_in;  v = B * u
           g_t = sum_{k=0..K-1} taps[k] * v_{t-(K-1)+k} [same document]
           out = (C * g) W_out                  no bias, no activation
    attn:  q = n W_q, k = n W_k, v = n W_v;  a head at a time q <- rmsnorm(q) g_q,
           k <- rmsnorm(k) g_k;  rotary at ``pos`` over the whole head
           dimension, frequency j = theta^(-2 j / d), the halves (x1, x2) ->
           (x1 cos - x2 sin, x2 cos + x1 sin);  scores q k^T / sqrt(d);  query
           head i reads key/value head i // (Hq / Hkv);  softmax over s <= t
           of the same document;  W_o
    dense: (silu(g) * v) W_out,  [g | v] = n W_in
    routed: s = sigmoid(n W_r) in R^E;  top = the k largest of s + bias;
           w_i = scaling * s_i / (sum_{j in top} s_j + 1e-6)
           out = sum_{i in top, i held} w_i (silu(n W_gate_i) * n W_up_i) W_down_i

Attention is a dense masked softmax, a block of query rows at a time; the
experts a plain loop over the experts held, every token through every one of
them, with weight zero where the router did not choose it.

Departures from the published model, all of them cuts the configuration's
file lists, or what it lists under ``assumed``:

- the tree holds one chip's share of a deployment: some query heads with the
  key/value heads they read, some of the convolution's channels (of B, C and
  u alike) and of the dense MLP's columns, the experts from ``expert_offset``
  on, a slice of the vocabulary. The router scores all experts and normalises
  over all it chose; what absent heads, channels, columns and experts would
  add is left out, and the partial sum goes on;
- the depth is whatever the tree holds (``layers_0`` ... in order);
- the head is the embedding (``assumed``: the family ties them);
- the convolution restarts with every packed document (``assumed``: the
  source defines it over one document);
- the selection bias is whatever the tree holds and gets no gradient;
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

from benchmarks.reference.granite import (  # noqa: F401  (re-exported for the followers)
    _mm,
    _same_document,
    _shifted,
    fp8_operand,
    masked,
    mean_loss,
    mlp,
    next_token_targets,
    rmsnorm,
    token_losses,
)
from benchmarks.reference.sdar_moe import rotary  # noqa: F401  (the same rotation, at positions given)

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512
ROUTER_EPS = 1e-6


def positions(seg):
    """[B, T]: each token's index inside its document, counted a token at a
    time."""

    def token(carry, now):
        before, count = carry
        count = jnp.where(now == before, count + 1, 0)
        return (now, count), count

    first = (jnp.full(seg.shape[:1], -1, seg.dtype), jnp.zeros(seg.shape[:1], jnp.int32))
    return lax.scan(token, first, seg.T)[1].T


def short_conv(u, seg, p, quantize=None):
    """u [B, T, D] float32, seg [B, T] -> [B, T, D]."""
    b, c, x = jnp.split(_mm(u, p["in_proj"]["kernel"], quantize), 3, axis=-1)
    taps = p["conv_taps"].astype(jnp.float32)
    v = b * x
    width = taps.shape[0]
    g = jnp.zeros_like(v)
    for k in range(width):
        shift = width - 1 - k
        keep = _same_document(seg, shift)[..., None]
        g = g + taps[k] * jnp.where(keep, _shifted(v, shift), 0.0)
    return _mm(c * g, p["out_proj"]["kernel"], quantize)


def attention(u, seg, p, spec, quantize=None, train=False):
    """u [B, T, D] float32 -> [B, T, D]: causal inside each document."""
    bsz, t, _ = u.shape
    hq, hkv, d = spec["num_attention_heads"], spec["num_key_value_heads"], spec["head_dim"]
    group, eps = hq // hkv, spec["norm_eps"]
    pos = positions(seg)
    q = _mm(u, p["q_proj"]["kernel"], quantize).reshape(bsz, t, hq, d)
    k = _mm(u, p["k_proj"]["kernel"], quantize).reshape(bsz, t, hkv, d)
    v = _mm(u, p["v_proj"]["kernel"], quantize).reshape(bsz, t, hkv, d)
    q = rotary(rmsnorm(q, p["q_norm"]["scale"], eps), pos, spec["rope_theta"])
    k = rotary(rmsnorm(k, p["k_norm"]["scale"], eps), pos, spec["rope_theta"])
    q = q.reshape(bsz, t, hkv, group, d)
    if quantize is not None:
        k = quantize(k)
    at = jnp.arange(t)
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def queries(args):
        q_blk, seg_q, at_q = args  # [B, Q, Hkv, G, d], [B, Q], [Q]
        if quantize is not None:
            q_blk = quantize(q_blk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k, precision=HIGHEST) / math.sqrt(d)
        keep = (seg_q[:, :, None] == seg[:, None, :]) & (at[None, None, :] <= at_q[None, :, None])
        s = jnp.where(keep[:, None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        vv = v
        if quantize is not None:
            w, vv = quantize(w), quantize(v)
        return jnp.einsum("bkgqs,bskd->bqkgd", w, vv, precision=HIGHEST)

    if train:
        queries = jax.checkpoint(queries)
    n = t // block
    out = lax.map(
        queries,
        (
            jnp.moveaxis(q.reshape(bsz, n, block, hkv, group, d), 1, 0),
            jnp.moveaxis(seg.reshape(bsz, n, block), 1, 0),
            at.reshape(n, block),
        ),
    )
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, t, hq * d)
    return _mm(out, p["o_proj"]["kernel"], quantize)


def route(h, router, spec):
    """(top [N, K] expert ids, weights [N, K]) of normed inputs ``h`` [N, D]."""
    s = jax.nn.sigmoid(
        jnp.einsum("nd,de->ne", h, router["weight"].astype(jnp.float32), precision=HIGHEST)
    )
    _, top = lax.top_k(s + lax.stop_gradient(router["bias"]), spec["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, top, axis=-1)
    scale = spec["routed_scaling_factor"]
    return top, scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + ROUTER_EPS)


def routing(h, layer, spec):
    """The experts the router chooses for the residual stream ``h`` [..., D]
    entering a layer's MoE (whatever its dtype): [N, K], sorted."""
    h = h.astype(jnp.float32).reshape(-1, h.shape[-1])
    u = rmsnorm(h, layer["ffn_norm"]["scale"], spec["norm_eps"])
    return jnp.sort(route(u, layer["mlp"]["router"], spec)[0], axis=-1)


def sparse_moe(u, p, spec, quantize=None, train=False):
    """u [B, T, D] float32 -> [B, T, D]."""
    h = u.reshape(-1, u.shape[-1])
    top, weights = route(h, p["router"], spec)
    experts, held = p["router"]["weight"].shape[1], p["experts"]["kernel_up"].shape[0]
    # [N, E]: a token's weight for every expert, zero where not chosen.
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


def block(x, seg, p, spec, quantize=None, train=False):
    """One decoder layer: the mixer its tree holds, then the dense MLP or the
    routed experts its tree holds."""
    eps = spec["norm_eps"]
    u = rmsnorm(x, p["operator_norm"]["scale"], eps)
    if "conv_taps" in p["mixer"]:
        h = x + short_conv(u, seg, p["mixer"], quantize)
    else:
        h = x + attention(u, seg, p["mixer"], spec, quantize, train)
    u = rmsnorm(h, p["ffn_norm"]["scale"], eps)
    if "router" in p["mlp"]:
        return h + sparse_moe(u, p["mlp"], spec, quantize, train)
    return h + mlp(u, p["mlp"], quantize)


def forward(
    params: dict,
    spec: dict,
    ids: jax.Array,
    seg: jax.Array,
    quantize: Optional[Callable] = None,
    train: bool = False,
    masks: Optional[dict] = None,
) -> jax.Array:
    """Logits [B, T, V] in float32 for token ids and document ids [B, T].
    With ``masks`` (a tree like ``params``, None where nothing is pruned)
    every layer runs on ``w * m``, the product formed inside the layer's
    ``jax.checkpoint``."""
    table = params["embedding"].astype(jnp.float32)
    x = table[ids]
    layer = 0
    while (name := f"layers_{layer}") in params:
        run = lambda x, p, m: block(x, seg, masked(p, m), spec, quantize, train)
        x = (jax.checkpoint(run) if train else run)(
            x, params[name], None if masks is None else masks[name]
        )
        layer += 1
    x = rmsnorm(x, params["final_norm"]["scale"], spec["norm_eps"])
    return _mm(x, table.T, quantize)
