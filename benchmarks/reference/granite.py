"""Plain forward of the ``granitemoehybrid`` decoder with no experts
(``num_local_experts`` 0; granite-4.0-h-micro's ``config.json``): Mamba-2
layers beside causal grouped-query attention without positional encoding, a
SwiGLU MLP after each, over packed documents.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision.
It reads a parameter tree laid out as the program's checkpoints are and
imports nothing of the program:

    embedding [V, D]
    layers_<i>/norm1/scale, layers_<i>/norm2/scale
    layers_<i>/mixer, a Mamba-2 mixer:
        in_proj/kernel [D, 2 I + 2 N + H]   columns [z | x | B | C | dt]
        conv_taps [K, I + 2 N], conv_bias [I + 2 N]
        dt_bias, A_log, D [H];  gate_norm/scale [I];  out_proj/kernel [I, D]
    or, told apart by ``q_proj``, an attention mixer:
        q_proj/kernel [D, Hq d], k_proj/kernel, v_proj/kernel [D, Hkv d],
        o_proj/kernel [Hq d, D]       (head h owns columns h d .. (h + 1) d,
                                       query head h reads key/value head
                                       h // (Hq / Hkv))
    layers_<i>/mlp/in_proj/kernel [D, 2 F]  columns [gate | value],
    layers_<i>/mlp/out_proj/kernel [F, D]
    final_norm/scale

and a ``spec``: the published keys ``embedding_multiplier``,
``residual_multiplier``, ``attention_multiplier``, ``logits_scaling``,
``rms_norm_eps``, ``num_attention_heads``, ``num_key_value_heads``.

The equations, for hidden states ``x [T, D]`` of one packed sequence whose
token ``t`` belongs to document ``seg[t]``:

    x      = embedding_multiplier * E[ids]
    x      = x + residual_multiplier * mixer(rmsnorm(x))
    x      = x + residual_multiplier * mlp(rmsnorm(x))
    mlp(u) = (silu(g) * v) W_out,  [g, v] = u W_in
    logits = rmsnorm(x) E^T / logits_scaling              (tied head)

    Mamba-2: [z, xBC, dt] = u W_in
      xBC_t = silu(sum_k taps[k] * xBC_{t-(K-1)+k} [same document] + bias)
      dt_t  = softplus(dt_t + dt_bias),  A = -exp(A_log)
      h_t   = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t,
              h = 0 entering a document's first token
      y_t   = h_t C_t + D x_t
      out   = (rmsnorm(y * silu(z)) * w) W_out
    attention: softmax over s <= t of the same document of
      attention_multiplier * q_t . k_s, no positional encoding.

The recurrence runs one token at a time (``lax.scan`` over ``t``); attention
is a plain masked softmax, a block of queries at a time.

Departures from the published model, all of them cuts the configuration's
file lists or memory devices of this file:

- ``E`` holds the rows of the vocabulary's slice only, and the logits, the
  loss and top-1 are over the slice (a smaller vocabulary);
- the depth is whatever the tree holds (``layers_0`` ... in order);
- in training mode every block is a ``jax.checkpoint``, the token scan is
  nested (an outer scan over stretches of ``SCAN_STRETCH`` tokens, each a
  ``jax.checkpoint`` of the inner token-by-token scan) and attention's query
  blocks are checkpointed, so that a backward pass at 8,192 tokens keeps one
  state a stretch and not one a token. The arithmetic is the same.

``quantize`` is for the control only: it is applied to both operands of every
projection, of the head and of attention's two products, and stands for a
matmul path in a lower precision than the configuration states.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

SCAN_STRETCH = 128
QUERY_BLOCK = 512
HIGHEST = lax.Precision.HIGHEST


def _rounded(x: jax.Array, dtype, top: float) -> jax.Array:
    """``x`` scaled per tensor so that its largest magnitude sits at ``top``,
    rounded to ``dtype``, scaled back."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_activation(x):
    return _rounded(x, jnp.float8_e4m3fn, 240.0)


_fp8_activation.defvjp(
    lambda x: (_fp8_activation(x), None),
    lambda _, g: (_rounded(g, jnp.float8_e5m2, 57344.0),),
)


def fp8_operand(x: jax.Array, weight: bool = False) -> jax.Array:
    """What a float8 matmul sees of ``x``, as float8 training has it
    (Micikevicius et al. 2022, arXiv:2209.05433): operands rounded to e4m3
    going forward, the gradient that flows back into the activations rounded
    to e5m2; the weights' gradient comes out of its matmul unrounded."""
    if weight:
        x = x.astype(jnp.float32)
        return x + lax.stop_gradient(_rounded(x, jnp.float8_e4m3fn, 240.0) - x)
    return _fp8_activation(x)


def _mm(x, w, quantize):
    """``x [..., a] @ w [a, b]``."""
    if quantize is not None:
        x, w = quantize(x), quantize(w, weight=True)
    return jnp.einsum("...a,ab->...b", x, w.astype(jnp.float32), precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _same_document(seg, shift):
    """[B, T]: token ``t - shift`` exists and lies in ``t``'s document."""
    t = seg.shape[1]
    earlier = jnp.pad(seg, ((0, 0), (shift, 0)), constant_values=-1)[:, :t]
    return earlier == seg


def _shifted(x, shift):
    """``x[:, t - shift]`` at ``t``, zero where there is no such token."""
    return jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, : x.shape[1]]


def _recurrence(x, dt, a, b, c, start, train):
    """``y_t = h_t C_t`` of the state-space recurrence, a token at a time.
    x [B, T, H, P], dt [B, T, H], a [H], b and c [B, T, N], start [B, T]."""
    bsz, t, heads, p = x.shape

    def token(h, inp):
        x_t, dt_t, b_t, c_t, start_t = inp
        h = jnp.where(start_t[:, None, None, None], 0.0, h)
        decay = jnp.exp(dt_t * a)  # [B, H]
        h = decay[:, :, None, None] * h + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :]
        return h, jnp.einsum("bhpn,bn->bhp", h, c_t, precision=HIGHEST)

    stretch = SCAN_STRETCH if train and t % SCAN_STRETCH == 0 else t

    def tokens(h, inps):
        return lax.scan(token, h, inps)

    if train:
        tokens = jax.checkpoint(tokens)
    # Time first, in stretches: [T / S, S, B, ...].
    inps = jax.tree.map(
        lambda v: jnp.moveaxis(v, 1, 0).reshape((t // stretch, stretch) + v.shape[:1] + v.shape[2:]),
        (x, dt, b, c, start),
    )
    h0 = jnp.zeros((bsz, heads, p, b.shape[-1]), jnp.float32)
    _, y = lax.scan(tokens, h0, inps)
    return jnp.moveaxis(y.reshape((t, bsz, heads, p)), 0, 1)


def mamba_mixer(u, seg, p, spec, quantize=None, train=False):
    """u [B, T, D] float32, seg [B, T] -> [B, T, D]."""
    heads = p["dt_bias"].shape[0]
    inner = p["out_proj"]["kernel"].shape[0]
    state = (p["conv_bias"].shape[0] - inner) // 2
    taps = p["conv_taps"].astype(jnp.float32)
    zxbcdt = _mm(u, p["in_proj"]["kernel"], quantize)
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * state], axis=-1)

    width = taps.shape[0]
    conv = jnp.zeros_like(xbc)
    for k in range(width):
        shift = width - 1 - k
        keep = _same_document(seg, shift)[..., None]
        conv = conv + taps[k] * jnp.where(keep, _shifted(xbc, shift), 0.0)
    xbc = jax.nn.silu(conv + p["conv_bias"])

    x, b, c = jnp.split(xbc, [inner, inner + state], axis=-1)
    x = x.reshape(x.shape[:2] + (heads, inner // heads))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = _recurrence(x, dt, a, b, c, ~_same_document(seg, 1), train)
    y = y + p["D"][:, None] * x
    y = y.reshape(z.shape) * jax.nn.silu(z)
    y = rmsnorm(y, p["gate_norm"]["scale"], spec["rms_norm_eps"])
    return _mm(y, p["out_proj"]["kernel"], quantize)


def attention_mixer(u, seg, p, spec, quantize=None, train=False):
    """Causal attention inside each document, grouped key/value heads, no
    positional encoding, scores scaled by ``attention_multiplier``."""
    bsz, t, _ = u.shape
    hq, hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    group = hq // hkv
    q = _mm(u, p["q_proj"]["kernel"], quantize)
    k = _mm(u, p["k_proj"]["kernel"], quantize)
    v = _mm(u, p["v_proj"]["kernel"], quantize)
    d = q.shape[-1] // hq
    q = q.reshape(bsz, t, hkv, group, d)
    k = k.reshape(bsz, t, hkv, d)
    v = v.reshape(bsz, t, hkv, d)
    if quantize is not None:
        k = quantize(k)
    pos = jnp.arange(t)
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def queries(args):
        q_blk, seg_q, pos_q = args  # [B, Q, Hkv, G, d], [B, Q], [Q]
        if quantize is not None:
            q_blk = quantize(q_blk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k, precision=HIGHEST)
        s = s * spec["attention_multiplier"]
        keep = (seg_q[:, :, None] == seg[:, None, :]) & (pos[None, None, :] <= pos_q[None, :, None])
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
            pos.reshape(n, block),
        ),
    )
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, t, hq * d)
    return _mm(out, p["o_proj"]["kernel"], quantize)


def mlp(u, p, quantize=None):
    gate, value = jnp.split(_mm(u, p["in_proj"]["kernel"], quantize), 2, axis=-1)
    return _mm(jax.nn.silu(gate) * value, p["out_proj"]["kernel"], quantize)


def block(x, seg, p, spec, quantize=None, train=False):
    """One decoder layer: the mixer its tree holds, then the MLP."""
    eps, res = spec["rms_norm_eps"], spec["residual_multiplier"]
    mixer = attention_mixer if "q_proj" in p["mixer"] else mamba_mixer
    u = rmsnorm(x, p["norm1"]["scale"], eps)
    x = x + res * mixer(u, seg, p["mixer"], spec, quantize, train)
    u = rmsnorm(x, p["norm2"]["scale"], eps)
    return x + res * mlp(u, p["mlp"], quantize)


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
    every layer runs on ``w * m``; the product is formed inside the layer's
    ``jax.checkpoint``, so a backward pass keeps the weights and the masks and
    not a second copy of the weights."""
    table = params["embedding"].astype(jnp.float32)
    x = spec["embedding_multiplier"] * table[ids]
    layer = 0
    while (name := f"layers_{layer}") in params:
        run = lambda x, p, m: block(x, seg, masked(p, m), spec, quantize, train)
        x = (jax.checkpoint(run) if train else run)(
            x, params[name], None if masks is None else masks[name]
        )
        layer += 1
    x = rmsnorm(x, params["final_norm"]["scale"], spec["rms_norm_eps"])
    return _mm(x, table.T, quantize) / spec["logits_scaling"]


def token_losses(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Softmax cross-entropy of every position against its target, float32;
    0 where the target is the padding label (negative)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    rows = -jnp.take_along_axis(logp, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.where(targets >= 0, rows, 0.0)


def mean_loss(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean over the valid targets."""
    return jnp.sum(token_losses(logits, targets)) / jnp.sum(targets >= 0)


def next_token_targets(ids: jax.Array, seg: jax.Array) -> jax.Array:
    """The next token of the same document; the padding label -1 at a
    document's last token and at the sequence's end."""
    nxt = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)))
    same = jnp.pad(seg[:, 1:] == seg[:, :-1], ((0, 0), (0, 1)))
    return jnp.where(same, nxt, -1)


def masked(params: dict, masks: Optional[dict]) -> dict:
    """``w * m`` wherever the mask tree holds an array, the weight elsewhere."""
    if masks is None:
        return params

    def go(p, m):
        if isinstance(p, dict):
            return {k: go(v, None if m is None else m.get(k)) for k, v in p.items()}
        return p if m is None else p * jnp.asarray(m, p.dtype)

    return go(params, masks)
