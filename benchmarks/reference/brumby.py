"""Plain forward of the ``brumby`` decoder (Brumby-14B-Base's ``config.json``)
over packed documents: the Qwen3 block with power retention (Gelada, Buckman,
Zhang & Bach, arXiv:2507.04239) in attention's place, then a SwiGLU MLP,
pre-norm, an untied head.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision. It
reads a parameter tree laid out as the program's checkpoints are and imports
nothing of the program; the norm, the product, the MLP, the masks, the loss
and the float8 operand it takes from ``reference/granite.py``, the rotation
from ``reference/sdar_moe.py``:

    embedding [V, D];  final_norm/scale;  lm_head/kernel [D, V]
    layers_<i>/input_norm/scale, layers_<i>/post_attention_norm/scale
    layers_<i>/retention:
        q_proj/kernel [D, Hq d], k_proj/kernel, v_proj/kernel [D, Hkv d],
        o_proj/kernel [Hq d, D], q_norm/scale, k_norm/scale [d]
        gate_weight [D, Hkv], gate_bias [Hkv]
    layers_<i>/mlp:  in_proj/kernel [D, 2 F] columns [gate | value];  out_proj/kernel [F, D]

and a ``spec``: ``rms_norm_eps``, ``num_attention_heads`` and
``num_key_value_heads`` AS HELD, ``head_dim``, ``rope_theta``,
``retention_eps`` (and, for one reading only, ``carry_cut``).

The equations, for hidden states ``x [T, D]`` of one packed sequence whose
token ``t`` belongs to document ``seg[t]`` and is its ``pos[t]``-th:

    x = E[ids];   h = x + Ret(rmsnorm(x));   y = h + MLP(rmsnorm(h))
    logits = rmsnorm(y_last) W_head
    Ret, for n = rmsnorm(x):
        q = n W_q, k = n W_k, v = n W_v;  a head at a time q <- rmsnorm(q) g_q,
        k <- rmsnorm(k) g_k;  rotary at ``pos`` over the whole head dimension,
        theta ``rope_theta``, the halves rotated;  query head i reads key/value
        head i // (Hq / Hkv)
        lam_t = log sigmoid(n_t . w_g + b_g)          one a key/value head
        a_ts  = exp(sum_{s < r <= t} lam_r) (q_t . k_s)^2 / d    s <= t, seg[s] = seg[t]
        o_t   = sum_s a_ts v_s / (sum_s a_ts + retention_eps)
        Ret   = concat_heads(o) W_o
    MLP: (silu(g) * v) W_out,  [g | v] = n W_in

Retention is the **quadratic** form: every pair (t, s) of a sequence, its
weight and nothing carried, a block of ``QUERY_BLOCK`` query rows at a time
so that it fits; the program runs the chunked recurrence, which is the same
sum in exact arithmetic. What is token by token (the projections, the norms,
the MLP, the head) runs ``ROW_BLOCK`` tokens at a time for the same reason:
at 32,768 tokens a float32 copy of the stream is 0.67 GB.

Departures from the published model, all of them cuts the configuration's
file lists, or what it lists under ``assumed``:

- the tree holds one chip's share of a deployment: some query heads with the
  key/value heads they read (and their gates), some of the MLP's columns, a
  slice of the vocabulary. What the absent heads and columns would add is left
  out, and the partial sum goes on;
- the depth is whatever the tree holds (``layers_0`` ... in order);
- the degree (2), the gate, the scale, ``retention_eps`` and the head norms
  are the configuration's file's ``assumed``;
- a packed document starts with no state and at position 0;
- in training mode every block of rows and of queries is a ``jax.checkpoint``.

``quantize`` is for the control only: both operands of every projection, of
retention's two products and of the head; never the gate, whose stated
precision is float32. ``spec["carry_cut"]`` is for one reading only (PERF.md
section 2): it drops every pair whose two tokens do not lie in the same
stretch of so many tokens of the sequence, which is what a chunked program
whose carried state is lost computes.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.granite import (  # noqa: F401  (re-exported for the followers)
    _mm,
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
ROW_BLOCK = 4096


def positions(seg):
    """[B, T]: each token's index inside its document."""
    at = jnp.arange(seg.shape[1])
    first = jnp.pad(seg[:, 1:] != seg[:, :-1], ((0, 0), (1, 0)), constant_values=True)
    return at - lax.cummax(jnp.where(first, at, 0), axis=1)


def _in_blocks(fn, arrays, size, train):
    """``fn`` of ``size`` tokens of every sequence at a time: ``arrays`` (a
    tree of [B, T, ...]) split along T, the results joined along it."""
    t = jax.tree.leaves(arrays)[0].shape[1]
    size = size if t % size == 0 else t
    split = lambda a: jnp.moveaxis(a.reshape(a.shape[0], t // size, size, *a.shape[2:]), 1, 0)
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape(a.shape[1], t, *a.shape[3:])
    out = lax.map(jax.checkpoint(fn) if train else fn, jax.tree.map(split, arrays))
    return jax.tree.map(join, out)


def retention(q, k, v, lam, seg, eps, quantize=None, train=False, carry_cut=0):
    """q [B, T, Hkv, G, d], k and v [B, T, Hkv, d], lam [B, T, Hkv], seg
    [B, T] -> o [B, T, Hkv, G, d], every pair of a sequence at a time."""
    t, d = q.shape[1], q.shape[-1]
    cum = jnp.cumsum(lam, axis=1)
    at = jnp.broadcast_to(jnp.arange(t), seg.shape)
    if quantize is not None:
        k = quantize(k)

    def queries(args):
        q_blk, cum_q, seg_q, at_q = args  # [B, Q, Hkv, G, d], [B, Q, Hkv], [B, Q], [B, Q]
        if quantize is not None:
            q_blk = quantize(q_blk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k, precision=HIGHEST)
        keep = (seg_q[:, :, None] == seg[:, None, :]) & (at[:, None, :] <= at_q[:, :, None])
        if carry_cut:
            keep &= at[:, None, :] // carry_cut == at_q[:, :, None] // carry_cut
        span = jnp.moveaxis(cum_q, 2, 1)[:, :, :, None] - jnp.moveaxis(cum, 2, 1)[:, :, None, :]
        decay = jnp.exp(jnp.where(keep[:, None], span, -jnp.inf))  # [B, Hkv, Q, S]
        a = s * s * (decay / d)[:, :, None]
        den = jnp.sum(a, axis=-1)  # [B, Hkv, G, Q]
        vv = v
        if quantize is not None:
            a, vv = quantize(a), quantize(v)
        num = jnp.einsum("bkgqs,bskd->bqkgd", a, vv, precision=HIGHEST)
        return num / (jnp.moveaxis(den, 3, 1)[..., None] + eps)

    return _in_blocks(queries, (q, cum, seg, at), QUERY_BLOCK, train)


def block(x, seg, p, spec, quantize=None, train=False):
    """One decoder layer, x [B, T, D] float32."""
    hq, hkv, d = spec["num_attention_heads"], spec["num_key_value_heads"], spec["head_dim"]
    eps, r = spec["rms_norm_eps"], p["retention"]

    def read(args):
        x, pos = args
        n = rmsnorm(x, p["input_norm"]["scale"], eps)
        heads = lambda name, h: _mm(n, r[name]["kernel"], quantize).reshape(*n.shape[:2], h, d)
        q = rotary(rmsnorm(heads("q_proj", hq), r["q_norm"]["scale"], eps), pos, spec["rope_theta"])
        k = rotary(rmsnorm(heads("k_proj", hkv), r["k_norm"]["scale"], eps), pos, spec["rope_theta"])
        gate = jnp.einsum("btd,dh->bth", n, r["gate_weight"].astype(jnp.float32), precision=HIGHEST)
        lam = jax.nn.log_sigmoid(gate + r["gate_bias"])
        return q.reshape(*n.shape[:2], hkv, hq // hkv, d), k, heads("v_proj", hkv), lam

    q, k, v, lam = _in_blocks(read, (x, positions(seg)), ROW_BLOCK, train)
    o = retention(
        q, k, v, lam, seg, spec["retention_eps"], quantize, train, spec.get("carry_cut", 0)
    )

    def write(args):
        x, o = args
        h = x + _mm(o.reshape(*o.shape[:2], hq * d), r["o_proj"]["kernel"], quantize)
        return h + mlp(rmsnorm(h, p["post_attention_norm"]["scale"], eps), p["mlp"], quantize)

    return _in_blocks(write, (x, o), ROW_BLOCK, train)


def hidden(
    params: dict,
    spec: dict,
    ids: jax.Array,
    seg: jax.Array,
    quantize: Optional[Callable] = None,
    train: bool = False,
    masks: Optional[dict] = None,
) -> jax.Array:
    """The final norm's output [B, T, D]: what the head reads."""
    x = params["embedding"].astype(jnp.float32)[ids]
    layer = 0
    while (name := f"layers_{layer}") in params:
        run = lambda x, p, m: block(x, seg, masked(p, m), spec, quantize, train)
        x = (jax.checkpoint(run) if train else run)(
            x, params[name], None if masks is None else masks[name]
        )
        layer += 1
    return rmsnorm(x, params["final_norm"]["scale"], spec["rms_norm_eps"])


def _head(params, masks):
    return masked(params["lm_head"], None if masks is None else masks["lm_head"])["kernel"]


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
    every layer runs on ``w * m``."""
    x = hidden(params, spec, ids, seg, quantize, train, masks)
    return _mm(x, _head(params, masks), quantize)


def loss(params, spec, ids, seg, targets, quantize=None, masks=None):
    """``mean_loss(forward(...), targets)`` in training mode, the head and the
    loss ``ROW_BLOCK`` tokens at a time: the logits are never whole."""
    x = hidden(params, spec, ids, seg, quantize, True, masks)
    kernel = _head(params, masks)
    rows = lambda args: token_losses(_mm(args[0], kernel, quantize), args[1])
    return jnp.sum(_in_blocks(rows, (x, targets), ROW_BLOCK, True)) / jnp.sum(targets >= 0)
