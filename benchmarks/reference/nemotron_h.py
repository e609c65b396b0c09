"""Plain forward of the ``nemotron_h`` decoder (NVIDIA-Nemotron-3-Super-
120B-A12B's ``config.json``): every layer ONE mixer, ``x + mixer(rmsnorm(x))``
with eps ``layer_norm_epsilon``, pre-norm, an untied head; the mixer a Mamba-2
layer with grouped ``B`` and ``C``, causal grouped-query attention without
positional encoding, or a LatentMoE; over packed documents.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision. It
reads a parameter tree laid out as the program's checkpoints are and imports
nothing of the program; what it shares with ``reference/granite.py`` (the
token-by-token recurrence, the masked softmax, the norm, the loss) it imports
from there:

    embedding [V, D];  lm_head/kernel [D, V];  final_norm/scale
    layers_<i>/norm/scale
    layers_<i>/mixer, a Mamba-2 mixer (told apart by ``A_log``):
        in_proj/kernel [D, 2 I + 2 G N + H]   columns [z | x | B_0..B_G-1 | C_0..C_G-1 | dt]
        conv_taps [K, I + 2 G N], conv_bias;  dt_bias, A_log, D [H]
        gate_norm/scale [I];  out_proj/kernel [I, D]
    or an attention mixer (``q_proj``), as ``reference/granite.py`` lays it out
    or a LatentMoE (``router``):
        router/weight [D, E], router/bias [E]      E: ALL the layer's routed experts
        latent_down/kernel [D, L], latent_up/kernel [L, D]
        experts/kernel_up [Eh, L, F], experts/kernel_down [Eh, F, L]
                                   the Eh experts ``expert_offset ..`` held here
        shared_up/kernel [D, S], shared_down/kernel [S, D]

and a ``spec``: ``layer_norm_epsilon``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim`` and ``n_groups`` AS HELD (the heads and
groups in the tree), ``num_experts_per_tok``, ``routed_scaling_factor`` and
``expert_offset``.

The equations, for hidden states ``x [T, D]`` of one packed sequence,
``h = rmsnorm(x)``:

    Mamba-2:  [z | xBC | dt] = h W_in;  xBC = silu(causal_conv(xBC) + b), cut at
              document starts;  x: [H, P], B, C: [G, N], head i reads group
              i // (H / G);  h_t = exp(dt_t a) h_{t-1} + dt_t x_t (outer) B_t,
              y_t = h_t C_t + D x_t, dt = softplus(dt + dt_bias);
              out = rmsnorm_per_group(y * silu(z)) W_out   (a group: I / G channels)
    attention: softmax over s <= t of the same document of q_t . k_s / sqrt(head_dim)
    LatentMoE: s = sigmoid(h W_r) in R^E;  top = the k largest of s + bias;
              w_i = scaling * s_i / (sum_{j in top} s_j + 1e-20)
              z = h W_down;  f_e(z) = relu(z W1_e)^2 W2_e
              out = (sum_{i in top, i held} w_i f_i(z)) W_up + relu(h V1)^2 V2

The experts are a plain loop over the experts held, every token through every
one of them, with weight zero where the router did not choose it.

Departures from the published model, all of them cuts the configuration's
file lists, or inferences it lists under ``assumed``:

- the tree holds one chip's share of a deployment: some of each mixer's heads
  (a Mamba group whole), some of the shared expert's columns, the routed
  experts from ``expert_offset`` on, a slice of the vocabulary. The router
  scores all experts and normalises over all it chose; what absent heads,
  columns and experts would add is left out, and the partial sum goes on;
- the depth is whatever the tree holds (``layers_0`` ... in order);
- no multi-token-prediction module (the config does not define its join);
- no positional encoding in attention (``nemotron_h`` applies none);
- in training mode every block is a ``jax.checkpoint``, as is each expert of
  the loop; the recurrence and attention nest theirs as granite.py's do.

``quantize`` is for the control only: both operands of every projection, of
the experts' products and of the head; never the router, whose stated
precision is float32.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.granite import (  # noqa: F401  (re-exported for the followers)
    _mm,
    _recurrence,
    _same_document,
    _shifted,
    attention_mixer,
    fp8_operand,
    masked,
    mean_loss,
    next_token_targets,
    rmsnorm,
    token_losses,
)

HIGHEST = lax.Precision.HIGHEST


def mamba_mixer(u, seg, p, spec, quantize=None, train=False):
    """u [B, T, D] float32, seg [B, T] -> [B, T, D]."""
    heads, groups = p["dt_bias"].shape[0], spec["n_groups"]
    inner = p["out_proj"]["kernel"].shape[0]
    state = (p["conv_bias"].shape[0] - inner) // (2 * groups)
    taps = p["conv_taps"].astype(jnp.float32)
    zxbcdt = _mm(u, p["in_proj"]["kernel"], quantize)
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * groups * state], axis=-1)

    width = taps.shape[0]
    conv = jnp.zeros_like(xbc)
    for k in range(width):
        shift = width - 1 - k
        keep = _same_document(seg, shift)[..., None]
        conv = conv + taps[k] * jnp.where(keep, _shifted(xbc, shift), 0.0)
    xbc = jax.nn.silu(conv + p["conv_bias"])

    x, b, c = jnp.split(xbc, [inner, inner + groups * state], axis=-1)
    x = x.reshape(x.shape[:2] + (heads, inner // heads))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    start = ~_same_document(seg, 1)
    per = heads // groups
    y = jnp.concatenate(
        [
            _recurrence(
                x[:, :, g * per : (g + 1) * per], dt[:, :, g * per : (g + 1) * per],
                a[g * per : (g + 1) * per], b[..., g * state : (g + 1) * state],
                c[..., g * state : (g + 1) * state], start, train,
            )
            for g in range(groups)
        ],
        axis=2,
    )  # fmt: skip
    y = y + p["D"][:, None] * x
    y = y.reshape(z.shape) * jax.nn.silu(z)
    # The gated norm, a group of channels at a time.
    grouped = y.reshape(y.shape[:-1] + (groups, inner // groups))
    grouped = grouped * lax.rsqrt(
        jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + spec["layer_norm_epsilon"]
    )
    y = grouped.reshape(y.shape) * p["gate_norm"]["scale"]
    return _mm(y, p["out_proj"]["kernel"], quantize)


def route(h, router, spec):
    """(top [N, K] expert ids, weights [N, K]) of normed inputs ``h`` [N, D]."""
    s = jax.nn.sigmoid(
        jnp.einsum("nd,de->ne", h, router["weight"].astype(jnp.float32), precision=HIGHEST)
    )
    _, top = lax.top_k(s + lax.stop_gradient(router["bias"]), spec["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, top, axis=-1)
    scale = spec["routed_scaling_factor"]
    return top, scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def routing(x, layer, spec):
    """The experts the router chooses for a layer's input ``x`` [..., D] (the
    residual stream entering the layer, whatever its dtype): [N, K], sorted."""
    x = x.astype(jnp.float32).reshape(-1, x.shape[-1])
    h = rmsnorm(x, layer["norm"]["scale"], spec["layer_norm_epsilon"])
    return jnp.sort(route(h, layer["mixer"]["router"], spec)[0], axis=-1)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def latent_moe(u, p, spec, quantize=None, train=False):
    """u [B, T, D] float32 -> [B, T, D]."""
    h = u.reshape(-1, u.shape[-1])
    top, weights = route(h, p["router"], spec)
    experts = p["router"]["weight"].shape[1]
    held = p["experts"]["kernel_up"].shape[0]
    # [N, E]: a token's weight for every expert, zero where not chosen.
    dense = jnp.zeros((h.shape[0], experts), jnp.float32)
    dense = dense.at[jnp.arange(h.shape[0])[:, None], top].set(weights)
    here = lax.dynamic_slice_in_dim(dense, spec["expert_offset"], held, axis=1)
    z = _mm(h, p["latent_down"]["kernel"], quantize)

    def one(total, expert):
        w1, w2, w = expert
        return total + w[:, None] * _mm(_relu2(_mm(z, w1, quantize)), w2, quantize), None

    one = jax.checkpoint(one) if train else one
    mixed, _ = lax.scan(
        one, jnp.zeros_like(z), (p["experts"]["kernel_up"], p["experts"]["kernel_down"], here.T)
    )
    out = _mm(mixed, p["latent_up"]["kernel"], quantize)
    shared = _mm(_relu2(_mm(h, p["shared_up"]["kernel"], quantize)), p["shared_down"]["kernel"], quantize)
    return (out + shared).reshape(u.shape)


def block(x, seg, p, spec, quantize=None, train=False):
    """One decoder layer: the one mixer its tree holds."""
    u = rmsnorm(x, p["norm"]["scale"], spec["layer_norm_epsilon"])
    mixer = p["mixer"]
    if "router" in mixer:
        return x + latent_moe(u, mixer, spec, quantize, train)
    if "q_proj" in mixer:
        attn = dict(spec, attention_multiplier=1.0 / math.sqrt(spec["head_dim"]))
        return x + attention_mixer(u, seg, mixer, attn, quantize, train)
    return x + mamba_mixer(u, seg, mixer, spec, quantize, train)


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
    every layer and the head run on ``w * m``, the product formed inside the
    layer's ``jax.checkpoint``."""
    x = params["embedding"].astype(jnp.float32)[ids]
    layer = 0
    while (name := f"layers_{layer}") in params:
        run = lambda x, p, m: block(x, seg, masked(p, m), spec, quantize, train)
        x = (jax.checkpoint(run) if train else run)(
            x, params[name], None if masks is None else masks[name]
        )
        layer += 1
    x = rmsnorm(x, params["final_norm"]["scale"], spec["layer_norm_epsilon"])
    head = masked(params["lm_head"], None if masks is None else masks["lm_head"])
    return _mm(x, head["kernel"], quantize)
