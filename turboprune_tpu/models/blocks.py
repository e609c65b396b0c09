"""The blocks that more than one language model runs. The model files
(granite.py, nemotron_h.py, sdar.py, lfm2.py, brumby.py) import from here and this file
imports none of them: a block that two models use is written here once, a
block that one uses stays with it (tests/test_models_table.py holds the
imports to it). An edit here is an edit to every model that names the block, and
tests/test_sdar.py holds each model's lowered program to its text.

Their input is a packed token batch (data/tokens.py): ids and the id of the
document each token belongs to. Nothing crosses a document's start: not a
convolution, not the recurrence's state, not attention, not a position.

    dense, RMSNorm, same_document, rotary       what every block is made of
    positions                                   a token's index in its document: lfm2.py, brumby.py
    MambaMixer, AttentionMixer, SwiGLU          granite.py, nemotron_h.py (lfm2.py, brumby.py: SwiGLU)
    RotaryAttention                             sdar.py, lfm2.py, each with its kernel
    Share                                       a chip's share of a layer: all but granite.py
    Router                                      sigmoid: nemotron_h.py, lfm2.py
    GatedExperts, SparseMoE                     sdar.py, lfm2.py, each with its router
    Head                                        untied: nemotron_h.py, sdar.py, brumby.py

The tags (``checkpoint_name``) and the named scopes (each model's docstring
lists its own) are shared and decide nothing; what a layer's backward pass
keeps is each model's own ``SAVED`` at its own ``nn.remat`` line
(ops/remat.py).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import moe
from ..ops.flash import flash_attention_causal
from ..ops.ssd import ssd_chunked

FLASH_BLOCK = 512


def dense(features: int, dtype, name: str, std: float = 0.02) -> nn.Dense:
    return nn.Dense(
        features,
        use_bias=False,
        dtype=dtype,
        kernel_init=nn.initializers.normal(std),
        name=name,
    )


class RMSNorm(nn.Module):
    """``groups`` > 1 normalises each of that many equal runs of channels on
    its own (Mamba-2's gated norm under ``n_groups``); the scale stays one
    vector over all channels."""

    eps: float
    dtype: Any = jnp.float32
    groups: int = 1

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        if self.groups > 1:
            x32 = x32.reshape(x.shape[:-1] + (self.groups, -1))
        y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (y.reshape(x.shape) * scale).astype(self.dtype)


def same_document(seg, shift: int):
    """[B, T]: token ``t - shift`` exists and lies in ``t``'s document."""
    earlier = jnp.pad(seg, ((0, 0), (shift, 0)), constant_values=-1)[:, : seg.shape[1]]
    return earlier == seg


def positions(seg):
    """[B, T] int32: each token's index inside its document, from the
    document ids alone."""
    at = jnp.arange(seg.shape[1], dtype=jnp.int32)
    starts = jnp.where(same_document(seg, 1), 0, at)  # a document's first token: its index
    return at - jax.lax.cummax(starts, axis=1)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Inverse softplus of a step drawn log-uniformly from [1e-3, 1e-1]
    (Mamba-2's own initialisation)."""
    lo, hi = math.log(1e-3), math.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi))
    return dt + jnp.log(-jnp.expm1(-dt))


def rotary(x, pos, theta: float):
    """``x`` [B, R, H, D] rotated by ``pos`` [B, R]: the halves of the head
    dimension as one complex number a frequency, ``theta ** (-2 j / D)``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = pos.astype(jnp.float32)[..., None, None] * freq  # [B, R, 1, D / 2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


class MambaMixer(nn.Module):
    """``n_groups`` groups of heads, each with its own ``B`` and ``C`` and its
    own run of the gated norm (``in_proj`` columns [z | x | B_0.. | C_0.. | dt]);
    at one group the program is what it was before there were groups."""

    heads: int
    head_dim: int
    state: int
    conv_width: int
    chunk: int
    eps: float
    dtype: Any = jnp.float32
    n_groups: int = 1
    out_std: float = 0.02  # of ``out_proj``'s initial values

    @nn.compact
    def __call__(self, u, seg):
        inner = self.heads * self.head_dim
        bc_dim = self.n_groups * self.state
        conv_dim = inner + 2 * bc_dim
        with jax.named_scope("mamba/in_proj"):
            zxbcdt = dense(inner + conv_dim + self.heads, self.dtype, "in_proj")(u)
            zxbcdt = checkpoint_name(zxbcdt, "mamba_in_proj")
            z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)

        bound = 1.0 / math.sqrt(self.conv_width)
        taps = self.param(
            "conv_taps",
            lambda key, shape: jax.random.uniform(key, shape, jnp.float32, -bound, bound),
            (self.conv_width, conv_dim),
        )
        conv_bias = self.param("conv_bias", nn.initializers.zeros, (conv_dim,))
        with jax.named_scope("mamba/conv"):
            # Causal, depthwise; a tap that would reach into the document
            # before reads zero.
            taps = taps.astype(self.dtype)
            conv = jnp.zeros_like(xbc)
            for k in range(self.conv_width):
                shift = self.conv_width - 1 - k
                earlier = jnp.pad(xbc, ((0, 0), (shift, 0), (0, 0)))[:, : xbc.shape[1]]
                keep = same_document(seg, shift)[..., None]
                conv = conv + taps[k] * jnp.where(keep, earlier, 0)
            xbc = nn.silu(conv + conv_bias.astype(self.dtype))

        x, b, c = jnp.split(xbc, [inner, inner + bc_dim], axis=-1)
        x = x.reshape(x.shape[:2] + (self.heads, self.head_dim))
        if self.n_groups > 1:
            b, c = (v.reshape(v.shape[:2] + (self.n_groups, self.state)) for v in (b, c))
        dt_bias = self.param("dt_bias", _dt_bias_init, (self.heads,))
        a_log = self.param(
            "A_log",
            lambda key, shape: jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)),
            (self.heads,),
        )
        skip = self.param("D", nn.initializers.ones, (self.heads,))
        with jax.named_scope("ssd"):
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            y = ssd_chunked(x, dt, -jnp.exp(a_log), b, c, seg, self.chunk)
            y = y + skip.astype(self.dtype)[:, None] * x
        with jax.named_scope("mamba/gate_norm"):
            y = y.reshape(z.shape) * nn.silu(z)
            y = RMSNorm(self.eps, self.dtype, self.n_groups, name="gate_norm")(y)
        with jax.named_scope("mamba/out_proj"):
            return dense(u.shape[-1], self.dtype, "out_proj", self.out_std)(y)


class AttentionMixer(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    scale: float  # ``attention_multiplier``, not 1 / sqrt(head_dim)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u, seg):
        bsz, t, dim = u.shape
        with jax.named_scope("attn/qkv"):
            q = dense(self.heads * self.head_dim, self.dtype, "q_proj")(u)
            k = dense(self.kv_heads * self.head_dim, self.dtype, "k_proj")(u)
            v = dense(self.kv_heads * self.head_dim, self.dtype, "v_proj")(u)
            rows = lambda x, h: x.reshape(bsz, t, h, self.head_dim).transpose(0, 2, 1, 3).reshape(
                bsz * h, t, self.head_dim
            )
            q, k, v = rows(q, self.heads), rows(k, self.kv_heads), rows(v, self.kv_heads)
            q, k, v = (checkpoint_name(x, f"attn_{n}") for x, n in ((q, "q"), (k, "k"), (v, "v")))
        with jax.named_scope("attn/flash"):
            block = math.gcd(t, FLASH_BLOCK)
            out = flash_attention_causal(q, k, v, seg, self.scale, block, block)
        with jax.named_scope("attn/out_proj"):
            out = out.reshape(bsz, self.heads, t, self.head_dim).transpose(0, 2, 1, 3)
            return dense(dim, self.dtype, "o_proj")(out.reshape(bsz, t, -1))


class RotaryAttention(nn.Module):
    """Grouped-query attention whose heads are normed and rotated: the
    projections, the norm of q and k a head at a time, the rotation, the
    by-head layout with its three tags and the output projection. The rule
    is the model's: ``positions()`` [B, R] of the rows ``u`` holds, read
    inside ``attn/rope``, and ``kernel(q, k, v)`` ([B * heads, R, D] each,
    batch-major), run inside ``attn/flash``, which returns the attention's
    output and what this layer sows into ``counters``."""

    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u, positions, kernel):
        bsz, rows, dim = u.shape
        d = self.head_dim
        with jax.named_scope("attn/qkv"):
            q = dense(self.heads * d, self.dtype, "q_proj")(u).reshape(bsz, rows, self.heads, d)
            k = dense(self.kv_heads * d, self.dtype, "k_proj")(u).reshape(bsz, rows, self.kv_heads, d)
            v = dense(self.kv_heads * d, self.dtype, "v_proj")(u).reshape(bsz, rows, self.kv_heads, d)
        with jax.named_scope("attn/qk_norm"):
            q = RMSNorm(self.eps, self.dtype, name="q_norm")(q)
            k = RMSNorm(self.eps, self.dtype, name="k_norm")(k)
        with jax.named_scope("attn/rope"):
            pos = positions()
            q, k = rotary(q, pos, self.theta), rotary(k, pos, self.theta)
            by_head = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, rows, d)
            q, k, v = (
                checkpoint_name(by_head(x), f"attn_{n}") for x, n in ((q, "q"), (k, "k"), (v, "v"))
            )
        with jax.named_scope("attn/flash"):
            out, counters = kernel(q, k, v)
            for name, value in counters.items():
                self.sow("counters", name, value)
        with jax.named_scope("attn/out_proj"):
            out = out.reshape(bsz, self.heads, rows, d).transpose(0, 2, 1, 3)
            return dense(dim, self.dtype, "o_proj")(out.reshape(bsz, rows, -1))


class SwiGLU(nn.Module):
    hidden: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        with jax.named_scope("mlp"):
            gate, value = jnp.split(dense(2 * self.hidden, self.dtype, "in_proj")(u), 2, axis=-1)
            return dense(u.shape[-1], self.dtype, "out_proj")(nn.silu(gate) * value)


@dataclasses.dataclass(frozen=True)
class Share:
    """Over how many chips a layer is divided, and which of them this is."""

    tensor_parallel: int = 1
    expert_parallel: int = 1
    expert_rank: int = 0

    def of(self, c) -> dict:
        """What this chip holds of each layer. ``c`` is a model's config, and
        it says what there is to divide: ``c.DIVIDED`` maps each key of the
        answer to the field whose count ``tensor_parallel`` chips divide
        evenly, ``c.KV_HEADS`` and ``c.EXPERTS`` name the fields that count the
        key/value heads and the routed experts. A dense model names no
        ``EXPERTS``: nothing of it is divided over ``expert_parallel`` chips,
        and its answer has no experts."""
        tp, ep = self.tensor_parallel, self.expert_parallel
        held = {}
        for key, name in c.DIVIDED.items():
            count = getattr(c, name)
            if count % tp:
                raise ValueError(f"{name} {count} does not divide over {tp} chips")
            held[key] = count // tp
        # A key/value head is held by every chip that holds a query head of its group.
        held["kv_heads"] = max(getattr(c, c.KV_HEADS) // tp, 1)
        experts = getattr(c, c.EXPERTS) if c.EXPERTS else 1
        if experts % ep or not 0 <= self.expert_rank < ep:
            raise ValueError(f"{experts} experts, rank {self.expert_rank} of {ep}")
        if not c.EXPERTS:
            return held
        return dict(
            held, experts_here=experts // ep, expert_offset=self.expert_rank * (experts // ep)
        )


class Router(nn.Module):
    """Float32 whatever the compute dtype. ``weight`` is a matrix and not a
    ``kernel``: it is never masked."""

    experts: int
    top_k: int
    scaling: float
    eps: float = 1e-20  # beside the chosen scores' sum (models/lfm2.py's source has 1e-6)

    @nn.compact
    def __call__(self, h32):
        weight = self.param("weight", nn.initializers.normal(0.02), (h32.shape[-1], self.experts))
        bias = self.param("bias", nn.initializers.zeros, (self.experts,))
        logits = jnp.einsum("nd,de->ne", h32, weight, precision=jax.lax.Precision.HIGHEST)
        logits = checkpoint_name(logits, "router_logits")
        return moe.route(logits, bias, self.top_k, self.scaling, self.eps)


class GatedExperts(nn.Module):
    """The routed experts held here, as three stacked kernels
    ``[experts, in, out]``."""

    hidden: int
    intermediate: int
    experts: int  # all of them, held here or not
    top_k: int
    experts_here: int
    expert_offset: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, top, weights):
        init = nn.initializers.normal(0.02)
        up = (self.experts_here, self.hidden, self.intermediate)
        kernels = (
            self.param("kernel_gate", init, up),
            self.param("kernel_up", init, up),
            self.param("kernel_down", init, (up[0], up[2], up[1])),
        )
        routing = (h.shape[0], self.top_k, self.experts)
        capacity, tile = moe.pair_capacity(*routing, self.experts_here), moe.pair_tile(*routing)
        out, counters = moe.routed_experts(
            h, top, weights, tuple(k.astype(self.dtype) for k in kernels), self.expert_offset,
            capacity, tile,
        )  # fmt: skip
        counters["moe_rounds"] = moe.rounds(top, self.expert_offset, self.experts_here, capacity, tile)
        return out, counters


class SparseMoE(nn.Module):
    """A routed feed-forward: the model's own ``router`` (``Router`` above,
    models/sdar.py's ``SoftmaxRouter``) over the ``experts`` held here. A
    block builds both with ``parent=None`` and this layer adopts them under
    the fields' names: their parameters are this layer's ``router/...`` and
    ``experts/...``."""

    router: nn.Module  # h32 [N, D] -> (top [N, top_k], weights [N, top_k])
    experts: GatedExperts

    @nn.compact
    def __call__(self, h32):
        """``h32`` [B, R, D]: the layer's normed input, float32."""
        dtype = self.experts.dtype
        flat = h32.reshape(-1, h32.shape[-1])
        with jax.named_scope("moe/router"):
            top, weights = self.router(flat)
        self.sow("intermediates", "top", top)
        out, counters = self.experts(flat.astype(dtype), top, weights)
        for name, value in counters.items():
            self.sow("counters", name, value)
        return out.astype(dtype).reshape(h32.shape)


def head_output(logits_of, x, *operands, reduce=None):
    """What every language model's ``__call__`` returns: the logits
    ``logits_of(x, *operands)``; or, where the caller gives a ``reduce``,
    ``reduce(logits_of, x, *operands)`` in their place. ``operands`` are the
    arrays the head reads beside ``x`` (the untied kernel, the tied table):
    ``logits_of`` closes over none, so that a ``reduce`` can differentiate
    with respect to them by a rule of its own. ``reduce`` runs ``logits_of``
    on as many of ``x``'s tokens at a time as it likes and returns what it
    makes of them (train/steps.py's loss a block of tokens at a time), so
    that ``[tokens, vocabulary]`` need never exist whole."""
    return logits_of(x, *operands) if reduce is None else reduce(logits_of, x, *operands)


class Head(nn.Module):
    """The untied head; ``reduce`` as ``head_output`` takes it."""

    vocab_size: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, reduce=None):
        kernel = self.param("kernel", nn.initializers.normal(0.02), (x.shape[-1], self.vocab_size))
        logits_of = lambda x, kernel: jnp.einsum(
            "btd,dv->btv", x, kernel.astype(self.dtype), preferred_element_type=jnp.float32
        )
        return head_output(logits_of, x, kernel, reduce=reduce)
