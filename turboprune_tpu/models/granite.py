"""The ``granitemoehybrid`` decoder without experts: Mamba-2 layers beside
causal grouped-query attention with no positional encoding, a SwiGLU MLP after
each mixer (granite-4.0-h-micro's ``config.json``; ``num_local_experts`` 0).

The first language model of the registry: its input is not an image but a
packed token batch ``tokens [B, 2, T]`` int32, ``tokens[:, 0]`` the ids and
``tokens[:, 1]`` the id of the document each token belongs to (data/tokens.py),
and its output is next-token logits ``[B, T, V]``. Nothing crosses a document's
start: not the convolution, not the recurrence's state, not attention.

    x = embedding_multiplier * E[ids]
    x = x + residual_multiplier * mixer(rmsnorm(x))
    x = x + residual_multiplier * mlp(rmsnorm(x))        per layer
    logits = rmsnorm(x) E^T / logits_scaling             (tied head)

Every layer is a ``jax.checkpoint`` (``nn.remat``): a backward pass keeps the
layers' inputs and rebuilds one layer's inside, the chunked scan's decay
matrices and the MLP's gate and value (256 MB a layer) among it. That is part
of the model, not an option: at 8,192 tokens one layer's internals are about
2 GB. What a layer keeps beside its input is ``SAVED``, a few narrow values
tagged where they are made and named here (ops/remat.py), by bytes at 8,192 tokens in bf16:
the mixer's result (32 MB a layer, so that neither ``out_proj`` nor ``o_proj``
nor what feeds them runs again for the MLP's sake), attention's q, k and v
(50 MB, once) and ``in_proj``'s output of the Mamba mixer (139.5 MB a layer).
The scan's and attention's own outputs are rebuilt: the kernels run as often
as under a bare checkpoint.

The 2-D projections are ``kernel`` leaves and so prunable
(ops/masking.py::is_prunable_path): ``in_proj`` and ``out_proj`` of the Mamba
mixer and of the MLP, ``q_proj``, ``k_proj``, ``v_proj``, ``o_proj``. The tied
``embedding``, the convolution's ``conv_taps`` and ``conv_bias``, ``A_log``,
``D``, ``dt_bias`` and every norm's ``scale`` are not.

Named scopes label the device trace: ``mamba/in_proj``, ``mamba/conv``,
``ssd``, ``mamba/gate_norm``, ``mamba/out_proj``, ``attn/qkv``, ``attn/flash``,
``attn/out_proj``, ``mlp``, ``lm_head``.

models/nemotron_h.py imports ``RMSNorm``, ``MambaMixer`` (there with
``n_groups`` groups of heads and its own ``out_std``), ``AttentionMixer`` and
``_dense`` from here: a change to one of them is a change to both models, and
at one group and the default ``out_std`` ``MambaMixer`` is the program this
model has always run (tests/test_granite.py). The mixers' tags are shared
and decide nothing; what is kept is each model's own tuple at its own
``nn.remat`` line.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import remat
from ..ops.flash import flash_attention_causal
from ..ops.ssd import ssd_chunked

# granite-4.0-h-micro's period of ``layer_types``: attention at index 5 of
# every ten layers.
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
FLASH_BLOCK = 512
# What the backward pass of a layer keeps beside the layer's input.
SAVED = ("mixer_out", "attn_q", "attn_k", "attn_v", "mamba_in_proj")


def _dense(features: int, dtype, name: str, std: float = 0.02) -> nn.Dense:
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


def _same_document(seg, shift: int):
    """[B, T]: token ``t - shift`` exists and lies in ``t``'s document."""
    earlier = jnp.pad(seg, ((0, 0), (shift, 0)), constant_values=-1)[:, : seg.shape[1]]
    return earlier == seg


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Inverse softplus of a step drawn log-uniformly from [1e-3, 1e-1]
    (Mamba-2's own initialisation)."""
    lo, hi = math.log(1e-3), math.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi))
    return dt + jnp.log(-jnp.expm1(-dt))


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
            zxbcdt = _dense(inner + conv_dim + self.heads, self.dtype, "in_proj")(u)
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
                keep = _same_document(seg, shift)[..., None]
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
            return _dense(u.shape[-1], self.dtype, "out_proj", self.out_std)(y)


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
            q = _dense(self.heads * self.head_dim, self.dtype, "q_proj")(u)
            k = _dense(self.kv_heads * self.head_dim, self.dtype, "k_proj")(u)
            v = _dense(self.kv_heads * self.head_dim, self.dtype, "v_proj")(u)
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
            return _dense(dim, self.dtype, "o_proj")(out.reshape(bsz, t, -1))


class SwiGLU(nn.Module):
    hidden: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        with jax.named_scope("mlp"):
            gate, value = jnp.split(_dense(2 * self.hidden, self.dtype, "in_proj")(u), 2, axis=-1)
            return _dense(u.shape[-1], self.dtype, "out_proj")(nn.silu(gate) * value)


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """The published keys the model reads, under their published names
    (``attention_head_dim`` is hidden_size / num_attention_heads there)."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    attention_head_dim: int
    attention_multiplier: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    rms_norm_eps: float
    shared_intermediate_size: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_chunk_size: int


class HybridBlock(nn.Module):
    kind: str  # "mamba" | "attention"
    cfg: HybridConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, seg):
        c = self.cfg
        u = RMSNorm(c.rms_norm_eps, self.dtype, name="norm1")(x)
        if self.kind == "attention":
            y = AttentionMixer(
                c.num_attention_heads, c.num_key_value_heads, c.attention_head_dim,
                c.attention_multiplier, self.dtype, name="mixer",
            )(u, seg)  # fmt: skip
        else:
            y = MambaMixer(
                c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state, c.mamba_d_conv,
                c.mamba_chunk_size, c.rms_norm_eps, self.dtype, name="mixer",
            )(u, seg)  # fmt: skip
        x = x + jnp.asarray(c.residual_multiplier, self.dtype) * checkpoint_name(y, "mixer_out")
        u = RMSNorm(c.rms_norm_eps, self.dtype, name="norm2")(x)
        y = SwiGLU(c.shared_intermediate_size, self.dtype, name="mlp")(u)
        return x + jnp.asarray(c.residual_multiplier, self.dtype) * y


class HybridLM(nn.Module):
    vocab_size: int
    cfg: HybridConfig
    layer_types: tuple
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        del train  # no dropout, no batch statistics
        c = self.cfg
        ids, seg = tokens[:, 0], tokens[:, 1]
        table = self.param(
            "embedding", nn.initializers.normal(0.02), (self.vocab_size, c.hidden_size)
        )
        # One leaf for the two uses: the model is tied.
        x = (c.embedding_multiplier * table[ids]).astype(self.dtype)
        block = nn.remat(HybridBlock, policy=remat.keeping(SAVED))
        for i, kind in enumerate(self.layer_types):
            x = block(kind, c, self.dtype, name=f"layers_{i}")(x, seg)
        x = RMSNorm(c.rms_norm_eps, self.dtype, name="final_norm")(x)
        with jax.named_scope("lm_head"):
            logits = jnp.einsum(
                "btd,vd->btv", x, table.astype(self.dtype), preferred_element_type=jnp.float32
            )
            return logits / c.logits_scaling


# granite-4.0-h-micro as published (huggingface.co/ibm-granite/
# granite-4.0-h-micro, config.json).
GRANITE_4_0_H_MICRO = dict(
    hidden_size=2048,
    num_attention_heads=32,
    num_key_value_heads=8,
    attention_head_dim=64,
    attention_multiplier=0.015625,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    rms_norm_eps=1e-5,
    shared_intermediate_size=8192,
    mamba_n_heads=64,
    mamba_d_head=64,
    mamba_d_state=128,
    mamba_d_conv=4,
    mamba_chunk_size=256,
)
# The same block at a test's size: every kind of layer, every multiplier,
# two chunks in a 32-token sequence.
HYBRID_TINY = dict(
    GRANITE_4_0_H_MICRO,
    hidden_size=32,
    num_attention_heads=4,
    num_key_value_heads=2,
    attention_head_dim=8,
    shared_intermediate_size=48,
    mamba_n_heads=4,
    mamba_d_head=16,
    mamba_d_state=8,
    mamba_chunk_size=16,
)


def _layer_types(num_layers: int) -> tuple:
    return tuple(PERIOD[i % len(PERIOD)] for i in range(num_layers))


def granite_4_0_h_micro(num_classes: int, *, num_layers: int = 0, dtype=jnp.float32) -> HybridLM:
    """``num_classes`` is the vocabulary held (100,352 published);
    ``num_layers`` 0 means the published 40."""
    return HybridLM(
        num_classes, HybridConfig(**GRANITE_4_0_H_MICRO), _layer_types(num_layers or 40), dtype
    )


def hybrid_lm_tiny(num_classes: int, *, num_layers: int = 0, dtype=jnp.float32) -> HybridLM:
    """Three layers, the attention one in the middle, unless told otherwise."""
    types = _layer_types(num_layers) if num_layers else ("mamba", "attention", "mamba")
    return HybridLM(num_classes, HybridConfig(**HYBRID_TINY), types, dtype)
