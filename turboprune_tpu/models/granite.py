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

``RMSNorm``, ``MambaMixer``, ``AttentionMixer`` and ``SwiGLU`` are
models/blocks.py's, which models/nemotron_h.py runs too (``MambaMixer`` there
with ``n_groups`` groups of heads and its own ``out_std``): at one group and
the default ``out_std`` ``MambaMixer`` is the program this model has always
run (tests/test_granite.py). The mixers' tags are shared and decide nothing;
what is kept is each model's own tuple at its own ``nn.remat`` line.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import remat
from .blocks import AttentionMixer, MambaMixer, RMSNorm, SwiGLU, head_output

# granite-4.0-h-micro's period of ``layer_types``: attention at index 5 of
# every ten layers.
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
# What the backward pass of a layer keeps beside the layer's input.
SAVED = ("mixer_out", "attn_q", "attn_k", "attn_v", "mamba_in_proj")


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
    def __call__(self, tokens, train: bool = False, reduce=None):
        """The logits ``[B, T, V]``; or, given ``reduce``, what it makes of
        them a block of tokens at a time (models/blocks.py's ``head_output``)."""
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
            logits_of = lambda x, table: jnp.einsum(
                "btd,vd->btv", x, table.astype(self.dtype), preferred_element_type=jnp.float32
            ) / c.logits_scaling
            return head_output(logits_of, x, table, reduce=reduce)


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


def _build(name: str, published: dict, layer_types, num_classes, dtype, layer_pattern, share):
    if layer_pattern or tuple(share):
        raise ValueError(f"{name!r} has no layer_pattern and no share")
    return HybridLM(num_classes, HybridConfig(**published), layer_types, dtype)


def granite_4_0_h_micro(
    num_classes: int, *, num_layers: int = 0, dtype=jnp.float32, layer_pattern: str = "",
    share: tuple = (),
) -> HybridLM:  # fmt: skip
    """``num_classes`` is the vocabulary held (100,352 published);
    ``num_layers`` 0 means the published 40; the model is built whole, its
    period its own: ``layer_pattern`` and ``share`` are refused."""
    types = _layer_types(num_layers or 40)
    return _build("granite_4_0_h_micro", GRANITE_4_0_H_MICRO, types, num_classes, dtype, layer_pattern, share)


def hybrid_lm_tiny(
    num_classes: int, *, num_layers: int = 0, dtype=jnp.float32, layer_pattern: str = "",
    share: tuple = (),
) -> HybridLM:  # fmt: skip
    """Three layers, the attention one in the middle, unless told otherwise."""
    types = _layer_types(num_layers) if num_layers else ("mamba", "attention", "mamba")
    return _build("hybrid_lm_tiny", HYBRID_TINY, types, num_classes, dtype, layer_pattern, share)
