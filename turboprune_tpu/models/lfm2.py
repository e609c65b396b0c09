"""The ``lfm2_moe`` decoder (LFM2-8B-A1B's ``config.json``): most layers mix
tokens with a doubly gated short convolution, one in four with rotary
grouped-query attention; the first ``num_dense_layers`` layers follow their
mixer with a dense SwiGLU MLP, every later one with sigmoid-routed SwiGLU
experts; the head is the embedding.

    h = x + Op(rmsnorm(x));   y = h + FF(rmsnorm(h))         eps 1e-5, 24 layers
    logits = rmsnorm(y_last) E^T                                     (tied head)
    Op = conv:  [B | C | u] = W_in n  (no bias);  v = B * u
                g_t = sum_{k=0..2} taps[k] * v_{t-2+k}   depthwise, causal, no
                bias, no activation; a tap that would reach into the document
                before reads zero;   out = W_out (C * g)
    Op = attn:  q, k, v = W_q n, W_k n, W_v n, no bias;  a head at a time
                q <- rmsnorm_64(q), k <- rmsnorm_64(k);  rotary over the whole
                head, theta 1,000,000, halves rotated, the position the
                token's index in its document;  causal inside the document,
                scores / sqrt(64), 4 query heads a key/value head;  W_o
    FF dense:   W_2 (silu(W_1 n) * W_3 n)                 2,048 -> 7,168 -> 2,048
    FF routed:  s = sigmoid(W_r n) over all 32, float32;  top = the 4 largest
                of s + b (b: no gradient);  w_i = scaling * s_i / (sum_top s + 1e-6)
                out = sum_{i in top, i held here} w_i W_2i (silu(W_1i n) * W_3i n)
                2,048 -> 1,792 -> 2,048; no shared expert

The input and the packing are models/granite.py's (``tokens [B, 2, T]``, ids
and document ids; nothing crosses a document's start: not the convolution,
not attention, not the positions). What this file shares is
models/blocks.py's: ``RMSNorm``, ``dense``, ``same_document``, ``SwiGLU``,
``RotaryAttention`` (here under ``packed_causal``: a document's own positions
and ops/flash.py's packed causal kernels), ``SparseMoE`` over ``GatedExperts``
(the pair buffer over three stacked kernels) and the sigmoid ``Router``
(float32, a selection bias; here with the source's 1e-6), and ``Share``.

**A chip's share** (``Share.of``, which divides what ``Lfm2Config.DIVIDED`` lists):
``tensor_parallel`` chips divide the query heads, each holding the key/value
heads its query heads read, the convolution's channels (the same channels of
``B``, ``C`` and ``u``, and the rows of ``W_out`` that read them), the dense
MLPs' columns and the vocabulary (``num_classes`` is what is held);
``expert_parallel`` chips divide the routed experts and ``expert_rank`` says
which are here. The convolution is depthwise and both gates elementwise, so a
chip's channels need no other chip's. The router keeps its width and its
``top_k`` and normalises over all the chosen; nothing stands in for the
absent chips or their exchange, and the partial sums go on.

Every layer is a ``jax.checkpoint`` that keeps ``SAVED`` beside its input
(ops/remat.py), by bytes at 8,192 rows: a routed layer's float32 router
logits (1 MB), the experts chosen and their sorted order (0.13 MB each); an
attention layer's q, k and v as the kernel takes them, normed and rotated
(10.5 MB at 8 query and 2 key/value heads). The convolution's three streams
are rebuilt: one product of 2,048 x 1,536 a layer.

Prunable: every projection ``kernel`` (``in_proj`` and ``out_proj`` of the
convolution and of the dense MLP, ``q_proj``, ``k_proj``, ``v_proj``,
``o_proj``) and the experts' three stacked kernels ``[experts, in, out]``,
each expert's each kernel a layer of its own (ops/masking.py). Not prunable:
the tied ``embedding``, the convolution's ``conv_taps``, the router's float32
``weight`` and its selection ``bias`` (zero, and no gradient reaches it) and
the norms. The router and the norm before it are float32 whatever the compute
dtype.

Named scopes: ``conv/in_proj``, ``conv/gate_conv`` (both gates and the three
taps), ``conv/out_proj``, ``attn/qkv``, ``attn/qk_norm``, ``attn/rope``,
``attn/flash``, ``attn/out_proj``, ``mlp`` (the dense layers), ``moe/router``,
ops/moe.py's ``moe/dispatch``, ``moe/experts``, ``moe/combine``, and
``lm_head``. Each routed layer sows ops/moe.py's ``COUNTERS`` and
``moe_rounds`` into ``counters`` (the train step sums them over the layers)
and, where a caller makes ``intermediates`` mutable, its MoE input and choice.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import moe, remat
from ..ops.flash import flash_attention_causal
from .blocks import (
    FLASH_BLOCK, GatedExperts, RMSNorm, RotaryAttention, Router, Share, SparseMoE, SwiGLU, dense,
    head_output, positions, same_document,
)  # fmt: skip

# What the backward pass of a layer keeps beside the layer's input.
SAVED = ("router_logits", "router_top", "moe_order", "attn_q", "attn_k", "attn_v")
ROUTER_EPS = 1e-6  # beside the chosen scores' sum (``norm_topk_prob``)
KINDS = ("conv", "full_attention")


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """The published keys the model reads, under their published names
    (``head_dim`` is hidden_size / num_attention_heads there)."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    norm_eps: float
    rope_theta: float
    conv_L_cache: int
    layer_types: tuple
    num_dense_layers: int
    num_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    num_hidden_layers: int

    # What ``Share.of`` divides (models/blocks.py), by field.
    DIVIDED = {
        "query_heads": "num_attention_heads", "conv_channels": "hidden_size",
        "dense_columns": "intermediate_size",
    }  # fmt: skip
    KV_HEADS, EXPERTS = "num_key_value_heads", "num_experts"


def held(c: Lfm2Config, share: Share) -> dict:
    """What this chip holds of each layer."""
    return share.of(c)


class ShortConv(nn.Module):
    """The doubly gated short convolution over ``channels`` of the layer's
    channels: ``in_proj`` columns [B | C | u], ``channels`` each."""

    channels: int
    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u, seg):
        with jax.named_scope("conv/in_proj"):
            b, c, x = jnp.split(dense(3 * self.channels, self.dtype, "in_proj")(u), 3, axis=-1)
        bound = 1.0 / math.sqrt(self.width)
        taps = self.param(
            "conv_taps",
            lambda key, shape: jax.random.uniform(key, shape, jnp.float32, -bound, bound),
            (self.width, self.channels),
        )
        with jax.named_scope("conv/gate_conv"):
            taps = taps.astype(self.dtype)
            v = b * x
            conv = jnp.zeros_like(v)
            for k in range(self.width):
                shift = self.width - 1 - k
                earlier = jnp.pad(v, ((0, 0), (shift, 0), (0, 0)))[:, : v.shape[1]]
                conv = conv + taps[k] * jnp.where(same_document(seg, shift)[..., None], earlier, 0)
            y = c * conv
        with jax.named_scope("conv/out_proj"):
            return dense(u.shape[-1], self.dtype, "out_proj")(y)


def packed_causal(seg):
    """``RotaryAttention``'s rule for packed documents ``seg`` [B, T]: a
    token's position is its index inside its document, and a query sees the
    keys of its document at or before itself (ops/flash.py's second part)."""

    def kernel(q, k, v):
        block = math.gcd(q.shape[1], FLASH_BLOCK)
        scale = 1.0 / math.sqrt(q.shape[2])
        return flash_attention_causal(q, k, v, seg, scale, block, block), {}  # nothing to sow

    return lambda: positions(seg), kernel


class Lfm2Block(nn.Module):
    kind: str  # one of ``KINDS``
    routed: bool
    cfg: Lfm2Config
    share: Share
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, seg):
        c, here = self.cfg, self.share.of(self.cfg)
        u = RMSNorm(c.norm_eps, self.dtype, name="operator_norm")(x)
        if self.kind == "conv":
            y = ShortConv(here["conv_channels"], c.conv_L_cache, self.dtype, name="mixer")(u, seg)
        elif self.kind == "full_attention":
            y = RotaryAttention(
                here["query_heads"], here["kv_heads"], c.head_dim, c.norm_eps, c.rope_theta,
                self.dtype, name="mixer",
            )(u, *packed_causal(seg))  # fmt: skip
        else:
            raise ValueError(f"no layer kind {self.kind!r} {KINDS}")
        h = x + y
        if not self.routed:
            u = RMSNorm(c.norm_eps, self.dtype, name="ffn_norm")(h)
            return h + SwiGLU(here["dense_columns"], self.dtype, name="mlp")(u)
        self.sow("intermediates", "moe_in", h)
        u = RMSNorm(c.norm_eps, jnp.float32, name="ffn_norm")(h)
        return h + SparseMoE(
            Router(
                c.num_experts, c.num_experts_per_tok, c.routed_scaling_factor, ROUTER_EPS,
                parent=None,
            ),
            GatedExperts(
                c.hidden_size, c.moe_intermediate_size, c.num_experts, c.num_experts_per_tok,
                here["experts_here"], here["expert_offset"], self.dtype, parent=None,
            ),
            name="mlp",
        )(u)  # fmt: skip


class Lfm2(nn.Module):
    vocab_size: int  # as held
    cfg: Lfm2Config
    layers: int  # the first so many of ``layer_types``
    share: Share = Share()
    dtype: Any = jnp.float32

    # What its routed layers sow into ``counters`` (train/steps.py).
    counters = (*moe.COUNTERS, "moe_rounds")

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
        x = table[ids].astype(self.dtype)
        block = nn.remat(Lfm2Block, policy=remat.keeping(SAVED))
        for i, kind in enumerate(c.layer_types[: self.layers]):
            x = block(
                kind, i >= c.num_dense_layers, c, self.share, self.dtype, name=f"layers_{i}"
            )(x, seg)
        x = RMSNorm(c.norm_eps, self.dtype, name="final_norm")(x)
        with jax.named_scope("lm_head"):
            logits_of = lambda x, table: jnp.einsum(
                "btd,vd->btv", x, table.astype(self.dtype), preferred_element_type=jnp.float32
            )
            return head_output(logits_of, x, table, reduce=reduce)


# LFM2-8B-A1B as published (huggingface.co/LiquidAI/LFM2-8B-A1B, config.json):
# attention at layers 2, 6, 10, 14, 18 and 21, the short convolution elsewhere.
LFM2_8B_A1B = dict(
    hidden_size=2048,
    intermediate_size=7168,
    moe_intermediate_size=1792,
    num_attention_heads=32,
    num_key_value_heads=8,
    head_dim=64,
    norm_eps=1e-5,
    rope_theta=1_000_000.0,
    conv_L_cache=3,
    layer_types=tuple(
        "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv" for i in range(24)
    ),
    num_dense_layers=2,
    num_experts=32,
    num_experts_per_tok=4,
    routed_scaling_factor=1.0,
    num_hidden_layers=24,
)
# The same blocks at a test's size: a dense convolution layer, then a routed
# attention layer and a routed convolution layer; sixteen experts of which a
# token picks four.
LFM2_MOE_TINY = dict(
    LFM2_8B_A1B,
    hidden_size=32,
    intermediate_size=48,
    moe_intermediate_size=24,
    num_attention_heads=4,
    num_key_value_heads=2,
    head_dim=8,
    layer_types=("conv", "full_attention", "conv"),
    num_dense_layers=1,
    num_experts=16,
    num_experts_per_tok=4,
    num_hidden_layers=3,
)


def _build(published: dict, num_classes, num_layers, dtype, layer_pattern, share) -> Lfm2:
    if layer_pattern:
        raise ValueError(
            f"this model's layers are its first num_layers as published: no layer_pattern ({layer_pattern!r})"
        )
    cfg = Lfm2Config(**published)
    if not 0 <= num_layers <= cfg.num_hidden_layers:
        raise ValueError(f"num_layers {num_layers} of {cfg.num_hidden_layers} published")
    return Lfm2(num_classes, cfg, num_layers or cfg.num_hidden_layers, Share(*share), dtype)


def lfm2_8b_a1b(
    num_classes: int, *, num_layers: int = 0, dtype=jnp.float32, layer_pattern: str = "",
    share: tuple = (),
) -> Lfm2:  # fmt: skip
    """``num_classes`` is the vocabulary held (65,536 published);
    ``num_layers`` the first so many of the published 24 (0 = all);
    ``share`` (tensor_parallel, expert_parallel, expert_rank)."""
    return _build(LFM2_8B_A1B, num_classes, num_layers, dtype, layer_pattern, share)


def lfm2_moe_tiny(
    num_classes: int, *, num_layers: int = 0, dtype=jnp.float32, layer_pattern: str = "",
    share: tuple = (),
) -> Lfm2:  # fmt: skip
    """Three layers unless told otherwise."""
    return _build(LFM2_MOE_TINY, num_classes, num_layers, dtype, layer_pattern, share)
