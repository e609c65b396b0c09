"""The ``sdar_moe`` decoder (SDAR-30B-A3B-Chat's ``config.json``): a rotary
grouped-query transformer whose every layer routes, the ``qwen3_moe`` block,
trained by diffusion over blocks (Arriola et al., arXiv:2503.09573).

    x0 = E[ids] (0.01 E[mask] for the mask's id);  h = x + Attn(rmsnorm(x));
    y = h + MoE(rmsnorm(h))                                             48 alike
    logits = rmsnorm(y_last) W_head                    eps 1e-6, untied, unscaled
    Attn:  q = W_q u, k = W_k u, v = W_v u, no bias;  a head at a time
           q <- rmsnorm_128(q), k <- rmsnorm_128(k) (a learned weight a head
           dimension); rotary over the whole head dimension, theta 1,000,000,
           halves rotated (x1, x2 -> x1 cos - x2 sin, x2 cos + x1 sin), the
           position the token's index in its document; scores q k^T /
           sqrt(128); 8 query heads a key/value head; softmax over the keys
           the mask keeps; W_o
    MoE:   p = softmax(W_r h) over all 128, float32;  top = the 8 largest;
           w_i = p_i / sum_{j in top} p_j
           out = sum_{i in top, i held here} w_i W_down_i (silu(W_gate_i h) * W_up_i h)
           2,048 -> 768 -> 2,048; no shared expert, no bias, no auxiliary loss

**Two copies under one mask.** The input is a block-diffusion batch
(data/tokens.py: ``tokens [B, 5, T]``, the clean ids, the document, the
block's ordinal, the position, the noised ids). The layers see ``2T`` rows a
sequence, the clean copy then the noised copy of the same tokens with the
same positions, under the block-diffusion mask (ops/flash.py, third part):
block-causal among the clean rows, a noised row onto the strictly earlier
clean blocks and its own noised block, nothing from clean onto noised. The
clean rows feed keys and values only: the final norm and the head run on the
noised half, and the logits are ``[B, T, V]``. The loss (train/steps.py,
read off the batch's weights) is ``(1 / T) sum_b (1 / t_b) sum_{i in b,
masked} -log softmax(logits_i)[x_0,i]``.

Departures from the source, each the configuration's file's ``assumed``:
the block length, the schedule and the weight are the batch's and not this
file's; the head-wise query/key norm is taken from ``qwen3_moe``, whose keys
``sdar_moe`` has; every kernel and the router start normal(0, 0.02), the
embedding's rows normal(0, 1), and the mask's row is read through a fixed
multiplier ``MASK_ROW`` of a hundredth. A quarter of the rows are the mask's
one id, and an untrained router tells rows apart by their residual stream
alone. With every row at 0.02 that stream is what attention writes, an
average over many keys that differs little from row to row, and most rows
choose alike; with unit rows a token's own id decides (models/nemotron_h.py's
lesson), and the mask's, which says nothing of the token it hides, would send
every masked row to the same 8 experts in every layer. Read at a hundredth, a
masked row's stream is its context's. The multiplier and not a small row: a
row of size 0.01 under the first norm takes its gradient times 100 from 4,096
rows a step and is then most of the whole tree's gradient norm, one sum that
no averaging steadies; through the multiplier its gradient is a row's like
any other's.

**A chip's share** (models/blocks.py's ``Share``): ``tensor_parallel``
chips divide the query heads, each holding the key/value heads its query
heads read (one held by several chips where there are more chips than
key/value heads), and the vocabulary (``num_classes`` is what is held);
``expert_parallel`` chips divide the routed experts and ``expert_rank`` says
which are here. The router keeps its width and its ``top_k`` and normalises
over all the chosen; nothing stands in for the absent chips or their
exchange, and the partial sums go on.

Every layer is a ``jax.checkpoint`` that keeps ``SAVED`` beside its input
(ops/remat.py), by bytes at 16,384 rows: the router's float32 logits (8.4
MB), the experts chosen and their sorted order (0.5 MB each), q, k and v as
the kernel takes them, normed and rotated (25 MB): 34 MB a layer.

Prunable: ``q_proj``, ``k_proj``, ``v_proj``, ``o_proj``, the head, and the
experts' three stacked kernels ``kernel_gate`` / ``kernel_up`` /
``kernel_down`` ``[experts, in, out]``, each expert's each kernel a layer of
its own (ops/masking.py). Not prunable: the ``embedding``, the router's
float32 ``weight`` and the norms. The router and the norm before it are
float32 whatever the compute dtype.

Named scopes: ``attn/qkv``, ``attn/qk_norm``, ``attn/rope``, ``attn/flash``,
``attn/out_proj``, ``moe/router``, ``moe/dispatch``, ``moe/experts``,
``moe/combine``, ``lm_head``. Counters sown into ``counters`` (the train step
sums them over the layers): ops/moe.py's ``COUNTERS``, ``moe_rounds`` (the
rounds the pair buffer took, one a layer unless pairs outgrew it),
``masked_targets`` (the rows whose noised id is not the clean one) and
``flash_steps_run`` / ``flash_steps_walked`` (the pairs of blocks a head's
attention kernel ran and the grid steps it walked for them, a layer); where a
caller makes ``intermediates`` mutable, each layer's MoE input and choice.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..data.tokens import BLK, CLEAN, DOC, NOISED, POS
from ..ops import moe, remat
from ..ops.flash import blockdiff_walk_counts, flash_attention_blockdiff
from .blocks import FLASH_BLOCK, GatedExperts, Head, RMSNorm, RotaryAttention, Share, SparseMoE

MASK_ROW = 0.01  # what the mask's embedding row is multiplied by as it is read
# What a layer's attention sows: the (query block, key block) pairs a head's
# kernel ran, and the grid steps it walked for them (ops/flash.py).
FLASH_COUNTERS = ("flash_steps_run", "flash_steps_walked")


# What the backward pass of a layer keeps beside the layer's input.
SAVED = ("router_logits", "router_top", "moe_order", "attn_q", "attn_k", "attn_v")


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """The published keys the model reads, under their published names."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rms_norm_eps: float
    rope_theta: float
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    num_hidden_layers: int

    # What ``Share.of`` divides (models/blocks.py), by field.
    DIVIDED = {"query_heads": "num_attention_heads"}
    KV_HEADS, EXPERTS = "num_key_value_heads", "num_experts"


def held(c: SdarConfig, share: Share) -> dict:
    """What this chip holds of each layer."""
    return share.of(c)


def block_diffusion(doc, blk, pos):
    """``RotaryAttention``'s rule for the two copies of a sequence, the clean
    rows then the noised: ``doc``, ``blk``, ``pos`` [B, T] are the same for
    both, and the mask is ops/flash.py's third part."""

    def kernel(q, k, v):
        block = math.gcd(doc.shape[1], FLASH_BLOCK)
        scale = 1.0 / math.sqrt(q.shape[2])
        out = flash_attention_blockdiff(q, k, v, doc, blk, scale, block, block)
        return out, dict(zip(FLASH_COUNTERS, blockdiff_walk_counts(doc, blk, block, block)))

    return lambda: jnp.concatenate([pos, pos], axis=1), kernel


class SoftmaxRouter(nn.Module):
    """Float32 whatever the compute dtype. ``weight`` is a matrix and not a
    ``kernel``: it is never masked."""

    experts: int
    top_k: int

    @nn.compact
    def __call__(self, h32):
        weight = self.param("weight", nn.initializers.normal(0.02), (h32.shape[-1], self.experts))
        logits = jnp.einsum("nd,de->ne", h32, weight, precision=jax.lax.Precision.HIGHEST)
        return moe.route_softmax(checkpoint_name(logits, "router_logits"), self.top_k)


class SdarBlock(nn.Module):
    cfg: SdarConfig
    share: Share
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, doc, blk, pos):
        c, here = self.cfg, self.share.of(self.cfg)
        u = RMSNorm(c.rms_norm_eps, self.dtype, name="input_norm")(x)
        h = x + RotaryAttention(
            here["query_heads"], here["kv_heads"], c.head_dim, c.rms_norm_eps, c.rope_theta,
            self.dtype, name="attn",
        )(u, *block_diffusion(doc, blk, pos))  # fmt: skip
        self.sow("intermediates", "moe_in", h)
        u = RMSNorm(c.rms_norm_eps, jnp.float32, name="post_attention_norm")(h)
        return h + SparseMoE(
            SoftmaxRouter(c.num_experts, c.num_experts_per_tok, parent=None),
            GatedExperts(
                c.hidden_size, c.moe_intermediate_size, c.num_experts, c.num_experts_per_tok,
                here["experts_here"], here["expert_offset"], self.dtype, parent=None,
            ),
            name="mlp",
        )(u)  # fmt: skip


class Sdar(nn.Module):
    vocab_size: int  # as held; its last id is the mask's
    cfg: SdarConfig
    layers: int
    share: Share = Share()
    dtype: Any = jnp.float32

    # What its layers and itself sow into ``counters`` (train/steps.py).
    counters = (*moe.COUNTERS, "moe_rounds", "masked_targets", *FLASH_COUNTERS)

    @nn.compact
    def __call__(self, tokens, train: bool = False, reduce=None):
        """The logits ``[B, T, V]``; or, given ``reduce``, what it makes of
        them a block of tokens at a time (models/blocks.py's ``Head``)."""
        del train  # no dropout, no batch statistics
        c = self.cfg
        clean, noised = tokens[:, CLEAN], tokens[:, NOISED]
        doc, blk, pos = tokens[:, DOC], tokens[:, BLK], tokens[:, POS]
        self.sow("counters", "masked_targets", jnp.sum(noised != clean, dtype=jnp.int32))
        table = self.param(
            "embedding", nn.initializers.normal(1.0), (self.vocab_size, c.hidden_size)
        )
        ids = jnp.concatenate([clean, noised], axis=1)
        x = table[ids]
        x = jnp.where((ids == self.vocab_size - 1)[..., None], MASK_ROW * x, x).astype(self.dtype)
        block = nn.remat(SdarBlock, policy=remat.keeping(SAVED))
        for i in range(self.layers):
            x = block(c, self.share, self.dtype, name=f"layers_{i}")(x, doc, blk, pos)
        # The clean rows have fed keys and values; the loss reads the noised.
        x = RMSNorm(c.rms_norm_eps, self.dtype, name="final_norm")(x[:, clean.shape[1] :])
        with jax.named_scope("lm_head"):
            return Head(self.vocab_size, self.dtype, name="lm_head")(x, reduce)


# SDAR-30B-A3B-Chat as published (huggingface.co/JetLM/SDAR-30B-A3B-Chat,
# config.json).
SDAR_30B_A3B = dict(
    hidden_size=2048,
    num_attention_heads=32,
    num_key_value_heads=4,
    head_dim=128,
    rms_norm_eps=1e-6,
    rope_theta=1_000_000.0,
    num_experts=128,
    num_experts_per_tok=8,
    moe_intermediate_size=768,
    num_hidden_layers=48,
)
# The same block at a test's size: two layers, two key/value heads, sixteen
# experts of which a token picks four.
SDAR_MOE_TINY = dict(
    SDAR_30B_A3B,
    hidden_size=32,
    num_attention_heads=4,
    num_key_value_heads=2,
    head_dim=8,
    num_experts=16,
    num_experts_per_tok=4,
    moe_intermediate_size=24,
    num_hidden_layers=2,
)


def _build(published: dict, num_classes, num_layers, dtype, layer_pattern, share) -> Sdar:
    if layer_pattern:
        raise ValueError(f"every layer of this model is alike: no layer_pattern ({layer_pattern!r})")
    cfg = SdarConfig(**published)
    return Sdar(num_classes, cfg, num_layers or cfg.num_hidden_layers, Share(*share), dtype)


def sdar_30b_a3b(
    num_classes: int, *, num_layers: int = 0, dtype=jnp.float32, layer_pattern: str = "",
    share: tuple = (),
) -> Sdar:  # fmt: skip
    """``num_classes`` is the vocabulary held (151,936 published), the mask's
    id its last; ``num_layers`` 0 means the published 48; ``share``
    (tensor_parallel, expert_parallel, expert_rank)."""
    return _build(SDAR_30B_A3B, num_classes, num_layers, dtype, layer_pattern, share)


def sdar_moe_tiny(
    num_classes: int, *, num_layers: int = 0, dtype=jnp.float32, layer_pattern: str = "",
    share: tuple = (),
) -> Sdar:  # fmt: skip
    """Two layers unless told otherwise."""
    return _build(SDAR_MOE_TINY, num_classes, num_layers, dtype, layer_pattern, share)
