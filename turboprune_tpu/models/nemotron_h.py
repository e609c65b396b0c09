"""The ``nemotron_h`` decoder (NVIDIA-Nemotron-3-Super-120B-A12B's
``config.json``): every layer is ONE mixer, ``x + mixer(rmsnorm(x))``, its
kind read from a pattern string: ``M`` a Mamba-2 mixer with grouped ``B`` and
``C``, ``*`` causal grouped-query attention with no positional encoding, ``E``
a LatentMoE: sparse routed experts in a latent space beside one shared expert.

    x0 = E[ids];  x <- x + mixer_l(rmsnorm_l(x));  logits = rmsnorm(x) W_head
    E:  s = sigmoid(W_r h) over all routed experts, float32
        top = the top_k largest of s + b;  w_i = scaling * s_i / (sum_top s + 1e-20)
        z = W_down h;  f_e(z) = W2_e relu(W1_e z)^2
        out = W_up (sum_{i in top} w_i f_i(z)) + V2 relu(V1 h)^2

The input and the packing are models/granite.py's (``tokens [B, 2, T]``, ids
and document ids; nothing crosses a document's start). What this file shares
is models/blocks.py's: ``RMSNorm``, ``MambaMixer`` (here with ``n_groups``
groups), ``AttentionMixer``, ``dense``, the sigmoid ``Router``, the untied
``Head`` and ``Share``. Every layer is a
``jax.checkpoint``: a backward pass keeps the layer's input, rebuilds the
layer's inside (the two grouped products of the experts and the kernels of
the scan and of attention among it) and keeps ``SAVED``, values tagged where
they are made (ops/remat.py) that are narrow and dear to rebuild. By bytes at
8,192 tokens: an ``E`` layer's router logits (float32, 16.8 MB, for a product
at ``Precision.HIGHEST``), its chosen experts and their sorted order (0.7 MB
each, for a ``top_k`` and a sort), ``latent_down``'s and ``shared_up``'s
outputs (16.8 and 11 MB); an ``M`` layer's ``in_proj`` output (38 MB); a
``*`` layer's q, k and v (12.6 MB): 0.43 GB over one period.

**A chip's share.** The model is built as one chip of a deployment holds it
(``Share.of``, which divides what ``NemotronHConfig.DIVIDED`` lists):
``tensor_parallel`` chips divide every mixer's heads (and with
them Mamba's groups), the shared expert's columns and nothing else;
``expert_parallel`` chips divide the routed experts, and ``expert_rank`` says
which of them are here. The router keeps its width and its ``top_k`` and
normalises over all the chosen; the chip computes the pairs whose expert it
holds (ops/moe.py) and its own heads' and columns' part of every other sum,
and that partial result goes on to the next layer. Nothing stands in for the
absent chips or their exchange. The default share is the whole model.

Prunable (ops/masking.py::is_prunable_path): every projection ``kernel``, the
head's among them, and the experts' stacked ``kernel_up`` / ``kernel_down``
``[experts, in, out]``. Not prunable: the ``embedding``, the router's
``weight`` (a float32 matrix that is not a kernel) and its selection ``bias``
(zero, and no gradient reaches it), the convolution, ``A_log``, ``D``,
``dt_bias`` and the norms.

The router and the norm before it are float32 whatever the compute dtype: a
flipped 22nd place moves the output by a whole expert.

Named scopes: ``moe/router``, ``moe/latent_down``, ``moe/dispatch``,
``moe/experts``, ``moe/combine``, ``moe/latent_up``, ``moe/shared`` beside
blocks.py's ``mamba/*``, ``ssd``, ``attn/*``, and ``lm_head``. Each ``E``
layer sows ops/moe.py's ``COUNTERS`` into the ``counters`` collection (the
train step sums them into its metrics), and, where a caller makes
``intermediates`` mutable, the layer's input and the experts it chose.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import moe, remat
from .blocks import AttentionMixer, Head, MambaMixer, RMSNorm, Router, Share, dense


# What the backward pass of a layer keeps beside the layer's input.
SAVED = (
    "router_logits", "router_top", "moe_order", "moe_latent_down", "moe_shared_up",
    "mamba_in_proj", "attn_q", "attn_k", "attn_v",
)  # fmt: skip


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The published keys the model reads, under their published names."""

    hidden_size: int
    hybrid_override_pattern: str
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    layer_norm_epsilon: float
    n_routed_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    moe_latent_size: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    num_hidden_layers: int

    # What ``Share.of`` divides (models/blocks.py), by field.
    DIVIDED = {
        "mamba_heads": "mamba_num_heads", "mamba_groups": "n_groups",
        "query_heads": "num_attention_heads",
        "shared_columns": "moe_shared_expert_intermediate_size",
    }  # fmt: skip
    KV_HEADS, EXPERTS = "num_key_value_heads", "n_routed_experts"


class Experts(nn.Module):
    """The routed experts held here, as two stacked kernels
    ``[experts, in, out]`` (layers ``kernel_up[e]`` and ``kernel_down[e]`` to
    everything that prunes per layer)."""

    cfg: NemotronHConfig
    experts_here: int
    expert_offset: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, z, top, weights):
        c = self.cfg
        init = nn.initializers.normal(0.02)
        up = (self.experts_here, c.moe_latent_size, c.moe_intermediate_size)
        kernel_up = self.param("kernel_up", init, up)
        kernel_down = self.param("kernel_down", init, (up[0], up[2], up[1]))
        routing = (z.shape[0], c.num_experts_per_tok, c.n_routed_experts)
        return moe.routed_experts(
            z, top, weights, (kernel_up.astype(self.dtype), kernel_down.astype(self.dtype)),
            self.expert_offset, moe.pair_capacity(*routing, self.experts_here),
            moe.pair_tile(*routing),
        )  # fmt: skip


class LatentMoE(nn.Module):
    cfg: NemotronHConfig
    experts_here: int
    expert_offset: int
    shared_columns: int
    dtype: Any = jnp.float32
    out_std: float = 0.02  # of the two projections that write to the residual stream

    @nn.compact
    def __call__(self, h32):
        """``h32`` [B, T, D]: the layer's normed input, float32."""
        c = self.cfg
        bsz, t, dim = h32.shape
        h = h32.astype(self.dtype)
        with jax.named_scope("moe/router"):
            top, weights = Router(
                c.n_routed_experts, c.num_experts_per_tok, c.routed_scaling_factor, name="router"
            )(h32.reshape(bsz * t, dim))
        self.sow("intermediates", "top", top)
        with jax.named_scope("moe/latent_down"):
            z = checkpoint_name(dense(c.moe_latent_size, self.dtype, "latent_down")(h), "moe_latent_down")
            z = z.reshape(bsz * t, -1)
        mixed, counters = Experts(
            c, self.experts_here, self.expert_offset, self.dtype, name="experts"
        )(z, top, weights)
        for name, value in counters.items():
            self.sow("counters", name, value)
        with jax.named_scope("moe/latent_up"):
            mixed = mixed.astype(self.dtype).reshape(bsz, t, -1)
            routed = dense(dim, self.dtype, "latent_up", self.out_std)(mixed)
        with jax.named_scope("moe/shared"):
            up = checkpoint_name(dense(self.shared_columns, self.dtype, "shared_up")(h), "moe_shared_up")
            shared = dense(dim, self.dtype, "shared_down", self.out_std)(jnp.square(nn.relu(up)))
        return routed + shared


class NemotronBlock(nn.Module):
    kind: str  # "M" | "*" | "E"
    cfg: NemotronHConfig
    share: Share
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, seg):
        c, held = self.cfg, self.share.of(self.cfg)
        # ``rescale_prenorm_residual``, over the published depth: what a
        # mixer writes to the residual stream starts small.
        out_std = 0.02 / math.sqrt(c.num_hidden_layers)
        if self.kind == "E":
            self.sow("intermediates", "layer_in", x)
            h = RMSNorm(c.layer_norm_epsilon, jnp.float32, name="norm")(x)
            y = LatentMoE(
                c, held["experts_here"], held["expert_offset"], held["shared_columns"],
                self.dtype, out_std, name="mixer",
            )(h)  # fmt: skip
            return x + y
        h = RMSNorm(c.layer_norm_epsilon, self.dtype, name="norm")(x)
        if self.kind == "*":
            y = AttentionMixer(
                held["query_heads"], held["kv_heads"], c.head_dim, 1.0 / math.sqrt(c.head_dim),
                self.dtype, name="mixer",
            )(h, seg)  # fmt: skip
        elif self.kind == "M":
            y = MambaMixer(
                held["mamba_heads"], c.mamba_head_dim, c.ssm_state_size, c.conv_kernel,
                c.chunk_size, c.layer_norm_epsilon, self.dtype, held["mamba_groups"],
                out_std=out_std, name="mixer",
            )(h, seg)  # fmt: skip
        else:
            raise ValueError(f"no layer kind {self.kind!r} (M, * or E)")
        return x + y


class NemotronH(nn.Module):
    vocab_size: int
    cfg: NemotronHConfig
    pattern: str  # the layers run, a stretch of ``hybrid_override_pattern``
    share: Share = Share()
    dtype: Any = jnp.float32

    # What its ``E`` layers sow into ``counters`` (train/steps.py).
    counters = moe.COUNTERS

    @nn.compact
    def __call__(self, tokens, train: bool = False, reduce=None):
        """The logits ``[B, T, V]``; or, given ``reduce``, what it makes of
        them a block of tokens at a time (models/blocks.py's ``Head``)."""
        del train  # no dropout, no batch statistics
        c = self.cfg
        ids, seg = tokens[:, 0], tokens[:, 1]
        # Unit rows: with them the untrained residual stream is the token's
        # own, and the routers see tokens apart (at 0.02 it is the layers'
        # common output, every token routes alike and SGD's first steps on
        # the first norm are fifty times too long).
        table = self.param(
            "embedding", nn.initializers.normal(1.0), (self.vocab_size, c.hidden_size)
        )
        x = table[ids].astype(self.dtype)
        block = nn.remat(NemotronBlock, policy=remat.keeping(SAVED))
        for i, kind in enumerate(self.pattern):
            x = block(kind, c, self.share, self.dtype, name=f"layers_{i}")(x, seg)
        x = RMSNorm(c.layer_norm_epsilon, self.dtype, name="final_norm")(x)
        with jax.named_scope("lm_head"):
            return Head(self.vocab_size, self.dtype, name="lm_head")(x, reduce)


# NVIDIA-Nemotron-3-Super-120B-A12B-BF16 as published (huggingface.co/nvidia/
# NVIDIA-Nemotron-3-Super-120B-A12B-BF16, config.json).
NEMOTRON_3_SUPER = dict(
    hidden_size=4096,
    hybrid_override_pattern=(
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
    ),
    num_attention_heads=32,
    num_key_value_heads=2,
    head_dim=128,
    mamba_num_heads=128,
    mamba_head_dim=64,
    n_groups=8,
    ssm_state_size=128,
    conv_kernel=4,
    chunk_size=128,
    layer_norm_epsilon=1e-5,
    n_routed_experts=512,
    num_experts_per_tok=22,
    routed_scaling_factor=5.0,
    moe_latent_size=1024,
    moe_intermediate_size=2688,
    moe_shared_expert_intermediate_size=5376,
    num_hidden_layers=88,
)
# The same blocks at a test's size: one layer of each kind, two scan groups,
# two key/value heads, sixteen experts of which a token picks four.
NEMOTRON_H_TINY = dict(
    NEMOTRON_3_SUPER,
    hidden_size=64,
    hybrid_override_pattern="EM*",
    num_attention_heads=4,
    num_key_value_heads=2,
    head_dim=8,
    mamba_num_heads=4,
    mamba_head_dim=16,
    n_groups=2,
    ssm_state_size=8,
    chunk_size=16,
    n_routed_experts=16,
    num_experts_per_tok=4,
    moe_latent_size=32,
    moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96,
    num_hidden_layers=3,
)


def _build(published: dict, num_classes, num_layers, dtype, layer_pattern, share) -> NemotronH:
    cfg = NemotronHConfig(**published)
    pattern = layer_pattern or cfg.hybrid_override_pattern
    return NemotronH(num_classes, cfg, pattern[: num_layers or None], Share(*share), dtype)


def nemotron_3_super_120b_a12b(
    num_classes: int, *, num_layers: int = 0, dtype=jnp.float32, layer_pattern: str = "",
    share: tuple = (),
) -> NemotronH:  # fmt: skip
    """``num_classes`` is the vocabulary held (131,072 published);
    ``layer_pattern`` the stretch of the published pattern that is run ("" =
    all 88 layers) and ``num_layers`` its first so many (0 = all of it);
    ``share`` (tensor_parallel, expert_parallel, expert_rank)."""
    return _build(NEMOTRON_3_SUPER, num_classes, num_layers, dtype, layer_pattern, share)


def nemotron_h_tiny(
    num_classes: int, *, num_layers: int = 0, dtype=jnp.float32, layer_pattern: str = "",
    share: tuple = (),
) -> NemotronH:  # fmt: skip
    """``EM*``: one layer of each kind, unless told otherwise."""
    return _build(NEMOTRON_H_TINY, num_classes, num_layers, dtype, layer_pattern, share)
