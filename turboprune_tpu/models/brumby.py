"""The ``brumby`` decoder (Brumby-14B-Base's ``config.json``; Manifest AI,
2025-10): the Qwen3-14B block with power retention (Gelada, Buckman, Zhang &
Bach, arXiv:2507.04239; ops/retention.py) where attention stood, in every
layer.

    h = x + Ret(rmsnorm(x));   y = h + SwiGLU(rmsnorm(h))        eps 1e-6, 40 alike
    logits = rmsnorm(y_last) W_head                                    untied head
    Ret, for n = rmsnorm(x):
        q = W_q n [40 heads x 128];  k = W_k n, v = W_v n [8 heads x 128]; no bias
        q, k <- a learned RMS norm of each head (128 weights each, eps 1e-6),
                then rotary over the whole head, theta 1,000,000, halves rotated,
                the position a token's index inside its packed document
        lam_t = log sigmoid(w_g . n_t + b_g)     float32, one a key/value head
                                                 and token: its 5 query heads share it
        a_ts  = exp(sum_{s < r <= t} lam_r) * (q_t . k_s / sqrt(128))^2
                                                 s <= t in t's document, else 0
        o_t   = sum_s a_ts v_s / (sum_s a_ts + 1e-16)
        Ret   = W_o concat_heads(o)
    SwiGLU:  W_2 (silu(W_1 n) * W_3 n)                    5,120 -> 17,408 -> 5,120

The input and the packing are models/granite.py's (``tokens [B, 2, T]``, ids
and document ids; nothing crosses a document's start: not the retention's
state, not a position). What this file shares is models/blocks.py's:
``RMSNorm``, ``dense``, ``rotary``, ``positions``, ``SwiGLU``, ``Head`` and
``Share``.

What the published config does not carry is the configuration's file's
``assumed``: the degree 2, the gate's shape (a linear map of the block's
normed input with a bias, through ``log sigmoid``, one scalar a key/value
head), the state shared by a key/value head's query heads, the scores' scale
before the square, ``eps_r`` 1e-16 (a guard of 0 / 0: ops/retention.py
says why not 1e-6), the head norms and the rotation kept under
retention, no sink and no output gate, normal(0, 0.02) everywhere and norm
weights 1, and the gate's bias: the inverse sigmoid of a retention a token
whose horizon ``1 / (1 - sigmoid(b))`` is log-uniform over [64, 16,384]
tokens (``_horizon_bias_init``, drawn as blocks.py's ``_dt_bias_init`` draws
Mamba's). With ``w_g`` at 0.02 and no bias every decay is a half a token and
a chunk's carried state arrives multiplied by 2^-512: a carry that is wrong
would change nothing.

**A chip's share** (``Share.of``, which divides what ``BrumbyConfig.DIVIDED``
lists): ``tensor_parallel`` chips divide the query heads, each holding the
key/value heads its query heads read, the MLP's columns and the vocabulary
(``num_classes`` is what is held). The gate's map and the norms are whole on
every chip (a gate a key/value head held). A dense model: it names no
experts and ``expert_parallel`` stays 1. Nothing stands in for the absent
chips or their all-reduce, and the partial sums go on.

Every layer is a ``jax.checkpoint`` that keeps ``SAVED`` beside its input
(ops/remat.py), by bytes at 32,768 tokens and 5 + 1 heads: q, k and v as the
kernel takes them, normed and rotated (58.7 MB), and ``lam`` (0.13 MB). The
kernel's own residuals (the states entering the chunks) are rebuilt with the
layer.

Prunable: ``q_proj``, ``k_proj``, ``v_proj``, ``o_proj``, the MLP's
``in_proj`` and ``out_proj``, the head. Not prunable: the ``embedding``, the
norms, and the gate's ``gate_weight`` and ``gate_bias``, which are no
kernels (as the routers' weights).

Named scopes: ``retention/qkv``, ``retention/qk_norm``, ``retention/rope``,
``retention/gate``, ``retention/scan`` (the kernels), ``retention/out_proj``,
``mlp``, ``lm_head``. Gauges (utils/tracing.py): ops/retention.py's two, and
``carried_chunks_per_step``, the chunks a traced call of the model walks (a
sequence's tokens over the chunk, times the batch, the layers and the
key/value heads held).
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
from ..ops.retention import EPS as RETENTION_EPS, power_retention  # the normaliser's guard, by its name here
from ..utils import tracing
from .blocks import Head, RMSNorm, Share, SwiGLU, dense, positions, rotary

# What the backward pass of a layer keeps beside the layer's input.
SAVED = ("ret_q", "ret_k", "ret_v", "ret_lam")
DEGREE = 2  # of the power: ops/retention.py computes this one
CHUNK = 512  # tokens a chunk of the walk (ops/retention.py says why)
HORIZON = (64.0, 16384.0)  # tokens: what a gate's bias starts at, log-uniform


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    """The published keys the model reads, under their published names."""

    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rms_norm_eps: float
    rope_theta: float
    num_hidden_layers: int
    retention_chunk: int = CHUNK  # not published: the walk's, smaller at a test's size

    # What ``Share.of`` divides (models/blocks.py), by field; no experts.
    DIVIDED = {"query_heads": "num_attention_heads", "dense_columns": "intermediate_size"}
    KV_HEADS, EXPERTS = "num_key_value_heads", None


def held(c: BrumbyConfig, share: Share) -> dict:
    """What this chip holds of each layer."""
    return share.of(c)


def _horizon_bias_init(key, shape, dtype=jnp.float32):
    """The inverse sigmoid of ``1 - 1 / h``, ``h`` tokens drawn log-uniformly
    from ``HORIZON``."""
    lo, hi = (math.log(h) for h in HORIZON)
    return jnp.log(jnp.expm1(jax.random.uniform(key, shape, dtype, lo, hi)))


class RetentionMixer(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float
    chunk: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u, seg):
        bsz, t, dim = u.shape
        d, group = self.head_dim, self.heads // self.kv_heads
        with jax.named_scope("retention/qkv"):
            q = dense(self.heads * d, self.dtype, "q_proj")(u).reshape(bsz, t, self.heads, d)
            k = dense(self.kv_heads * d, self.dtype, "k_proj")(u).reshape(bsz, t, self.kv_heads, d)
            v = dense(self.kv_heads * d, self.dtype, "v_proj")(u).reshape(bsz, t, self.kv_heads, d)
        with jax.named_scope("retention/qk_norm"):
            q = RMSNorm(self.eps, self.dtype, name="q_norm")(q)
            k = RMSNorm(self.eps, self.dtype, name="k_norm")(k)
        with jax.named_scope("retention/rope"):
            pos = positions(seg)
            q, k = rotary(q, pos, self.theta), rotary(k, pos, self.theta)
            # By head, a key/value head's query heads together: [B, H, (G,) T, d].
            q = q.reshape(bsz, t, self.kv_heads, group, d).transpose(0, 2, 3, 1, 4)
            k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            q, k, v = (checkpoint_name(x, f"ret_{n}") for x, n in ((q, "q"), (k, "k"), (v, "v")))
        with jax.named_scope("retention/gate"):
            weight = self.param("gate_weight", nn.initializers.normal(0.02), (dim, self.kv_heads))
            bias = self.param("gate_bias", _horizon_bias_init, (self.kv_heads,))
            gate = jnp.einsum(
                "btd,dh->bth", u.astype(jnp.float32), weight, precision=jax.lax.Precision.HIGHEST
            )
            lam = checkpoint_name(jax.nn.log_sigmoid(gate + bias), "ret_lam")  # [B, T, H] float32
        with jax.named_scope("retention/scan"):
            out = power_retention(q, k, v, lam, seg, chunk=self.chunk)
        with jax.named_scope("retention/out_proj"):
            out = out.transpose(0, 3, 1, 2, 4).reshape(bsz, t, self.heads * d)
            return dense(dim, self.dtype, "o_proj")(out)


class BrumbyBlock(nn.Module):
    cfg: BrumbyConfig
    share: Share
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, seg):
        c, here = self.cfg, self.share.of(self.cfg)
        u = RMSNorm(c.rms_norm_eps, self.dtype, name="input_norm")(x)
        h = x + RetentionMixer(
            here["query_heads"], here["kv_heads"], c.head_dim, c.rms_norm_eps, c.rope_theta,
            c.retention_chunk, self.dtype, name="retention",
        )(u, seg)  # fmt: skip
        u = RMSNorm(c.rms_norm_eps, self.dtype, name="post_attention_norm")(h)
        return h + SwiGLU(here["dense_columns"], self.dtype, name="mlp")(u)


class Brumby(nn.Module):
    vocab_size: int  # as held
    cfg: BrumbyConfig
    layers: int
    share: Share = Share()
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = False, reduce=None):
        """The logits ``[B, T, V]``; or, given ``reduce``, what it makes of
        them a block of tokens at a time (models/blocks.py's ``Head``)."""
        del train  # no dropout, no batch statistics
        c = self.cfg
        ids, seg = tokens[:, 0], tokens[:, 1]
        chunks = -(-ids.shape[1] // c.retention_chunk) * ids.shape[0]
        tracing.gauge(
            "carried_chunks_per_step", chunks * self.layers * self.share.of(c)["kv_heads"]
        )
        table = self.param(
            "embedding", nn.initializers.normal(0.02), (self.vocab_size, c.hidden_size)
        )
        x = table[ids].astype(self.dtype)
        block = nn.remat(BrumbyBlock, policy=remat.keeping(SAVED))
        for i in range(self.layers):
            x = block(c, self.share, self.dtype, name=f"layers_{i}")(x, seg)
        x = RMSNorm(c.rms_norm_eps, self.dtype, name="final_norm")(x)
        with jax.named_scope("lm_head"):
            return Head(self.vocab_size, self.dtype, name="lm_head")(x, reduce)


# Brumby-14B-Base as published (huggingface.co/manifestai/Brumby-14B-Base,
# config.json): Qwen3-14B's sizes.
BRUMBY_14B_BASE = dict(
    hidden_size=5120,
    intermediate_size=17408,
    num_attention_heads=40,
    num_key_value_heads=8,
    head_dim=128,
    rms_norm_eps=1e-6,
    rope_theta=1_000_000.0,
    num_hidden_layers=40,
)
# The same block at a test's size: two layers, two key/value heads of two
# query heads each, chunks of 16 tokens.
BRUMBY_TINY = dict(
    BRUMBY_14B_BASE,
    hidden_size=32,
    intermediate_size=48,
    num_attention_heads=4,
    num_key_value_heads=2,
    head_dim=8,
    num_hidden_layers=2,
    retention_chunk=16,
)


def _build(published: dict, num_classes, num_layers, dtype, layer_pattern, share) -> Brumby:
    if layer_pattern:
        raise ValueError(f"every layer of this model is alike: no layer_pattern ({layer_pattern!r})")
    cfg = BrumbyConfig(**published)
    if not 0 <= num_layers <= cfg.num_hidden_layers:
        raise ValueError(f"num_layers {num_layers} of {cfg.num_hidden_layers} published")
    return Brumby(num_classes, cfg, num_layers or cfg.num_hidden_layers, Share(*share), dtype)


def brumby_14b_base(
    num_classes: int, *, num_layers: int = 0, dtype=jnp.float32, layer_pattern: str = "",
    share: tuple = (),
) -> Brumby:  # fmt: skip
    """``num_classes`` is the vocabulary held (151,936 published);
    ``num_layers`` 0 means the published 40; ``share`` (tensor_parallel, 1, 0)."""
    return _build(BRUMBY_14B_BASE, num_classes, num_layers, dtype, layer_pattern, share)


def brumby_tiny(
    num_classes: int, *, num_layers: int = 0, dtype=jnp.float32, layer_pattern: str = "",
    share: tuple = (),
) -> Brumby:  # fmt: skip
    """Two layers unless told otherwise."""
    return _build(BRUMBY_TINY, num_classes, num_layers, dtype, layer_pattern, share)
