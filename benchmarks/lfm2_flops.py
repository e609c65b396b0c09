"""Operations and bytes of the ``lfm2_moe`` decoder as one chip holds it (the
configuration ``lfm2-8b-a1b``), counted from shapes, from the packing layout
and from the step's own counter.

As ``granite_flops.py`` (whose attention counts, layout helpers and roofline
this imports) they count **the mathematics, whatever implements it**: a
multiply and an add for every term of every product of the layer equations
(top of ``benchmarks/reference/lfm2_moe.py``), three forward passes for a
training step, nothing the program recomputes or masks away.

- A projection (every 2-D ``kernel`` of the tree, the router's ``weight`` and
  the tied head over the vocabulary held): 2 x parameters a token.
- A routed expert: a (token, expert) pair whose expert is held here is the
  three products ``2 (D F + D F + F D) = 6 D F`` operations (22.0 MFLOP at
  2,048 x 1,792). How many pairs a step has is the routing's: the step's own
  counter ``moe_pairs`` (ops/moe.py).
- The gated short convolution, a token and a channel held: the first gate's
  product, ``K`` taps of a multiply and an add each, the second gate's
  product: ``2 K + 2`` operations (8 at three taps). The mixer (``shortconv_*``)
  is these and its two projections.
- Attention: each document's own triangle of query-key pairs, ``4 d`` a pair
  and query head.

Bytes are one read of each operand and one write of the result in the compute
dtype (bf16). The short-convolution mixer, a pass: its input and its output
(``D`` values a token each) and its two kernels; ``B``, ``C``, ``u`` and what
the gates and taps make of them are no operand and no result of the mixer, and
a program that keeps them on the chip moves none of them (XLA's does for most:
its fusions compute the taps and the second gate inside ``out_proj``'s
product); three passes, as the granite cell counts its scan (the program runs
the forward twice, so the share this gives reads low, never high). Attention
as ``granite_flops.py``, three passes. The experts' three grouped products, a
pass: one read of the held experts' kernels and each pair's rows in and out
(``D + F`` twice and ``F + D``); the layer is a ``jax.checkpoint``, so the
products run the forward twice and their roofline (``lfm2_experts_*``) counts
four passes, as the other two routed cells count their own; ``step_flops`` the
mathematics' three.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.granite_flops import (  # noqa: F401  (roofline_seconds for the metrics)
    COMPUTE_BYTES,
    TRAIN_PASSES,
    attention_forward_bytes,
    attention_forward_flops,
    causal_pairs,
    document_lengths,
    roofline_seconds,
)

EXPERT_PASSES = TRAIN_PASSES + 1.0  # the rebuilt forward


def layers(params, key: str, part: str = "mixer") -> list[dict]:
    """The ``part`` subtrees of the layers whose ``part`` holds ``key``."""
    return [
        v[part] for k, v in sorted(params.items()) if k.startswith("layers_") and key in v[part]
    ]


def expert_pair_flops(hidden: int, width: int) -> float:
    return 6.0 * hidden * width


def gates_forward_flops(tokens: float, channels: int, taps: int) -> float:
    return tokens * channels * (2.0 * taps + 2.0)


def shortconv_forward_flops(tokens: float, hidden: int, channels: int, taps: int) -> float:
    """The mixer: ``W_in`` (D x 3 C), both gates and the taps, ``W_out`` (C x D)."""
    return 2.0 * tokens * 4.0 * hidden * channels + gates_forward_flops(tokens, channels, taps)


def shortconv_forward_bytes(tokens: float, hidden: int, channels: int) -> float:
    return COMPUTE_BYTES * (2.0 * tokens * hidden + 4.0 * hidden * channels)


def step_counts(params, spec: dict, segment_ids: np.ndarray, pairs_per_step: float) -> dict:
    """A mean training step's counts, for ``segment_ids`` [S, B, T] (S steps,
    averaged) and the mean of the step counter ``moe_pairs`` (all routed
    layers together): ``step_flops`` of the whole model, and operations and
    bytes of the short-convolution mixers, of attention and of the experts'
    grouped products."""
    seg = np.asarray(segment_ids)
    tokens = float(seg[0].size)
    pairs = sum(causal_pairs(l) for l in document_lengths(seg)) / seg.shape[0]
    hq, hkv, d = spec["num_attention_heads"], spec["num_key_value_heads"], spec["head_dim"]
    size = lambda leaf: float(math.prod(leaf.shape))

    convs, attns = layers(params, "conv_taps"), layers(params, "q_proj")
    dense, routed = layers(params, "in_proj", "mlp"), layers(params, "router", "mlp")
    per_token = (
        sum(size(m["in_proj"]["kernel"]) + size(m["out_proj"]["kernel"]) for m in convs + dense)
        + sum(size(m[f"{name}_proj"]["kernel"]) for m in attns for name in "qkvo")
        + sum(size(m["router"]["weight"]) for m in routed)
        + size(params["embedding"])  # the head
    )
    hidden = params["embedding"].shape[1]
    gate_flops = sum(gates_forward_flops(tokens, *m["conv_taps"].shape[::-1]) for m in convs)
    conv_flops = sum(
        shortconv_forward_flops(tokens, hidden, *m["conv_taps"].shape[::-1]) for m in convs
    )
    conv_bytes = sum(shortconv_forward_bytes(tokens, hidden, m["conv_taps"].shape[1]) for m in convs)
    attn_flops = len(attns) * attention_forward_flops(pairs, hq, d)
    attn_bytes = len(attns) * attention_forward_bytes(tokens, hq, hkv, d)
    expert_flops = expert_bytes = 0.0
    if routed:
        held, _, width = routed[0]["experts"]["kernel_gate"].shape
        expert_flops = pairs_per_step * expert_pair_flops(hidden, width)
        expert_bytes = COMPUTE_BYTES * (
            len(routed) * 3.0 * held * hidden * width + pairs_per_step * 3.0 * (hidden + width)
        )
    return {
        "step_flops": TRAIN_PASSES * (2.0 * tokens * per_token + gate_flops + attn_flops + expert_flops),
        "shortconv_flops": TRAIN_PASSES * conv_flops,
        "shortconv_bytes": TRAIN_PASSES * conv_bytes,
        "flash_causal_flops": TRAIN_PASSES * attn_flops,
        "flash_causal_bytes": TRAIN_PASSES * attn_bytes,
        "lfm2_experts_flops": EXPERT_PASSES * expert_flops,
        "lfm2_experts_bytes": EXPERT_PASSES * expert_bytes,
        "causal_pairs_per_step": pairs,
        "tokens_per_step": tokens,
        "moe_pairs_per_step": float(pairs_per_step),
    }
