"""Operations and bytes of the ``sdar_moe`` decoder under block-diffusion
training as one chip holds it (the configuration ``sdar-30b-a3b-chat``),
counted from shapes, from the packing layout and from the step's own counter.

As ``granite_flops.py`` (whose roofline this imports) they count **the
mathematics, whatever implements it**: a multiply and an add for every term
of every product of the layer equations (top of
``benchmarks/reference/sdar_moe.py``), three forward passes for a training
step, nothing the program recomputes or masks away.

- The layers see ``2 T`` rows a sequence, the clean copy and the noised copy;
  the head sees the noised ``T``.
- A projection (the four 2-D ``kernel``s of a layer's attention) and the
  router's ``weight``: 2 x parameters a row. The head: 2 x parameters a
  noised row.
- A routed expert: a (row, expert) pair whose expert is held here is the
  three products ``2 (D F + D F + F D) = 6 D F`` operations (9.44 MFLOP at
  2,048 x 768). How many pairs a step has is the routing's: the step's own
  counter ``moe_pairs`` (ops/moe.py).
- Attention: ``4 d`` a kept (query, key) pair and query head, the pairs
  counted from the layout by the block-diffusion rule (``kept_pairs``): a
  clean query keeps the clean keys of its document up to its block's end, a
  noised one the clean keys before its block and its block's noised keys.

Bytes are one read of each operand and one write of the result in the compute
dtype (bf16). Attention, a pass: q and the output (Hq d each) and k and v (Hkv
d each), a row of the 2 T. The experts' three grouped products, a pass: one
read of the held experts' kernels and each pair's rows in and out (``D + F``
twice and ``F + D``). The layer is a ``jax.checkpoint``, so the products run
the forward twice: their roofline (``swiglu_experts_*``) counts four passes,
as the sparse-expert cell counts its own; attention's (``flash_blockdiff_*``)
three, as the granite cell counts its own; ``step_flops`` the mathematics'
three.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.granite_flops import COMPUTE_BYTES, TRAIN_PASSES, roofline_seconds  # noqa: F401

EXPERT_PASSES = TRAIN_PASSES + 1.0  # the rebuilt forward


def layers(params) -> list[dict]:
    return [v for k, v in sorted(params.items()) if k.startswith("layers_")]


def kept_pairs(segment_ids: np.ndarray, block_length: int) -> float:
    """The (query, key) pairs the rule keeps over both copies of every
    sequence of ``segment_ids`` [..., T], a document at a time: with its L
    tokens in blocks of ``block_length`` (the last short), a clean query of
    block b keeps the end(b) tokens up to its block's end, a noised one the
    start(b) before its block and the len(b) of its own: end(b) both."""
    total = 0
    for row in np.asarray(segment_ids).reshape(-1, np.shape(segment_ids)[-1]):
        for length in np.bincount(row - row.min()):
            ends = np.minimum((np.arange(length) // block_length + 1) * block_length, length)
            total += 2 * int(ends.sum())
    return float(total)


def expert_pair_flops(hidden: int, width: int) -> float:
    return 6.0 * hidden * width


def attention_forward_flops(pairs: float, heads: int, head_dim: int) -> float:
    return 4.0 * head_dim * heads * pairs


def attention_forward_bytes(rows: float, heads: int, kv_heads: int, head_dim: int) -> float:
    return rows * COMPUTE_BYTES * head_dim * (2.0 * heads + 2.0 * kv_heads)


def step_counts(
    params, spec: dict, segment_ids: np.ndarray, block_length: int, pairs_per_step: float
) -> dict:
    """A mean training step's counts, for ``segment_ids`` [S, B, T] (S steps,
    averaged) and the mean of the step counter ``moe_pairs`` (all layers
    together)."""
    seg = np.asarray(segment_ids)
    tokens = float(seg[0].size)
    rows = 2.0 * tokens
    kept = kept_pairs(seg, block_length) / seg.shape[0]
    hq, hkv, d = spec["num_attention_heads"], spec["num_key_value_heads"], spec["head_dim"]
    size = lambda leaf: float(math.prod(leaf.shape))

    stack = layers(params)
    per_row = sum(
        sum(size(layer["attn"][f"{name}_proj"]["kernel"]) for name in "qkvo")
        + size(layer["mlp"]["router"]["weight"])
        for layer in stack
    )
    projections = 2.0 * rows * per_row + 2.0 * tokens * size(params["lm_head"]["kernel"])
    held, hidden, width = stack[0]["mlp"]["experts"]["kernel_gate"].shape
    expert_flops = pairs_per_step * expert_pair_flops(hidden, width)
    expert_bytes = COMPUTE_BYTES * (
        len(stack) * 3.0 * held * hidden * width + pairs_per_step * 3.0 * (hidden + width)
    )
    attn_flops = len(stack) * attention_forward_flops(kept, hq, d)
    attn_bytes = len(stack) * attention_forward_bytes(rows, hq, hkv, d)
    return {
        "step_flops": TRAIN_PASSES * (projections + attn_flops + expert_flops),
        "flash_blockdiff_flops": TRAIN_PASSES * attn_flops,
        "flash_blockdiff_bytes": TRAIN_PASSES * attn_bytes,
        "swiglu_experts_flops": EXPERT_PASSES * expert_flops,
        "swiglu_experts_bytes": EXPERT_PASSES * expert_bytes,
        "kept_pairs_per_step": kept,
        "tokens_per_step": tokens,
        "rows_per_step": rows,
        "moe_pairs_per_step": float(pairs_per_step),
    }
