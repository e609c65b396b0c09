"""Operations and bytes of the hybrid language model (the configuration
``granite-4.0-h-micro``), counted from shapes and from the packing layout.

They count **the mathematics, whatever implements it**: a multiply and an add
for every term of every product the layer equations hold (the equations are
at the top of ``benchmarks/reference/granite.py``), nothing the program
recomputes, nothing it masks away and computes all the same.

- A projection (every 2-D ``kernel`` of the tree, and the tied head over the
  vocabulary held): 2 x parameters a token.
- The state-space scan in its chunked form at chunk ``Q``, a token and a
  Mamba layer: the four block products ``C B^T`` (2 Q N), the weighted sum
  over the chunk (2 Q P H), a chunk's state (2 N P H) and the carried state's
  share (2 N P H): ``2 Q N + 2 Q P H + 4 N P H`` (4.26 MFLOP at Q 256, N 128,
  H 64, P 64). The token-by-token recurrence would be ``6 N P H`` (3.1 MFLOP):
  the chunked form trades operations for matrix shape.
- Attention: each query sees the keys of its own document at or before it, so
  a document of L tokens has L (L + 1) / 2 pairs; ``q . k`` and the weighted
  sum of values are ``4 d`` operations a pair and query head.
- A training step is three forward passes (forward, and a backward pass of
  twice its size), as ``model_flops.py`` counts the ResNets.

Bytes are one read of each operand and one write of the result in the compute
dtype (bf16, 2 bytes), three times that for a training step: what a kernel
that kept everything else on the chip would move. For the scan: ``x`` (H P),
``B``, ``C`` (N each) and ``dt`` (H) in, ``y`` (H P) out, a token. For
attention: ``q`` and the output (Hq d each), ``k`` and ``v`` (Hkv d each).
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import numpy as np

TRAIN_PASSES = 3.0
COMPUTE_BYTES = 2  # bf16


def document_lengths(segment_ids: np.ndarray) -> list[np.ndarray]:
    """For each packed sequence of ``segment_ids`` [..., T] the lengths of
    the documents (or pieces of documents) it holds."""
    rows = np.asarray(segment_ids).reshape(-1, np.shape(segment_ids)[-1])
    return [np.bincount(row - row.min()) for row in rows]


def causal_pairs(lengths: Sequence[int]) -> float:
    return float(sum(int(n) * (int(n) + 1) // 2 for n in lengths))


def _kernels(params) -> list[tuple]:
    return [
        tuple(leaf.shape)
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        if str(getattr(path[-1], "key", path[-1])) == "kernel"
    ]


def _mamba_layers(params) -> list[dict]:
    return [v["mixer"] for k, v in params.items() if k.startswith("layers_") and "A_log" in v["mixer"]]


def _attention_layers(params) -> list[dict]:
    return [v["mixer"] for k, v in params.items() if k.startswith("layers_") and "q_proj" in v["mixer"]]


def ssd_dims(mixer: dict) -> tuple[int, int, int]:
    """(H, P, N) of a Mamba mixer's subtree (arrays or shapes)."""
    heads = mixer["dt_bias"].shape[0]
    inner = mixer["out_proj"]["kernel"].shape[0]
    return heads, inner // heads, (mixer["conv_bias"].shape[0] - inner) // 2


def ssd_forward_flops(tokens: float, heads: int, p: int, n: int, chunk: int) -> float:
    return tokens * (2.0 * chunk * n + 2.0 * chunk * p * heads + 4.0 * n * p * heads)


def ssd_forward_bytes(tokens: float, heads: int, p: int, n: int) -> float:
    return tokens * COMPUTE_BYTES * (2.0 * heads * p + 2.0 * n + heads)


def attention_forward_flops(pairs: float, heads: int, head_dim: int) -> float:
    return 4.0 * head_dim * heads * pairs


def attention_forward_bytes(tokens: float, heads: int, kv_heads: int, head_dim: int) -> float:
    return tokens * COMPUTE_BYTES * head_dim * (2.0 * heads + 2.0 * kv_heads)


def step_counts(params, spec: dict, segment_ids: np.ndarray, chunk: int) -> dict:
    """A mean training step's counts, for one step's worth of packed
    sequences ``segment_ids`` [S, B, T] (S steps, averaged): ``step_flops``
    of the whole model, and operations and bytes of the scan (all Mamba
    layers together) and of attention (all attention layers)."""
    seg = np.asarray(segment_ids)
    steps = seg.shape[0]
    tokens = float(seg[0].size)
    pairs = sum(causal_pairs(l) for l in document_lengths(seg)) / steps
    vocab, hidden = params["embedding"].shape
    hq, hkv = spec["num_attention_heads"], spec["num_key_value_heads"]

    projections = 2.0 * tokens * (sum(math.prod(s) for s in _kernels(params)) + vocab * hidden)
    ssd_flops = ssd_bytes = 0.0
    for mixer in _mamba_layers(params):
        h, p, n = ssd_dims(mixer)
        ssd_flops += ssd_forward_flops(tokens, h, p, n, chunk)
        ssd_bytes += ssd_forward_bytes(tokens, h, p, n)
    attn_flops = attn_bytes = 0.0
    for mixer in _attention_layers(params):
        d = mixer["q_proj"]["kernel"].shape[1] // hq
        attn_flops += attention_forward_flops(pairs, hq, d)
        attn_bytes += attention_forward_bytes(tokens, hq, hkv, d)
    return {
        "step_flops": TRAIN_PASSES * (projections + ssd_flops + attn_flops),
        "ssd_flops": TRAIN_PASSES * ssd_flops,
        "ssd_bytes": TRAIN_PASSES * ssd_bytes,
        "flash_causal_flops": TRAIN_PASSES * attn_flops,
        "flash_causal_bytes": TRAIN_PASSES * attn_bytes,
        "causal_pairs_per_step": pairs,
        "tokens_per_step": tokens,
    }


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over the
    bf16 peak and bytes over the HBM bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
