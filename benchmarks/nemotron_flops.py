"""Operations and bytes of the ``nemotron_h`` decoder as one chip holds it
(the configuration ``nemotron-3-super-120b-a12b``), counted from shapes, from
the packing layout and from the step's own counters.

As ``granite_flops.py`` (whose attention counts, layout helpers and roofline
this imports) they count **the mathematics, whatever implements it**: a
multiply and an add for every term of every product of the layer equations
(top of ``benchmarks/reference/nemotron_h.py``), three forward passes for a
training step, nothing the program recomputes or masks away.

- A projection (every 2-D ``kernel`` of the tree, the head among them, and
  the router's ``weight``): 2 x parameters a token.
- The routed experts: a (token, expert) pair whose expert is held here is
  ``2 (L F + F L)`` operations, L the latent and F the expert width. How many
  pairs a step has is the routing's: the step's own counter ``moe_pairs``
  (ops/moe.py), so the count is of the work that was there to do, whatever
  buffer or kernel did it.
- The state-space scan in its chunked form at chunk Q with G groups of heads,
  a token and a Mamba layer: ``2 Q N G + 2 Q P H + 4 N P H`` (granite_flops.py
  at G = 1).
- Attention: each document's own triangle of query-key pairs, ``4 d`` a pair
  and query head.

Bytes are one read of each operand and one write of the result in the compute
dtype (bf16). For the experts' two grouped products, a pass: one read of the
held experts' kernels and each pair's rows in and out (``L + F`` and ``F +
L``). The layer is a ``jax.checkpoint``, so the products run the forward
twice: the kernel's roofline (``experts_*``) counts four passes, as the kernel
is given them; ``step_flops`` counts the mathematics' three.
"""

from __future__ import annotations

import math

import jax
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


def mixers(params, key: str) -> list[dict]:
    return [v["mixer"] for k, v in params.items() if k.startswith("layers_") and key in v["mixer"]]


def _projection_parameters(params) -> int:
    """Every 2-D ``kernel`` and every router ``weight``."""
    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        key = str(getattr(path[-1], "key", path[-1]))
        if (key == "kernel" or key == "weight") and len(leaf.shape) == 2:
            total += math.prod(leaf.shape)
    return total


def ssd_forward_flops(tokens, heads, p, n, groups, chunk) -> float:
    return tokens * (2.0 * chunk * n * groups + 2.0 * chunk * p * heads + 4.0 * n * p * heads)


def ssd_forward_bytes(tokens, heads, p, n, groups) -> float:
    return tokens * COMPUTE_BYTES * (2.0 * heads * p + 2.0 * n * groups + heads)


def expert_pair_flops(latent: int, width: int) -> float:
    return 4.0 * latent * width


def step_counts(params, spec: dict, segment_ids: np.ndarray, pairs_per_step: float) -> dict:
    """A mean training step's counts, for ``segment_ids`` [S, B, T] (S steps,
    averaged) and the mean of the step counter ``moe_pairs`` (all ``E`` layers
    together): ``step_flops`` of the whole model, and operations and bytes of
    the scan, of attention and of the experts' grouped products."""
    seg = np.asarray(segment_ids)
    tokens = float(seg[0].size)
    pairs = sum(causal_pairs(l) for l in document_lengths(seg)) / seg.shape[0]
    hq, hkv, groups = spec["num_attention_heads"], spec["num_key_value_heads"], spec["n_groups"]

    projections = 2.0 * tokens * _projection_parameters(params)
    ssd_flops = ssd_bytes = 0.0
    for mixer in mixers(params, "A_log"):
        heads = mixer["dt_bias"].shape[0]
        inner = mixer["out_proj"]["kernel"].shape[0]
        n = (mixer["conv_bias"].shape[0] - inner) // (2 * groups)
        ssd_flops += ssd_forward_flops(tokens, heads, inner // heads, n, groups, spec["chunk_size"])
        ssd_bytes += ssd_forward_bytes(tokens, heads, inner // heads, n, groups)
    attn_flops = attn_bytes = 0.0
    for mixer in mixers(params, "q_proj"):
        d = mixer["q_proj"]["kernel"].shape[1] // hq
        attn_flops += attention_forward_flops(pairs, hq, d)
        attn_bytes += attention_forward_bytes(tokens, hq, hkv, d)
    moe_layers = mixers(params, "router")
    expert_flops = expert_bytes = 0.0
    if moe_layers:
        held, latent, width = moe_layers[0]["experts"]["kernel_up"].shape
        expert_flops = pairs_per_step * expert_pair_flops(latent, width)
        expert_bytes = COMPUTE_BYTES * (
            len(moe_layers) * 2.0 * held * latent * width + pairs_per_step * 2.0 * (latent + width)
        )
    return {
        "step_flops": TRAIN_PASSES * (projections + ssd_flops + attn_flops + expert_flops),
        "ssd_flops": TRAIN_PASSES * ssd_flops,
        "ssd_bytes": TRAIN_PASSES * ssd_bytes,
        "flash_causal_flops": TRAIN_PASSES * attn_flops,
        "flash_causal_bytes": TRAIN_PASSES * attn_bytes,
        "experts_flops": EXPERT_PASSES * expert_flops,
        "experts_bytes": EXPERT_PASSES * expert_bytes,
        "causal_pairs_per_step": pairs,
        "tokens_per_step": tokens,
        "moe_pairs_per_step": float(pairs_per_step),
    }
