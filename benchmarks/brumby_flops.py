"""Operations and bytes of the ``brumby`` decoder as one chip holds it (the
configuration ``brumby-14b-base``), counted from shapes and from the packing
layout.

As ``granite_flops.py`` (whose layout helpers and roofline this imports) they
count **the mathematics, whatever implements it**: a multiply and an add for
every term of every product of the layer equations (top of
``benchmarks/reference/brumby.py``), three forward passes for a training
step, nothing the program recomputes or masks away.

- A projection (every 2-D ``kernel`` of the tree, the head among them, and the
  gate's ``gate_weight``): 2 x parameters a token.
- Power retention, a document of ``L`` tokens and a query head: the lesser of
  the two forms a program may run. Quadratic: every pair ``s <= t`` of the
  document, its score (``2 d``) and its weight times ``v`` (``2 d``): ``4 d``
  a pair. Recurrent: a token reads the state (``phi(q)^T [S | z]``: ``D (d +
  1)`` multiply-adds, ``D = d (d + 1) / 2`` the symmetric features: 8,256 at
  128) for each query head and writes it (``phi(k) [v | 1]^T``, the same) once
  for its key/value head. A long document is cheaper carried, a short one
  squared: each counts as its cheaper form, so the count is the same whatever
  implements it (the kernels' chunked walk, whose within-chunk squares and
  full 65 x 128 feature blocks are more, is charged the difference as lost
  share).

Bytes are one read of each operand and one write of the result in the compute
dtype (bf16): retention, a pass, reads ``q``, ``k``, ``v`` and the float32
``lam`` and writes ``o``. The layer is a ``jax.checkpoint`` and the program
runs the forward twice: the kernels' roofline (``retention_*``) counts four
passes, forward twice and a backward of twice its size, as the routed cells
count their experts; ``step_flops`` counts the mathematics' three.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.granite_flops import (  # noqa: F401  (roofline_seconds for the metrics)
    COMPUTE_BYTES,
    TRAIN_PASSES,
    causal_pairs,
    document_lengths,
    roofline_seconds,
)

RETENTION_PASSES = TRAIN_PASSES + 1.0  # the rebuilt forward


def symmetric_features(head_dim: int) -> int:
    return head_dim * (head_dim + 1) // 2


def retention_document_forms(length: int, heads: int, kv_heads: int, head_dim: int) -> tuple:
    """(quadratic, recurrent): one forward pass over one document, either way."""
    state = 2.0 * symmetric_features(head_dim) * (head_dim + 1)
    return 4.0 * head_dim * heads * causal_pairs([length]), length * state * (heads + kv_heads)


def retention_document_flops(length: int, heads: int, kv_heads: int, head_dim: int) -> float:
    """One forward pass over one document, the cheaper of its two forms."""
    return min(retention_document_forms(length, heads, kv_heads, head_dim))


def retention_forward_bytes(tokens: float, heads: int, kv_heads: int, head_dim: int) -> float:
    return tokens * (COMPUTE_BYTES * head_dim * (2.0 * heads + 2.0 * kv_heads) + 4.0 * kv_heads)


def step_counts(params, spec: dict, segment_ids: np.ndarray) -> dict:
    """A mean training step's counts, for ``segment_ids`` [S, B, T] (S steps,
    averaged): ``step_flops`` of the whole model, and operations and bytes of
    retention, all layers together."""
    seg = np.asarray(segment_ids)
    tokens = float(seg[0].size)
    hq, hkv, d = spec["num_attention_heads"], spec["num_key_value_heads"], spec["head_dim"]
    size = lambda leaf: float(math.prod(leaf.shape))
    layers = [v for k, v in sorted(params.items()) if k.startswith("layers_")]
    per_token = size(params["lm_head"]["kernel"]) + sum(
        sum(size(layer["retention"][f"{name}_proj"]["kernel"]) for name in "qkvo")
        + size(layer["retention"]["gate_weight"])
        + size(layer["mlp"]["in_proj"]["kernel"])
        + size(layer["mlp"]["out_proj"]["kernel"])
        for layer in layers
    )
    documents = [int(n) for row in document_lengths(seg) for n in row if n]
    forms = [retention_document_forms(n, hq, hkv, d) for n in documents]
    forward = len(layers) * sum(min(f) for f in forms) / seg.shape[0]
    carried = sum(n for n, (quadratic, recurrent) in zip(documents, forms) if recurrent < quadratic)
    return {
        "step_flops": TRAIN_PASSES * (2.0 * tokens * per_token + forward),
        "retention_flops": RETENTION_PASSES * forward,
        "retention_bytes": RETENTION_PASSES * len(layers) * retention_forward_bytes(tokens, hq, hkv, d),
        "causal_pairs_per_step": causal_pairs(documents) / seg.shape[0],
        # Tokens of documents long enough that carrying is the cheaper form.
        "carried_tokens_per_step": carried / seg.shape[0],
        "tokens_per_step": tokens,
    }
