"""Mask pytrees — the sparsity mechanism.

The reference stores masks as buffers on custom module subclasses and
multiplies ``mask * weight`` in every forward
(/root/reference/utils/mask_layers.py:25,69,109). Here masks are a pytree
mirroring the model params, with a boolean array at every *prunable* leaf
(conv / dense kernels — reference masks every Conv2d and Linear, including
the classifier head, custom_models.py:217-220) and ``None`` elsewhere.
``apply_masks`` multiplies them into the params inside the jitted forward, so
XLA fuses the multiply into the convolution's operand producer; gradients
flow to the raw params scaled by the mask exactly as in the reference
(pruned weights get zero gradient from the forward but can still drift via
momentum / weight decay — a semantic we preserve, SURVEY.md §3.3).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Iterator, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import tracing

PyTree = Any

# Treat None as a leaf so mask trees (None at non-prunable positions) keep the
# exact structure of the param tree.
def _is_none(x) -> bool:
    return x is None


def _leaf_key(path: tuple) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", str(last))))


def is_prunable_path(path: tuple) -> bool:
    """A param leaf is prunable iff it is a conv/dense kernel, or a stack of
    them.

    Flax linen names conv and dense weights 'kernel'; biases are 'bias' and
    norm params 'scale'/'bias' — matching the reference's rule of masking
    exactly the Conv2d/Linear weights (custom_models.py:217-220).

    A *stacked kernel* is one leaf ``kernel_<role>`` of shape
    ``[layers, in, out]`` that holds one dense kernel per layer along its
    first axis: the routed experts of models/nemotron_h.py
    (``.../experts/kernel_up``, ``.../experts/kernel_down``), which one
    grouped product reads whole. Its mask has its shape; the global criteria
    see its elements like any other's, and everything that works per layer
    (``mask_layers``: the ERK and balanced allocators, the per-layer
    thresholds, ``kept_counts``, ``layerwise_sparsity``) sees ``layers``
    kernels of ``[in, out]`` named ``.../experts/kernel_up[e]``. Any other
    matrix (a router's ``weight``, an ``embedding``) is not a kernel and is
    never masked."""
    key = _leaf_key(path)
    return key == "kernel" or key.startswith("kernel_")


def is_stacked_path(path: tuple) -> bool:
    return _leaf_key(path).startswith("kernel_")


def tree_paths(tree: PyTree) -> Iterator[tuple]:
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        yield path


def path_name(path: tuple) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "name", getattr(p, "idx", p)))))
    return "/".join(parts)


def make_masks(
    params: PyTree, predicate: Callable[[tuple], bool] = is_prunable_path
) -> PyTree:
    """Dense (all-ones) mask tree: bool ones at prunable leaves, None elsewhere."""

    def leaf_mask(path, leaf):
        if predicate(path):
            return jnp.ones(jnp.shape(leaf), dtype=jnp.bool_)
        return None

    return jax.tree_util.tree_map_with_path(leaf_mask, params)


def apply_masks(params: PyTree, masks: PyTree) -> PyTree:
    """``w * m`` at masked leaves; identity elsewhere. Call inside jit."""

    def apply(m, p):
        if m is None:
            return p
        return p * m.astype(p.dtype)

    with jax.named_scope("mask_apply"):
        return jax.tree.map(apply, masks, params, is_leaf=_is_none)


def mask_where(masks: PyTree, fn: Callable[..., jax.Array], *trees: PyTree) -> PyTree:
    """Map ``fn(mask, *leaves)`` over masked positions only; None passthrough."""

    def go(m, *leaves):
        if m is None:
            return None
        return fn(m, *leaves)

    return jax.tree.map(go, masks, *trees, is_leaf=_is_none)


def mask_leaves(masks: PyTree) -> list[jax.Array]:
    return [m for m in jax.tree.leaves(masks, is_leaf=_is_none) if m is not None]


def mask_leaves_with_path(masks: PyTree) -> list[tuple[tuple, jax.Array]]:
    out = []
    for path, m in jax.tree_util.tree_flatten_with_path(
        masks, is_leaf=_is_none
    )[0]:
        if m is not None:
            out.append((path, m))
    return out


def num_prunable(masks: PyTree) -> int:
    return sum(int(m.size) for m in mask_leaves(masks))


def mask_layers(masks: PyTree) -> list[tuple[str, tuple, int]]:
    """[(name, shape, numel)] of every prunable layer, in traversal order: a
    leaf is one layer under its path's name, a stacked kernel
    (``is_prunable_path``) one layer ``name[e]`` of shape ``[in, out]`` for
    each index ``e`` of its first axis."""
    out = []
    for path, m in mask_leaves_with_path(masks):
        name, shape = path_name(path), tuple(m.shape)
        if is_stacked_path(path):
            out += [(f"{name}[{e}]", shape[1:], int(m.size) // shape[0]) for e in range(shape[0])]
        else:
            out.append((name, shape, int(m.size)))
    return out


@functools.partial(jax.jit, static_argnums=1)
def _kept_counts(leaves: list[jax.Array], stacked: tuple[bool, ...]) -> jax.Array:
    return jnp.concatenate(
        [
            jnp.sum(m.reshape(m.shape[0] if s else 1, -1), axis=1, dtype=jnp.int32)
            for m, s in zip(leaves, stacked)
        ]
    )


def kept_counts(masks: PyTree) -> list[int]:
    """The kept count of every prunable layer, in ``mask_layers`` order (a
    stacked kernel counts once for each kernel it holds): the
    one way to count a mask tree. One compiled program over the whole tree
    (its executable keyed, as ``jax.jit`` keys it, by the tree's structure
    and shapes), one dispatch and one fetch of an int32 vector (a leaf holds
    under 2^31 elements); the host goes on in Python ints, so every count is
    exact. Each dispatch adds one to the ``mask_reads`` gauge: the host waits
    here for whatever wrote the masks, so callers that can carry the result
    do (``MaskCount``, ``PruningHarness.mask_count``)."""
    with_path = mask_leaves_with_path(masks)
    if not with_path:
        return []
    tracing.count("mask_reads")
    stacked = tuple(is_stacked_path(path) for path, _ in with_path)
    return np.asarray(_kept_counts([m for _, m in with_path], stacked)).tolist()


class MaskCount(NamedTuple):
    """The zeros and the size of a mask tree, as Python ints."""

    zeros: int
    total: int

    @property
    def sparsity(self) -> float:
        """Percent of prunable weights masked out (reference
        PruneModel.get_overall_sparsity, custom_models.py:51-62 — returns %)."""
        return (self.zeros / self.total) * 100.0 if self.total else 0.0

    @property
    def density(self) -> float:
        return 1.0 - self.sparsity / 100.0


def count_masks(masks: PyTree) -> MaskCount:
    """One read of the device (``kept_counts``)."""
    total = num_prunable(masks)
    return MaskCount(total - sum(kept_counts(masks)), total)


def overall_sparsity(masks: PyTree) -> float:
    """Percent of prunable weights masked out; one read of the device."""
    return count_masks(masks).sparsity


def overall_density(masks: PyTree) -> float:
    return count_masks(masks).density


def layerwise_sparsity(masks: PyTree) -> dict[str, float]:
    """Per-layer sparsity %, keyed by the layer's name (``mask_layers``: the
    param path, with ``[e]`` for each kernel of a stacked one; reference
    print_layer_sparsity, custom_models.py:29-49)."""
    out = {}
    for (name, _, numel), kept in zip(mask_layers(masks), kept_counts(masks)):
        out[name] = ((numel - kept) / numel) * 100.0
    return out


def reset_masks(masks: PyTree) -> PyTree:
    """All-ones masks of the same structure (reference reset_masks,
    custom_models.py:148-151)."""
    return mask_where(masks, lambda m: jnp.ones_like(m))


def combine_rewind(
    current_params: PyTree, rewind_params: PyTree, masks: PyTree
) -> PyTree:
    """Weight rewinding: restore ALL params from the rewind checkpoint.

    The reference restores every non-mask tensor (custom_models.py:137-144);
    masks live in a separate tree here, so this is a full param swap — kept as
    a named op so the call site documents intent."""
    del current_params, masks
    return rewind_params


def global_threshold_mask(
    scores: PyTree, masks: PyTree, density: float
) -> PyTree:
    """Global magnitude-style masking: keep weights whose score exceeds the
    k-th smallest score, k = (1-density) * N over ALL prunable weights
    (reference prune_mag, pruning_utils.py:61-89: global kthvalue then
    ``mask = score > threshold``).

    Scores at already-pruned positions must be 0 (callers multiply by the
    mask) so pruning is monotone across levels. When k < 1 the reference
    leaves the masks untouched (pruning_utils.py:81) — replicated here; the
    density is a host-side float so k is static.

    The threshold (k-th smallest = (n-k+1)-th largest) comes from
    ``lax.top_k`` over kept+1 elements instead of a full ``jnp.sort``:
    identical value, so the masks are bit-identical to the sort path
    (asserted in tests), but the partial selection scales with the KEPT
    count — at the recipe's 90%+ sparsities that is a 10x+ smaller
    selection problem than sorting all N prunable weights."""
    flat = jnp.concatenate(
        [s.reshape(-1) for s in mask_leaves(scores)]
    ).astype(jnp.float32)
    n = flat.shape[0]
    k = int((1.0 - density) * n)
    if k < 1:
        return masks
    threshold = _kth_smallest(flat, k)
    return mask_where(scores, lambda s: s > threshold)


def _kth_smallest(flat: jax.Array, k: int) -> jax.Array:
    """kthvalue(k) (1-indexed) via ``lax.top_k``: the k-th smallest of n
    values is the smallest of the top (n - k + 1), i.e. the last entry of
    ``top_k(flat, n - k + 1)``. Values are compared exactly (no recompute),
    so the result is bit-identical to ``jnp.sort(flat)[k - 1]``."""
    kept_plus_one = int(flat.shape[0]) - k + 1
    top, _ = jax.lax.top_k(flat, kept_plus_one)
    return top[-1]


def per_layer_threshold_mask(scores: PyTree, densities: dict[str, float]) -> PyTree:
    """Per-layer kthvalue masking used by random_erk / random_balanced
    (reference pruning_utils.py:126-146, 326-347); ``densities`` is keyed as
    ``mask_layers`` names the layers, so each kernel of a stacked one has its
    own threshold."""

    def one(path, s):
        if is_stacked_path(path):
            name = path_name(path)
            return jnp.stack([layer(s[e], densities[f"{name}[{e}]"]) for e in range(s.shape[0])])
        return layer(s, densities[path_name(path)])

    def layer(s, d):
        n = s.size
        k = int((1.0 - d) * n)
        if k <= 0:
            # Keep every position with a positive score. Scores at
            # already-pruned positions are exactly 0 (callers multiply by the
            # mask), so a density-1 layer keeps its existing mask rather than
            # resurrecting pruned weights — the reference's k==0 threshold-0
            # behavior (pruning_utils.py:137-143).
            return s > 0.0
        threshold = _kth_smallest(s.reshape(-1).astype(jnp.float32), k)
        return s > threshold

    return _map_with_path_masked(one, scores)


def _map_with_path_masked(fn, masks_like: PyTree) -> PyTree:
    def go(path, m):
        if m is None:
            return None
        return fn(path, m)

    return jax.tree_util.tree_map_with_path(go, masks_like, is_leaf=_is_none)
