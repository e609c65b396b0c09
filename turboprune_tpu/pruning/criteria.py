"""Pruning criteria as pure functions ``(params, masks, ...) -> masks``.

Rebuilds every criterion of the reference's pruning engine
(/root/reference/utils/pruning_utils.py) as side-effect-free pytree ops:

  mag              global |mask*w| kthvalue threshold   (pruning_utils.py:61-89)
  snip             one-batch |grad*w*mask|, global      (:160-205)
  synflow          abs-linearized ones-forward saliency (:208-285)
  random_erk       ERK layer densities + random scores  (:92-146)
  random_balanced  equal per-layer budget + random      (:288-347)
  er_erk           ERK densities, Bernoulli masks (PaI) (:350-378)
  er_balanced      balanced densities, Bernoulli (PaI)  (:381-415)
  nm               mag + N:M projection (sparse/nm.py)  (this repo only)

All run replicated on every host from replicated state — determinism by
construction replaces the reference's rank-0-prune + DDP-broadcast dance
(SURVEY.md §3.1). The PRNG key is passed in explicitly so every host derives
identical Bernoulli/normal draws.

SynFlow's in-place abs/sign dance (pruning_utils.py:223-248) becomes a pure
``tree_map(abs)`` — no sign restore needed since the real params are never
touched.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..ops.masking import (
    PyTree,
    global_threshold_mask,
    is_stacked_path,
    mask_leaves,
    mask_where,
    path_name,
    per_layer_threshold_mask,
)

# Budget allocators live in densities.py (pure shape math); re-exported
# here because criteria was their historical home.
from .densities import _layer_sizes, balanced_densities, erk_densities

# ---------------------------------------------------------------------------
# helpers


def _random_normal_scores(masks: PyTree, rng: jax.Array) -> PyTree:
    """|N(0,1)| scores at unmasked positions, 0 at masked (so previously
    pruned weights can never win a per-layer threshold)."""
    leaves = mask_leaves(masks)
    keys = jax.random.split(rng, len(leaves))
    it = iter(range(len(leaves)))

    def score(m):
        k = keys[next(it)]
        return m.astype(jnp.float32) * jnp.abs(
            jax.random.normal(k, m.shape, jnp.float32)
        )

    return mask_where(masks, score)


def _bernoulli_masks(
    masks: PyTree, densities: dict[str, float], rng: jax.Array
) -> PyTree:
    """set_er_mask: mask ~ Bernoulli(p) per layer (reference
    mask_layers.py:36-43)."""
    names = [name for name, _, _ in _layer_sizes(masks)]
    keys = dict(zip(names, jax.random.split(rng, len(names))))
    draw = lambda name, shape: jax.random.bernoulli(keys[name], densities[name], shape)

    def go(path, m):
        if m is None:
            return None
        name = path_name(path)
        if is_stacked_path(path):  # a layer for each kernel it holds
            return jnp.stack([draw(f"{name}[{e}]", m.shape[1:]) for e in range(m.shape[0])])
        return draw(name, m.shape)

    return jax.tree_util.tree_map_with_path(
        go, masks, is_leaf=lambda x: x is None
    )


# ---------------------------------------------------------------------------
# criteria


def prune_mag(params: PyTree, masks: PyTree, density: float) -> PyTree:
    scores = mask_where(
        masks, lambda m, p: jnp.abs(p * m.astype(p.dtype)), params
    )
    return global_threshold_mask(scores, masks, density)


def prune_nm(
    params: PyTree,
    masks: PyTree,
    density: float,
    n: int,
    m: int,
    transposable: bool = True,
) -> PyTree:
    """Magnitude IMP step + N:M projection: the global-threshold mask is
    snapped to the highest-magnitude-preserving separable N:M pattern per
    layer (sparse/nm.py). Projection is monotone (mask & pattern), so the
    no-resurrection invariant the ladder depends on survives; achieved
    density lands below the ladder target by the projection's cut, which is
    the structured-sparsity price the pattern pays for real speedup."""
    from ..sparse.nm import project_masks

    new_masks = prune_mag(params, masks, density)
    projected, _ = project_masks(params, new_masks, n, m, transposable)
    return projected


def prune_random_erk(
    params: PyTree, masks: PyTree, density: float, rng: jax.Array
) -> PyTree:
    del params
    densities = erk_densities(masks, density)
    scores = _random_normal_scores(masks, rng)
    return per_layer_threshold_mask(scores, densities)


def prune_random_balanced(
    params: PyTree, masks: PyTree, density: float, rng: jax.Array
) -> PyTree:
    del params
    densities = balanced_densities(masks, density)
    scores = _random_normal_scores(masks, rng)
    return per_layer_threshold_mask(scores, densities)


def prune_er_erk(
    params: PyTree, masks: PyTree, density: float, rng: jax.Array
) -> PyTree:
    del params
    return _bernoulli_masks(masks, erk_densities(masks, density), rng)


def prune_er_balanced(
    params: PyTree, masks: PyTree, density: float, rng: jax.Array
) -> PyTree:
    del params
    return _bernoulli_masks(masks, balanced_densities(masks, density), rng)


def prune_snip(
    loss_grad_fn: Callable[[PyTree, PyTree, Any], PyTree],
    params: PyTree,
    masks: PyTree,
    density: float,
    batch: Any,
) -> PyTree:
    """SNIP: saliency |∂L/∂w * w * m| on ONE batch, global threshold.

    ``loss_grad_fn(params, masks, batch) -> grads`` must differentiate the
    masked forward's CE loss wrt the raw params (so grads already carry the
    mask factor, matching the reference's masked-layer backward,
    pruning_utils.py:186-191)."""
    grads = loss_grad_fn(params, masks, batch)
    scores = mask_where(
        masks,
        lambda m, g, p: jnp.abs(g * p * m.astype(p.dtype)).astype(jnp.float32),
        grads,
        params,
    )
    return global_threshold_mask(scores, masks, density)


def prune_synflow(
    forward_sum_fn: Callable[[PyTree, PyTree, Any], jax.Array],
    variables_abs: PyTree,
    params: PyTree,
    masks: PyTree,
    density: float,
    ones_input: jax.Array,
) -> PyTree:
    """SynFlow: R = sum(f_|θ|(1)); score |m * ∂R/∂w * w| on the ABS params.

    The reference abs-es the whole state dict in place, backprops a ones
    input, then restores signs (pruning_utils.py:223-271). Purely: the caller
    passes ``variables_abs`` = tree_map(abs, variables); we differentiate
    wrt its params and score with the ORIGINAL param magnitudes (|w| equals
    abs(w), so scoring with either matches the reference)."""
    del params

    def loss(p_abs):
        return forward_sum_fn(p_abs, masks, ones_input)

    grads = jax.grad(loss)(variables_abs["params"])
    scores = mask_where(
        masks,
        lambda m, g, p: (m.astype(jnp.float32)
                         * jnp.abs(g.astype(jnp.float32) * p.astype(jnp.float32))),
        grads,
        variables_abs["params"],
    )
    return global_threshold_mask(scores, masks, density)
