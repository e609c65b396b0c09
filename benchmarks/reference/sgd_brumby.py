"""Plain SGD training of the plain ``brumby`` decoder
(``reference/brumby.py``), to follow the program's steps: what
``reference/sgd_granite.py`` is to ``reference/granite.py``, with the same
optimizer semantics and schedule (``reference/sgd.py``'s ``Recipe``) and the
same loss,

    loss = mean over the valid targets of the batch of
           softmax cross-entropy(forward(w * m, ids, segment ids), target)

in float32 at ``highest`` matmul precision, retention in its quadratic form,
the head and the loss a block of tokens at a time (``brumby.loss``). Nothing
of the program is imported.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import brumby
from benchmarks.reference.sgd import Recipe


def _step(recipe: Recipe, spec: dict, quantize: Optional[Callable]):
    def loss_of(params, masks, tokens, targets):
        return brumby.loss(params, spec, tokens[:, 0], tokens[:, 1], targets, quantize, masks)

    def step(params, buf, masks, tokens, targets, lr):
        loss, grads = jax.value_and_grad(loss_of)(params, masks, tokens, targets)
        grads = jax.tree.map(lambda g, w: g + recipe.weight_decay * w, grads, params)
        buf = jax.tree.map(lambda b, g: recipe.momentum * b + g, buf, grads)
        params = jax.tree.map(lambda w, b: w - lr * b, params, buf)
        return params, buf, loss

    return jax.jit(step, donate_argnums=(0, 1))


def follow(
    recipe: Recipe,
    spec: dict,
    params: dict,
    buf: dict,
    masks: dict,
    tokens: np.ndarray,
    targets: np.ndarray,
    first_step: int = 0,
    quantize: Optional[Callable] = None,
) -> dict:
    """Takes the steps over ``tokens`` [K, B, 2, T] and ``targets`` [K, B, T]
    one after the other from ``params`` and the momentum buffers ``buf``.
    Returns the mean of the steps' losses and the weights and buffers after
    the last, on the host."""
    as32 = lambda tree: jax.tree.map(lambda x: jnp.array(x, jnp.float32), tree)
    params, buf = as32(params), as32(buf)
    masks = jax.tree.map(jnp.asarray, masks)
    step = _step(recipe, spec, quantize)
    losses = []
    with jax.default_matmul_precision("highest"):
        for k in range(tokens.shape[0]):
            params, buf, loss = step(
                params, buf, masks, jnp.asarray(tokens[k]), jnp.asarray(targets[k]),
                jnp.float32(recipe.lr(first_step + k)),
            )  # fmt: skip
            losses.append(loss)
    losses = [float(x) for x in losses]
    out = {
        "loss": float(np.mean(losses)),
        "losses": losses,
        "params": jax.device_get(params),
        "buf": jax.device_get(buf),
    }
    del params, buf
    return out
