"""Plain SGD training of the plain ResNet, to follow the program's steps.

The semantics are the recipe's (torch ``SGD(lr, momentum, weight_decay)`` under
a triangular learning rate, SURVEY.md section 3.3), written out:

    loss  = mean over the batch of softmax cross-entropy(forward(w * m, x), y)
    g     = d loss / d w                  (the raw weights: a masked weight
                                           gets no data gradient)
    g     = g + weight_decay * w          (every weight, masked ones too)
    buf   = momentum * buf + g
    w     = w - lr(step) * buf
    lr(s) = base_lr * interp(s; [0, warmup, total] -> [0.2, 1, 0]),
            warmup = max(int(total * warmup_fraction), 1)

in float32 at ``highest`` matmul precision, batch-norm from the batch's own
statistics. Nothing of the program is imported; the starting weights, masks
and batches are the inputs it is given.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import resnet


@dataclass(frozen=True)
class Recipe:
    base_lr: float
    momentum: float
    weight_decay: float
    warmup_fraction: float
    total_steps: int

    def lr(self, step: int) -> float:
        warmup = max(int(self.total_steps * self.warmup_fraction), 1)
        return self.base_lr * float(
            np.interp(float(step), [0.0, warmup, self.total_steps], [0.2, 1.0, 0.0])
        )


def _step(recipe: Recipe, quantize: Optional[Callable]):
    def loss_of(params, masks, batch_stats, images, labels):
        logits = resnet.forward(
            resnet.masked(params, masks), batch_stats, images, quantize, train=True
        )
        return jnp.mean(resnet.cross_entropy(logits, labels))

    def step(params, buf, masks, batch_stats, images, labels, lr):
        loss, grads = jax.value_and_grad(loss_of)(params, masks, batch_stats, images, labels)
        grads = jax.tree.map(lambda g, w: g + recipe.weight_decay * w, grads, params)
        buf = jax.tree.map(lambda b, g: recipe.momentum * b + g, buf, grads)
        params = jax.tree.map(lambda w, b: w - lr * b, params, buf)
        return params, buf, loss

    return jax.jit(step, donate_argnums=(0, 1))


def follow(
    recipe: Recipe,
    params: dict,
    buf: dict,
    masks: dict,
    batch_stats: dict,
    images: np.ndarray,
    labels: np.ndarray,
    first_step: int = 0,
    quantize: Optional[Callable] = None,
) -> dict:
    """Takes the steps over ``images`` [K, B, H, W, 3] and ``labels`` [K, B]
    one after the other from ``params`` and the momentum buffers ``buf``.
    Returns the mean of the steps' losses and the weights and buffers after
    the last, on the host."""
    as32 = lambda tree: jax.tree.map(lambda x: jnp.array(x, jnp.float32), tree)
    params, buf = as32(params), as32(buf)
    masks = jax.tree.map(jnp.asarray, masks)
    step = _step(recipe, quantize)
    losses = []
    with jax.default_matmul_precision("highest"):
        for k in range(images.shape[0]):
            params, buf, loss = step(
                params, buf, masks, batch_stats,
                jnp.asarray(images[k]), jnp.asarray(labels[k]),
                jnp.float32(recipe.lr(first_step + k)),
            )
            losses.append(loss)
    losses = [float(x) for x in losses]
    return {
        "loss": float(np.mean(losses)),
        "losses": losses,
        "params": jax.device_get(params),
        "buf": jax.device_get(buf),
    }


def masked_path(recipe: Recipe, w: np.ndarray, buf: np.ndarray, steps: int, first_step: int = 0):
    """Where ``steps`` steps take weights that a mask holds at zero: they get
    no data gradient, so their path is the optimizer's alone and needs no
    forward pass. In float32 and in the order written at the top, as the
    weights are float32: a step moves such a weight by a millionth of itself,
    which float32 holds to a percent, and only the same arithmetic lands on
    the same roundings."""
    w, buf = np.array(w, np.float32), np.array(buf, np.float32)
    momentum, decay = np.float32(recipe.momentum), np.float32(recipe.weight_decay)
    for k in range(steps):
        buf = momentum * buf + decay * w
        w = w - np.float32(recipe.lr(first_step + k)) * buf
    return w
