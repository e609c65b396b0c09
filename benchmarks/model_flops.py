"""Floating-point operations the model needs, counted from shapes.

The count walks the jaxpr of the plain reference's forward pass
(``benchmarks/reference/resnet.py``) and adds up its convolutions and matrix
products: for each, two operations (a multiply and an add) for every output
element and every element of the contraction, padding included, which is how
the published figures count (ResNet50 at 224x224: 4.09 G multiply-adds). It is
a property of the model, the same on every backend, and untouched by what a PR
does to the program's step. Batch-norm, ReLU, pooling and the residual adds
are left out (about 1 % of ResNet50's operations).

A training step is counted as three forward passes (forward, and a backward
pass of twice its size) over the batch; mask multiplies, the optimizer and
anything the compiler recomputes are not model operations.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference import resnet as reference


def _eqn_flops(eqn) -> float:
    if eqn.primitive.name == "conv_general_dilated":
        out = eqn.outvars[0].aval.shape
        kernel = eqn.invars[1].aval.shape
        spec = eqn.params["dimension_numbers"].rhs_spec  # (out, in, *spatial)
        contraction = math.prod(kernel[i] for i in spec[1:])
        return 2.0 * math.prod(out) * contraction
    if eqn.primitive.name == "dot_general":
        out = eqn.outvars[0].aval.shape
        lhs = eqn.invars[0].aval.shape
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        return 2.0 * math.prod(out) * math.prod(lhs[i] for i in lhs_contract)
    return 0.0


def _jaxpr_flops(jaxpr) -> float:
    count = 0.0
    for eqn in jaxpr.eqns:
        count += _eqn_flops(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += _jaxpr_flops(sub)
    return count


def forward_flops(params, batch_stats, image_size: int) -> float:
    """Operations of one forward pass of one image. ``params`` and
    ``batch_stats`` may be arrays or shapes."""
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), (params, batch_stats)
    )
    image = jax.ShapeDtypeStruct((1, image_size, image_size, 3), jnp.float32)
    return _jaxpr_flops(jax.make_jaxpr(reference.forward)(*shapes, image).jaxpr)


def train_step_flops(params, batch_stats, image_size: int, batch: int) -> float:
    return 3.0 * forward_flops(params, batch_stats, image_size) * batch
