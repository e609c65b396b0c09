"""Plain ResNet v1 forward (He et al. 2015, arXiv:1512.03385), in inference
mode (batch-norm from the running statistics) and in training mode (batch-norm
from the batch's own mean and biased variance).

Straightforward ``jax.numpy`` / ``lax.conv_general_dilated`` in float32 at
``highest`` matmul precision. It reads a parameter tree and a batch-statistics
tree laid out as the program's checkpoints are (``conv1``, ``bn1``,
``layer{stage}_{block}/{Conv_k, BatchNorm_k, downsample_conv, downsample_bn}``,
``fc``) and imports nothing of the program.

Departures from the paper, all read off the tree and none chosen here:

- the stride of a bottleneck block sits on its 3x3 convolution (torchvision's
  "v1.5"), and every convolution but the ImageNet stem pads ``SAME`` as XLA
  defines it (at stride 2 on an even size that is one row after, none before);
- a 3x3 ``conv1`` kernel means the CIFAR stem: stride 1 and no max-pool.

``quantize`` is for the control only: it is applied to each convolution's and
the classifier's two operands, and stands for a matmul path in a lower
precision than the configuration states, in the backward pass too.

In training mode every block and every stage is a ``jax.checkpoint``: a
backward pass keeps the stages' inputs, rebuilds one stage's block inputs and
then one block's inside, so a float32 ResNet50 at batch 256 fits beside the
program's resident state on a 16 GB chip.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

BN_EPSILON = 1e-5
_DIMS = ("NHWC", "HWIO", "NHWC")


def _rounded(x: jax.Array, dtype, top: float) -> jax.Array:
    """``x`` scaled per tensor so that its largest magnitude sits at ``top``,
    rounded to ``dtype``, scaled back."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_activation(x):
    return _rounded(x, jnp.float8_e4m3fn, 240.0)


_fp8_activation.defvjp(
    lambda x: (_fp8_activation(x), None),
    lambda _, g: (_rounded(g, jnp.float8_e5m2, 57344.0),),
)


def fp8_operand(x: jax.Array, weight: bool = False) -> jax.Array:
    """What a float8 matmul sees of ``x``, as float8 training has it
    (Micikevicius et al. 2022, arXiv:2209.05433): operands rounded to e4m3
    going forward, and the gradient that flows back into the activations
    rounded to e5m2; the weights' gradient comes out of its matmul unrounded."""
    if weight:
        x = x.astype(jnp.float32)
        return x + lax.stop_gradient(_rounded(x, jnp.float8_e4m3fn, 240.0) - x)
    return _fp8_activation(x)


def _conv(x, kernel, stride, padding, quantize):
    if quantize is not None:
        x, kernel = quantize(x), quantize(kernel, weight=True)
    return lax.conv_general_dilated(
        x,
        kernel.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=_DIMS,
        precision=lax.Precision.HIGHEST,
    )


def _bn(x, p, s, train):
    if train:
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    else:
        mean, var = s["mean"], s["var"].astype(jnp.float32)
    return (x - mean) * (lax.rsqrt(var + BN_EPSILON) * p["scale"]) + p["bias"]


def _block(x, p, s, stride, quantize, train):
    """Basic (two 3x3) or bottleneck (1x1, 3x3, 1x1) residual block, told
    apart by whether the tree holds a third convolution."""
    bottleneck = "Conv_2" in p
    strides = (1, stride, 1) if bottleneck else (stride, 1)
    y = x
    for k, st in enumerate(strides):
        y = _conv(y, p[f"Conv_{k}"]["kernel"], st, "SAME", quantize)
        y = _bn(y, p[f"BatchNorm_{k}"], s[f"BatchNorm_{k}"], train)
        if k + 1 < len(strides):
            y = jax.nn.relu(y)
    if "downsample_conv" in p:
        x = _conv(x, p["downsample_conv"]["kernel"], stride, "SAME", quantize)
        x = _bn(x, p["downsample_bn"], s["downsample_bn"], train)
    return jax.nn.relu(y + x)


def _stem(x, params, batch_stats, quantize, train):
    kernel = params["conv1"]["kernel"]
    if kernel.shape[0] == 3:
        x = _conv(x, kernel, 1, "SAME", quantize)
        return jax.nn.relu(_bn(x, params["bn1"], batch_stats["bn1"], train))
    x = _conv(x, kernel, 2, [(3, 3), (3, 3)], quantize)
    x = jax.nn.relu(_bn(x, params["bn1"], batch_stats["bn1"], train))
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)],
    )


def _stage(x, params, batch_stats, stage, quantize, train):
    block = 0
    while f"layer{stage}_{block}" in params:
        name = f"layer{stage}_{block}"
        stride = 2 if stage > 1 and block == 0 else 1
        run = lambda x, p, s, stride=stride: _block(x, p, s, stride, quantize, train)
        if train:
            run = jax.checkpoint(run)
        x = run(x, params[name], batch_stats[name])
        block += 1
    return x


def forward(
    params: dict,
    batch_stats: dict,
    images: jax.Array,
    quantize: Optional[Callable[[jax.Array], jax.Array]] = None,
    train: bool = False,
) -> jax.Array:
    """Logits [N, classes] in float32 for images [N, H, W, 3]."""
    x = images.astype(jnp.float32)
    stem = lambda x, p, s: _stem(x, p, s, quantize, train)
    x = (jax.checkpoint(stem) if train else stem)(x, params, batch_stats)
    stage = 1
    while f"layer{stage}_0" in params:
        run = lambda x, p, s, stage=stage: _stage(x, p, s, stage, quantize, train)
        x = (jax.checkpoint(run) if train else run)(x, params, batch_stats)
        stage += 1
    x = jnp.mean(x, axis=(1, 2))
    w, b = params["fc"]["kernel"].astype(jnp.float32), params["fc"]["bias"]
    if quantize is not None:
        x, w = quantize(x), quantize(w, weight=True)
    return jnp.dot(x, w, precision=lax.Precision.HIGHEST) + b


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-row softmax cross-entropy, float32."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]


def masked(params: dict, masks: dict) -> dict:
    """``w * m`` wherever the mask tree holds an array, the weight elsewhere."""

    def go(p, m):
        if isinstance(p, dict):
            return {k: go(v, None if m is None else m.get(k)) for k, v in p.items()}
        return p if m is None else p * jnp.asarray(m, p.dtype)

    return go(params, masks)
