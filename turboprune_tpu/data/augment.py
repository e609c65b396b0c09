"""Jittable batched image augmentation (NHWC, device-resident).

Rebuilds the reference's airbench GPU-batched augmentation
(/root/reference/utils/dataset.py:38-98) as pure JAX ops over the WHOLE
training set: one jitted call at epoch start (``augment_epoch``, the
compiled module ``jit_augment_epoch``) augments all N images, and batches
are then plain slices of device arrays — zero per-step host work, which is
the TPU-shaped version of the reference's "keep the dataset on the
accelerator" trick (dataset.py:149, SURVEY.md §7).

Every op here is dense over N: elementwise selects, static slices, a
reverse. Nothing indexes by data. The crop was once a
``vmap(lax.dynamic_slice)`` with a per-image offset; the TPU compiler made
a gather of it and expanded the gather into a ``while`` of N iterations,
each slicing one image out and writing one in: 1,013 ms a CIFAR epoch
(50,000 images, a third of an IMP level of ResNet18) and 918 ms for 2,048
images at 224x224, more than their eight ResNet50 steps. As selects over
static shifts the whole call takes 12.5 and 25 ms (``augment_ms`` on a
v5e; PERF.md section 6, PR 25).

Semantics preserved (dataset.py:191-215):
  - normalize once with dataset mean/std
  - ``flip``: one random per-image pre-flip at epoch 0, then under
    ``altflip`` flip the ENTIRE set on odd epochs (higher diversity than
    i.i.d. flipping); without altflip, fresh random flips each epoch
  - ``translate=r``: reflect-pad by r then a random (sy, sx) shift per image
  - ``cutout=s``: zero a random s x s square per image
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# Standard CIFAR channel statistics (public constants; reference
# dataset.py:32-35).
CIFAR10_MEAN = (0.4914, 0.4822, 0.4465)
CIFAR10_STD = (0.2470, 0.2435, 0.2616)
CIFAR100_MEAN = (0.5071, 0.4867, 0.4408)
CIFAR100_STD = (0.2675, 0.2565, 0.2761)
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


def normalize_uint8(images: jax.Array, mean, std) -> jax.Array:
    """uint8 [0,255] NHWC -> normalized float32 (scale to [0,1] first)."""
    mean = jnp.asarray(mean, jnp.float32)
    std = jnp.asarray(std, jnp.float32)
    return (images.astype(jnp.float32) / 255.0 - mean) / std


def batch_flip_lr(images: jax.Array, key: jax.Array) -> jax.Array:
    """Random horizontal flip per image (reference batch_flip_lr,
    dataset.py:38-40)."""
    flip = jax.random.bernoulli(key, 0.5, (images.shape[0], 1, 1, 1))
    return jnp.where(flip, images[:, :, ::-1, :], images)


def pad_reflect(images: jax.Array, r: int) -> jax.Array:
    """Reflect-pad H and W by r (reference F.pad(..., 'reflect'),
    dataset.py:201)."""
    return jnp.pad(images, ((0, 0), (r, r), (r, r), (0, 0)), mode="reflect")


def _select_shift(x: jax.Array, shift: jax.Array, axis: int, size: int) -> jax.Array:
    """``x[i]`` cut to ``[shift[i], shift[i] + size)`` along ``axis``: a
    select over every static slice of that length, elementwise over N."""
    out = jax.lax.slice_in_dim(x, 0, size, axis=axis)
    for s in range(1, x.shape[axis] - size + 1):
        window = jax.lax.slice_in_dim(x, s, s + size, axis=axis)
        out = jnp.where(shift == s, window, out)
    return out


@partial(jax.jit, static_argnames=("crop_size",))
def batch_translate_crop(
    padded: jax.Array, key: jax.Array, crop_size: int
) -> jax.Array:
    """Random (sy, sx) crop of ``crop_size`` from padded images — one
    independent integer shift per image (reference batch_crop,
    dataset.py:43-69).

    A shift is one of ``2r + 1`` static values per axis, so the crop is a
    select over static slices of the whole array, rows first and columns
    second: ``2 (2r + 1)`` slices and two loop fusions on the TPU, no
    gather. The price is the rows-only intermediate, ``crop_size /
    (crop_size + 2r)`` of the input's bytes, alive for the call. A select
    copies values, so the result equals
    ``padded[i, sy[i]:sy[i]+c, sx[i]:sx[i]+c]`` in every bit, for any
    dtype; ``sy`` and ``sx`` are the draws they always were, so a seeded or
    resumed run keeps its augmentation stream."""
    n, h = padded.shape[:2]
    r2 = h - crop_size  # == 2r
    ky, kx = jax.random.split(key)
    sy = jax.random.randint(ky, (n,), 0, r2 + 1).reshape(n, 1, 1, 1)
    sx = jax.random.randint(kx, (n,), 0, r2 + 1).reshape(n, 1, 1, 1)
    rows = _select_shift(padded, sy, 1, crop_size)
    return _select_shift(rows, sx, 2, crop_size)


def batch_cutout(images: jax.Array, key: jax.Array, size: int) -> jax.Array:
    """Zero a random size x size square per image (reference
    make_random_square_masks + batch_cutout, dataset.py:74-98)."""
    n, h, w, c = images.shape
    ky, kx = jax.random.split(key)
    cy = jax.random.randint(ky, (n, 1, 1, 1), 0, h - size + 1)
    cx = jax.random.randint(kx, (n, 1, 1, 1), 0, w - size + 1)
    ys = jnp.arange(h).reshape(1, h, 1, 1)
    xs = jnp.arange(w).reshape(1, 1, w, 1)
    in_square = (
        (ys >= cy) & (ys < cy + size) & (xs >= cx) & (xs < cx + size)
    )
    return jnp.where(in_square, 0.0, images)


@partial(
    jax.jit,
    static_argnames=("translate", "cutout", "altflip", "flip", "crop_size"),
)
def augment_epoch(
    preflipped_padded: jax.Array,
    key: jax.Array,
    epoch: jax.Array,
    *,
    crop_size: int,
    flip: bool = True,
    translate: int = 2,
    cutout: int = 0,
    altflip: bool = True,
) -> jax.Array:
    """Augment the ENTIRE training set for one epoch in one compiled
    program of dense ops (no loop over images: see the module docstring).

    Input is the epoch-0-preprocessed tensor: normalized, pre-flipped (if
    ``flip``), reflect-padded (if ``translate``) — the reference caches
    exactly this (dataset.py:191-201). Per epoch this applies the random
    translate-crop, the altflip whole-set flip on odd epochs (or fresh
    random flips when not altflip), and cutout."""
    k_crop, k_flip, k_cut = jax.random.split(key, 3)
    images = preflipped_padded
    if translate > 0:
        with jax.named_scope("augment/crop"):
            images = batch_translate_crop(images, k_crop, crop_size)
    if flip:
        with jax.named_scope("augment/flip"):
            if altflip:
                images = jax.lax.cond(
                    epoch % 2 == 1,
                    lambda x: x[:, :, ::-1, :],
                    lambda x: x,
                    images,
                )
            else:
                images = batch_flip_lr(images, k_flip)
    if cutout > 0:
        with jax.named_scope("augment/cutout"):
            images = batch_cutout(images, k_cut, cutout)
    return images
