"""The comparisons that decide ``correct``, and the control that must fail.

Each number compared has a limit of its own, kept in the cell's file
(``limits``), and is printed beside it in every run. A limit is
``["max", x]`` (the number may not exceed x) or ``["min", x]`` (it may not
fall under x). ``PERF.md`` gives the readings each was set from.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import resnet as reference

# Rows of the reference forward per call at 224x224: what a float32 ResNet50
# holds beside the program's resident state on a 16 GB chip. Smaller images
# go in blocks of as many pixels.
REFERENCE_ROWS_224 = 64


@dataclass
class Check:
    name: str
    value: float
    sense: str  # "max" or "min"
    limit: float

    @property
    def ok(self) -> bool:
        if not math.isfinite(self.value):
            return False
        return self.value <= self.limit if self.sense == "max" else self.value >= self.limit

    def line(self) -> str:
        rel = "<=" if self.sense == "max" else ">="
        return (
            f"[correct] {self.name} = {self.value:.6g} (limit {rel} "
            f"{self.limit:.6g}): {'ok' if self.ok else 'NOT CORRECT'}"
        )


def judge(values: dict[str, float], limits: dict[str, list]) -> list[Check]:
    """Every number measured has to have a limit, and every limit a number."""
    if set(values) != set(limits):
        raise KeyError(
            f"numbers compared {sorted(values)} and limits {sorted(limits)} differ"
        )
    return [
        Check(name, float(values[name]), limits[name][0], float(limits[name][1]))
        for name in sorted(values)
    ]


# ------------------------------------------------------------------ oracle
def _is_none(x) -> bool:
    return x is None


def _prunable(tree, masks) -> list:
    """The leaves of ``tree`` where the mask tree, which mirrors it with None
    at what is never pruned, holds a mask; each paired with its mask."""
    pairs = zip(jax.tree.leaves(tree), jax.tree.leaves(masks, is_leaf=_is_none))
    return [(x, m) for x, m in pairs if m is not None]


def magnitude_oracle(params: dict, masks: dict, density: float) -> np.ndarray:
    """Global magnitude pruning in numpy, from the reference's definition
    (pruning_utils.py:61-89): the k-th smallest |w * m| over all prunable
    weights, k = (1 - density) * N, and ``mask = score > threshold`` — so
    weights tied with the threshold go too. One flat vector in the order of
    ``flat_masks``."""
    flat = np.concatenate(
        [
            np.abs(np.asarray(p) * np.asarray(m, np.float32)).reshape(-1)
            for p, m in _prunable(params, masks)
        ]
    )
    k = int((1.0 - density) * flat.size)
    if k < 1:
        return flat > -1.0
    return flat > np.partition(flat, k - 1)[k - 1]


def flat_masks(masks: dict) -> np.ndarray:
    # None is no leaf to jax.tree, so these are the masks alone, in tree order.
    return np.concatenate(
        [np.asarray(m).reshape(-1).astype(bool) for m in jax.tree.leaves(masks)]
    )


def ladder_density(level: int, prune_rate: float) -> float:
    """Density of a level of the geometric ladder, multiplied up as the
    recipe does (0.8 * 0.8 is 0.6400000000000001, and k is a floor)."""
    density = 1.0
    for _ in range(level):
        density *= 1.0 - prune_rate
    return density


# ----------------------------------------------------------------- forward
def reference_logits(
    params: dict,
    masks: dict,
    batch_stats: dict,
    images: jax.Array,
    quantize: Optional[Callable] = None,
) -> np.ndarray:
    """The plain float32 forward of ``params * masks``, in blocks of rows."""
    weights = reference.masked(
        jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params), masks
    )
    fwd = jax.jit(lambda w, s, x: reference.forward(w, s, x, quantize))
    out = []
    with jax.default_matmul_precision("highest"):
        rows = REFERENCE_ROWS_224 * max(1, 224 // images.shape[1]) ** 2
        for lo in range(0, images.shape[0], rows):
            out.append(np.asarray(fwd(weights, batch_stats, images[lo : lo + rows])))
    return np.concatenate(out)


def logit_gap(logits: np.ndarray, ref: np.ndarray) -> float:
    """For each image the norm of its logits' difference over the norm of
    the reference's logits; of those, the median over the images.

    Used by the tests that hold the reference against the program's model."""
    diff = np.linalg.norm(logits.astype(np.float64) - ref, axis=1)
    return float(np.median(diff / np.linalg.norm(ref.astype(np.float64), axis=1)))


def mean_loss(logits: np.ndarray, labels: np.ndarray) -> float:
    valid = labels >= 0
    rows = np.asarray(
        reference.cross_entropy(jnp.asarray(logits), jnp.asarray(np.maximum(labels, 0)))
    )
    return float(rows[valid].astype(np.float64).sum() / valid.sum())


def relative_change(new: dict, old: dict) -> float:
    """Norm of the parameters' change over the norm of where they started."""
    num = sum(
        float(jnp.sum((jnp.asarray(a, jnp.float32) - jnp.asarray(b, jnp.float32)) ** 2))
        for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))
    )
    den = sum(float(jnp.sum(jnp.asarray(b, jnp.float32) ** 2)) for b in jax.tree.leaves(old))
    return math.sqrt(num / den)


def row_losses(logits: np.ndarray, labels: np.ndarray) -> np.ndarray:
    """Each row's softmax cross-entropy against its label."""
    return np.asarray(
        reference.cross_entropy(jnp.asarray(logits), jnp.asarray(labels)), np.float64
    )


def probe_gap(losses: np.ndarray, ref: np.ndarray, floor: float) -> float:
    """For each sampled image the gap between the program's loss and the
    reference's as a share of the reference's (or of ``floor``, where that is
    larger); of those, the median over the images. The widest single gap
    swings with the state a seed trains to, the median image does not, and a
    lower matmul precision moves every image."""
    return float(np.median(np.abs(losses - ref) / np.maximum(ref, floor)))


# ---------------------------------------------------------------- training
def _leaf_norms(tree) -> np.ndarray:
    return np.array(
        [np.linalg.norm(np.asarray(x, np.float64).reshape(-1)) for x in jax.tree.leaves(tree)]
    )


def norm_gap(got, ref, whole: bool = False) -> float:
    """The gap between the program's norm and the reference's (not the norm
    of their difference), by the worst leaf: measured against the reference's
    norm of that leaf or of the median leaf, whichever is larger, since some
    gradients are all but zero. ``whole`` takes the one norm of all leaves
    together instead."""
    a, b = _leaf_norms(got), _leaf_norms(ref)
    if whole:
        return float(abs(np.linalg.norm(a) - np.linalg.norm(b)) / np.linalg.norm(b))
    return float(np.max(_gaps(a, b)))


def _gaps(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.abs(a - b) / np.maximum(b, np.median(b))


def worst_leaves(got, ref, top: int = 3) -> str:
    """The leaves with the widest gaps, by name, for the run's earlier lines."""
    gaps = _gaps(_leaf_norms(got), _leaf_norms(ref))
    names = [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(ref)]
    order = np.argsort(gaps)[::-1][:top]
    return ", ".join(f"{names[i]} {gaps[i]:.5f}" for i in order)


def tree_change(after, before):
    return jax.tree.map(
        lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64), after, before
    )


def training_gaps(before: dict, program: dict, ref: dict, whole: bool = True) -> dict:
    """The followed steps, program against reference: the mean of the steps'
    losses, the momentum buffers after the last step (the gradients as the
    optimizer got them, each weighted by the momentum's power of its age), and
    the parameters' change over the steps. The norms are of the whole tree:
    by the worst leaf, sound runs on the chip read 0.22 to 0.47 after eight
    steps (PERF.md section 2), which no limit can use."""
    change = lambda after: tree_change(after, before["params"])
    return {
        "train_loss_gap": abs(program["loss"] - ref["loss"]) / abs(ref["loss"]),
        "momentum_norm_gap": norm_gap(program["buf"], ref["buf"], whole),
        "update_norm_gap": norm_gap(change(program["params"]), change(ref["params"]), whole),
    }


def masked_update_gap(before: dict, after: dict, path) -> float:
    """Over the weights the masks hold at zero: the norm of the difference
    between the program's change over the followed steps and the plain
    optimizer's (``path(w, buf)`` gives where it takes them), as a share of
    the latter. Such a weight gets no data gradient, so its path is the
    optimizer's alone (weight decay into the gradient, momentum, each step's
    learning rate) and does not depend on how the live weights' paths drift."""
    diff = size = 0.0
    for (w0, m), (b0, _), (w1, _) in zip(
        _prunable(before["params"], before["masks"]),
        _prunable(before["buf"], before["masks"]),
        _prunable(after["params"], before["masks"]),
    ):
        off = ~np.asarray(m, bool)
        start = np.asarray(w0, np.float64)[off]
        want = path(start, np.asarray(b0, np.float64)[off]) - start
        got = np.asarray(w1, np.float64)[off] - start
        diff += float(np.sum((got - want) ** 2))
        size += float(np.sum(want**2))
    return math.sqrt(diff / size)
