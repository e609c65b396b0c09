"""Density ladders, per-layer allocations and cyclic epoch schedules
(host-side math).

Parity targets: ``generate_densities`` (/root/reference/utils/
harness_utils.py:117-145) and ``generate_cyclical_schedule``
(harness_utils.py:159-245). The reference's cyclic schedule is broken as
called — `cyclic_harness.py:175` passes `epochs_per_level=` to a `(cfg)`
signature and TypeErrors whenever num_cycles > 1 (SURVEY.md §2.1) — so here
the function takes explicit arguments and works.

The per-layer allocators (``erk_densities``/``balanced_densities``) live
here too — they are pure budget math over layer shapes, not criteria.
"""

from __future__ import annotations

from ..ops.masking import PyTree, mask_layers

# "nm" is magnitude IMP + N:M projection (criteria.prune_nm): same
# geometric ladder as "mag".
ITERATIVE_METHODS = ("mag", "random_erk", "random_balanced", "nm")
PAI_METHODS = ("er_erk", "er_balanced", "synflow", "snip")


def _layer_sizes(masks: PyTree) -> list[tuple[str, tuple, int]]:
    """[(name, shape, numel)] per prunable layer, in traversal order. A
    stacked kernel ``[layers, in, out]`` (ops/masking.py::is_prunable_path:
    the routed experts) is ``layers`` layers of shape ``[in, out]`` named
    ``.../experts/kernel_up[e]``: ERK's ``sum(shape) / numel`` and the
    balanced budget are per expert, as they would be for that many Dense
    layers, and a level's per-layer sparsity log reads per expert."""
    return mask_layers(masks)


def erk_densities(masks: PyTree, density: float) -> dict[str, float]:
    """ERK allocation: layer density ∝ sum(kernel shape)/numel, scaled by a
    global factor C so the total kept-parameter budget hits ``density``
    (reference pruning_utils.py:102-127, 357-371).

    Layers whose scaled density exceeds 1.0 are pinned dense and the excess
    budget is REDISTRIBUTED over the remaining layers by recomputing C
    (iterated to a fixed point — a redistribution can push further layers
    over 1.0). The reference clamps without redistributing, silently keeping
    fewer parameters than the requested budget at high densities; at
    moderate densities (nothing clamps) the two are identical.

    Note: the reference computes the fc layer's shape sum through its
    Conv1dMask (out, in, 1) representation, adding a stray +1; we use the
    true (in, out) Dense shape."""
    layers = _layer_sizes(masks)
    raw = {name: sum(shape) / numel for name, shape, numel in layers}
    sizes = {name: numel for name, _, numel in layers}
    budget = density * sum(sizes.values())
    pinned: set[str] = set()
    c = 0.0
    while True:
        rest = [name for name, _, _ in layers if name not in pinned]
        remaining = budget - sum(sizes[name] for name in pinned)
        denom = sum(raw[name] * sizes[name] for name in rest)
        c = remaining / denom if denom > 0 else 0.0
        overflow = [name for name in rest if c * raw[name] > 1.0]
        if not overflow or not rest:
            break
        pinned.update(overflow)
    return {
        name: 1.0 if name in pinned else float(min(max(c * raw[name], 0.0), 1.0))
        for name, _, _ in layers
    }


def balanced_densities(masks: PyTree, density: float) -> dict[str, float]:
    """Balanced allocation: equal kept-parameter count X = density*total/L per
    layer; layers smaller than X saturate at density 1 and their surplus is
    redistributed (reference pruning_utils.py:298-327, 388-407, including its
    L - i divisor)."""
    layers = _layer_sizes(masks)
    total = sum(numel for _, _, numel in layers)
    L = len(layers)
    X = density * total / L
    out = {}
    for i, (name, _, numel) in enumerate(layers):
        if X / numel < 1.0:
            out[name] = X / numel
        else:
            out[name] = 1.0
            diff = X - numel
            X = X + diff / (L - i)
    return out


def generate_densities(
    prune_method: str,
    target_sparsity: float,
    prune_rate: float,
    current_sparsity: float = 0.0,
) -> list[float]:
    """Geometric density ladder d_{i+1} = d_i * (1 - prune_rate) down to the
    target for iterative methods; single step for PaI; [1.0] for dense."""
    if prune_method in ITERATIVE_METHODS:
        densities = []
        current_density = 1.0 - current_sparsity
        target_density = 1.0 - target_sparsity
        # Epsilon guards float dust: 0.8 * 0.8 = 0.6400000000000001 must not
        # spawn a spurious extra level past an exact target of 0.64.
        while current_density > target_density * (1.0 + 1e-9):
            densities.append(current_density)
            current_density *= 1.0 - prune_rate
        densities.append(current_density)
        return densities
    if prune_method in PAI_METHODS:
        return [1.0 - target_sparsity]
    if prune_method == "just dont":
        return [1.0]
    raise ValueError(f"Unknown pruning method: {prune_method}")


def generate_cyclical_schedule(
    epochs_per_level: int, num_cycles: int, strategy: str = "constant"
) -> list[int]:
    """Split an epoch budget across training cycles by strategy, then trim so
    the total never exceeds the budget."""
    if num_cycles <= 1:
        return [epochs_per_level]

    if strategy == "linear_decrease":
        step = epochs_per_level / (num_cycles * (num_cycles + 1) / 2)
        epochs = [int(step * (num_cycles - i)) for i in range(num_cycles)]
    elif strategy == "linear_increase":
        step = epochs_per_level / (num_cycles * (num_cycles + 1) / 2)
        epochs = [int(step * (i + 1)) for i in range(num_cycles)]
    elif strategy == "exponential_decrease":
        factor = 0.5 ** (1 / (num_cycles - 1))
        total = sum(factor**i for i in range(num_cycles))
        epochs = [int(epochs_per_level * factor**i / total) for i in range(num_cycles)]
    elif strategy == "exponential_increase":
        factor = 2 ** (1 / (num_cycles - 1))
        total = sum(factor**i for i in range(num_cycles))
        epochs = [int(epochs_per_level * factor**i / total) for i in range(num_cycles)]
    elif strategy == "cyclic_peak":
        mid = num_cycles // 2
        inc = epochs_per_level / (mid * (mid + 1) / 2)
        dec = epochs_per_level / ((num_cycles - mid) * (num_cycles - mid + 1) / 2)
        epochs = [int(inc * (i + 1)) for i in range(mid)]
        epochs += [int(dec * (num_cycles - i)) for i in range(mid, num_cycles)]
    elif strategy == "alternating":
        high = epochs_per_level // (num_cycles // 2 + num_cycles % 2)
        low = epochs_per_level // (2 * (num_cycles // 2 + num_cycles % 2))
        epochs = [high if i % 2 == 0 else low for i in range(num_cycles)]
    elif strategy == "plateau":
        inc_cycles = num_cycles // 2
        plateau_cycles = num_cycles - inc_cycles
        inc = epochs_per_level / (inc_cycles * (inc_cycles + 1) / 2)
        epochs = [int(inc * (i + 1)) for i in range(inc_cycles)]
        epochs += [epochs_per_level // num_cycles] * plateau_cycles
    elif strategy == "constant":
        epochs = [epochs_per_level // num_cycles] * num_cycles
    else:
        raise ValueError(f"Unknown cyclic strategy: {strategy}")

    total = sum(epochs)
    if total > epochs_per_level:
        # Floor-rescale; sum(floor(e*scale)) <= budget always holds after
        # this, so no further correction is needed.
        scale = epochs_per_level / total
        epochs = [int(e * scale) for e in epochs]

    # Int truncation can produce 0-epoch cycles (e.g. exponential_decrease
    # with a small budget) — the harness would silently run no-op cycles.
    # Every cycle trains at least 1 epoch; overflow is trimmed from the
    # largest cycles, which terminates because budget >= num_cycles.
    if epochs_per_level < num_cycles:
        raise ValueError(
            f"epochs_per_level={epochs_per_level} < num_cycles={num_cycles}: "
            "cannot give every cycle at least one epoch"
        )
    epochs = [max(1, e) for e in epochs]
    while sum(epochs) > epochs_per_level:
        epochs[epochs.index(max(epochs))] -= 1
    return epochs
