"""Median host time the epoch loop spends outside train_epoch, per epoch of
the window: from one train_epoch's return to the next one's entry or, after a
level's last epoch, to train_one_level's return. Eval, metrics, console, and
the dispatch of the next epoch's shuffle and augmentation fall in it."""

from statistics import median


def read(obs):
    t0, t1 = obs["window"]
    epochs = obs["spans"].named("train_epoch", t0, t1)
    if not epochs:
        return None
    # What ends an epoch's gap: the next entry to train_epoch (the window's
    # last boundary is one that never got a span) or the level's return.
    ends = sorted(
        [s.start for s in epochs]
        + [s.end for s in obs["spans"].named("train_one_level", t0, t1)]
        + list(obs["boundaries"])
    )
    gaps = []
    for epoch in epochs:
        after = [t for t in ends if t >= epoch.end]
        if after:
            gaps.append(after[0] - epoch.end)
    return 1e3 * median(gaps) if gaps else None
