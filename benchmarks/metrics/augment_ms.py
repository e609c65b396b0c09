"""Device time of one epoch's augmentation: the durations of the device
loader's augmentation program in the trace (the name is the cell's
``augment_program``), per execution; one execution crops and flips a whole
epoch's images. Nothing to read where the cell names no such program or the
trace holds none."""


def read(obs):
    if obs["trace"] is None or "augment_program" not in obs:
        return None
    runs = [
        d
        for name, durations in obs["trace"]["modules"].items()
        if name.startswith(obs["augment_program"])
        for d in durations
    ]
    return 1e3 * sum(runs) / len(runs) if runs else None
