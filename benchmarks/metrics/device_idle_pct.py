"""Share of the traced stretch in which no operation ran on the device:
1 - union of the device's operation intervals / the stretch."""


def read(obs):
    if obs["trace"] is None:
        return None
    return 100.0 * (1.0 - obs["trace"]["busy_s"] / obs["trace"]["window_s"])
