"""Training images consumed over the whole window's wall-clock: train epochs,
the eval after each, logging, and in a ladder prune, rewind and checkpoints.
Not the step's rate. Host clock. Nothing to read in a cell that trains nothing."""


def read(obs):
    if "images" not in obs:
        return None
    t0, t1 = obs["window"]
    return obs["images"] / (t1 - t0)
