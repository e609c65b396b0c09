"""The fullest held expert's (token, expert) pairs over the mean of the held
experts', a mean over the window's steps and the LatentMoE layers: the step
counters ``moe_load_max`` (each layer's fullest expert, summed over the
layers) and ``moe_pairs`` (ops/moe.py), as the program fetched them with each
epoch's sums. 1 is a perfectly even routing; the grouped products' tiles and
an expert-parallel deployment's slowest chip follow the fullest."""


def read(obs):
    moe = obs.get("moe")
    if not moe or not moe["moe_pairs"]:
        return None
    return moe["moe_load_max"] * moe["experts_here"] / moe["moe_pairs"]
