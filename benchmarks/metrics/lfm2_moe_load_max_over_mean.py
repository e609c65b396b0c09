"""The fullest held expert's (token, expert) pairs over the mean of the held
experts', a mean over the window's steps and the routed layers, under the
sigmoid router with its selection bias at zero: the step counters
``moe_load_max`` (each layer's fullest expert, summed over the layers) and
``moe_pairs`` (ops/moe.py), as the program fetched them with each epoch's
sums. 1 is a perfectly even routing; the grouped products' tiles and an
expert-parallel deployment's slowest chip follow the fullest. Nothing to read
where the job kept no such counters."""


def read(obs):
    moe = obs.get("lfm2_moe")
    if not moe or not moe["moe_pairs"]:
        return None
    return moe["moe_load_max"] * moe["experts_here"] / moe["moe_pairs"]
