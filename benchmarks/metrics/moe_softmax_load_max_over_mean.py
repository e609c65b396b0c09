"""The fullest held expert's (row, expert) pairs over the mean of the held
experts', a mean over the window's steps and the layers, under the softmax
router: the step counters ``moe_load_max`` (each layer's fullest expert,
summed over the layers) and ``moe_pairs`` (ops/moe.py), as the program
fetched them with each epoch's sums. 1 is a perfectly even routing. A quarter
of the rows are the mask's one id: where the residual stream of such rows is
the embedding's alone they choose alike, and this reads several. Nothing to
read where the job kept no such counters."""


def read(obs):
    moe = obs.get("moe_softmax")
    if not moe or not moe["moe_pairs"]:
        return None
    return moe["moe_load_max"] * moe["experts_here"] / moe["moe_pairs"]
