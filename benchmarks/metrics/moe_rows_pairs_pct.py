"""Of the rows of the pair buffer that the routed layers' grouped products
ran, the share that hold a (token, expert) pair: the step counters
``moe_pairs`` and ``moe_rows_run`` (ops/moe.py: the rows handed to the
products, each held expert's pairs on whole tiles, over every round and
layer), as the program fetched them with each epoch's sums, from whichever of
the three routed jobs' dictionaries the cell has. 100 would be no row of
alignment at all; products that ran the whole buffer would read 54 to 62 in
these cells. Nothing to read where the program keeps no such counter."""


def read(obs):
    for name in ("moe", "moe_softmax", "lfm2_moe"):
        counted = obs.get(name) or {}
        if counted.get("moe_rows_run"):
            return 100.0 * counted["moe_pairs"] / counted["moe_rows_run"]
    return None
