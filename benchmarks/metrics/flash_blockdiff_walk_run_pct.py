"""Of the grid steps that a head's block-diffusion attention kernels walked,
the share that ran a (query block, key block) pair of scores: the step
counters ``flash_steps_run`` and ``flash_steps_walked`` (models/sdar.py, from
ops/flash.py's walk; each layer's, summed over the layers), as the program
fetched them with each epoch's sums. 100 is a grid with no dead step; a square
grid over this cell's layout would read about 12. Nothing to read where the
program keeps no such counters."""


def read(obs):
    counted = obs.get("moe_softmax") or {}
    if not counted.get("flash_steps_walked"):
        return None
    return 100.0 * counted["flash_steps_run"] / counted["flash_steps_walked"]
