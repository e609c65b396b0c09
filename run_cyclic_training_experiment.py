#!/usr/bin/env python
"""Cyclic-training pruning experiment CLI (reference:
/root/reference/run_cyclic_training_experiment.py).

Same outer structure as run_experiment.py but trains each sparsity level in
``cyclic_training.num_cycles`` cycles with the LR schedule re-warmed each
cycle (strategy knob splits the epoch budget — 8 strategies,
turboprune_tpu/pruning/densities.py:generate_cyclical_schedule).
"""

from __future__ import annotations

import sys

from run_experiment import parse_args, setup


def main(argv=None) -> int:
    args = parse_args(argv if argv is not None else sys.argv[1:])
    cfg = setup(args)

    from turboprune_tpu.driver import run_cyclic
    from turboprune_tpu.parallel import is_primary

    expt_dir, summaries = run_cyclic(cfg)
    if is_primary():
        print(f"\nCyclic experiment complete: {expt_dir}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
