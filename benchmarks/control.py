#!/usr/bin/env python3
"""The control of ``correct``, at a cell's own size on the chip.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed it runs the cell as ``run.py`` does, with a short window, and
then puts the plain reference computed with float8 operands (the nearest
precision below the bfloat16 the configurations state) in the program's place:
the same eval images and final state, the same followed steps from the same
starting state, the same comparisons. It prints the
sound run's numbers and the control's side by side; the limits in the cell's
file sit between them. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from benchmarks import run  # noqa: E402


def control_numbers(final: dict) -> dict:
    """The numbers of ``correct`` that a precision moves, with the float8
    reference where the program was: its forward pass over the eval set, and
    its SGD over the followed steps."""
    from benchmarks import correct
    from benchmarks.reference import resnet as reference
    from benchmarks.reference import sgd

    state = final["state"]
    low = correct.reference_logits(
        state.params, state.masks, state.batch_stats, final["images"],
        quantize=reference.fp8_operand,
    )
    pick, probe = final["pick"], final["probe"]
    ref_loss = correct.mean_loss(final["ref"], final["labels"])
    numbers = {
        "eval_loss_gap": abs(correct.mean_loss(low, final["labels"]) - ref_loss)
        / max(ref_loss, final["loss_floor"]),
        "eval_probe_loss_gap": correct.probe_gap(
            correct.row_losses(low[pick], probe),
            correct.row_losses(final["ref"][pick], probe),
            final["loss_floor"],
        ),
    }
    if final["ref_train"] is not None:
        f = final["followed"]
        low_train = sgd.follow(
            final["recipe"], f["params"], f["buf"], f["masks"], f["batch_stats"],
            f["images"], f["labels"], f["first_step"], quantize=reference.fp8_operand,
        )
        numbers.update(correct.training_gaps(f, low_train, final["ref_train"]))
    return numbers


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--seconds", type=float, default=5.0)
    args = p.parse_args(argv)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        got: dict = {}

        def after(result, got=got):
            got["control"] = control_numbers(result["final"])
            got["sound"] = {c.name: c.value for c in result["checks"]}
            result["final"].clear()

        one = argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds, trace=0
        )
        line = run.run_cell(one, t_start=time.perf_counter(), after=after)
        if line is None:
            return run.EXIT_NO_DEVICE
        rows.append((seed, line["correct"], got))
        run.say(f"[control] seed {seed}: run correct={line['correct']} sound={got['sound']} control={got['control']}")
        gc.collect()
    for name in rows[0][2]["control"]:
        sound = [g["sound"][name] for _, _, g in rows]
        low = [g["control"][name] for _, _, g in rows]
        run.say(
            f"[control] {name}: sound runs' largest {max(sound):.6g}, "
            f"control's smallest {min(low):.6g} over {len(rows)} seeds"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
