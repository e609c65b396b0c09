"""A benchmark at tiny size in a scratch directory, for the CPU tests: the
jobs, metrics and peaks are the real files, copied; configurations and cells
are written here. That a copy with new files runs without an edit to run.py
or registry.py is itself what the data-driven tests show."""

from __future__ import annotations

import argparse
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REAL = json.loads((BENCH.parent / "BENCHMARK.json").read_text())

TINY_OVERRIDES = [
    "dataset_params.dataloader_type=synthetic",
    "dataset_params.total_batch_size=32",
    "dataset_params.synthetic_num_train=64",
    "dataset_params.synthetic_num_test=48",
]
# Limits wide open: the tests that need one set it.
OPEN_LIMITS = {
    "nonfinite_losses": ["max", 0],
    "images_miscounted": ["max", 0],
    "param_change": ["min", 1e-6],
    "ladder_excess_weights": ["max", 2],
    "eval_loss_gap": ["max", 1e9],
    "eval_probe_loss_gap": ["max", 1e9],
    "train_loss_gap": ["max", 1e9],
    "momentum_norm_gap": ["max", 1e9],
    "update_norm_gap": ["max", 1e9],
}


FOLLOWED = ("train_loss_gap", "momentum_norm_gap", "update_norm_gap")


def make_bench(root: Path) -> tuple[Path, Path]:
    """(repo_root, bench_dir) of a scratch benchmark with a tiny ladder cell
    and a tiny dense cell."""
    bench = root / "benchmarks"
    for sub in ("jobs", "metrics"):
        shutil.copytree(BENCH / sub, bench / sub)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    (bench / "configs").mkdir()
    (bench / "workloads").mkdir()
    config = {
        "name": "tiny-resnet18",
        "entry_config": "cifar10_imp",
        "overrides": TINY_OVERRIDES,
    }
    (bench / "configs" / "tiny-resnet18.json").write_text(json.dumps(config))
    cells = {
        "tiny-ladder": {
            "params": {
                "unit": "level", "warmup": 2, "trace_units": 1, "warm_prunes": "window",
                "follow_epoch": 1, "step_program": "jit_scan_chunk",
                "overrides": [
                    "experiment_params.epochs_per_level=1",
                    "pruning_params.target_sparsity=0.9",
                ],
            },
            "limits": {
                **{k: v for k, v in OPEN_LIMITS.items() if k not in FOLLOWED},
                "mask_oracle_mismatch": ["max", 0],
                "masked_update_gap": ["max", 1e-4],
            },
        },
        "tiny-dense": {
            "params": {
                "unit": "epoch", "warmup": 1, "trace_units": 1, "follow_epoch": 0,
                "step_program": "jit_scan_chunk",
                "overrides": ["experiment_params.epochs_per_level=40"],
            },
            "limits": dict(OPEN_LIMITS),
        },
    }
    for name, cell in cells.items():
        cell["job"] = "imp_ladder"
        (bench / "workloads" / f"{name}.json").write_text(json.dumps(cell))
    benchmark = dict(REAL)
    benchmark["configs"] = [{"name": "tiny-resnet18"}]
    benchmark["workloads"] = [
        {"name": n, "config": "tiny-resnet18", "traffic": n, "chips": 1} for n in cells
    ]
    rename = {"r18-cifar10-imp-ladder": "tiny-ladder", "r50-imagenet-dense-level": "tiny-dense"}
    benchmark["end_to_end"] = [
        {**m, **({"workloads": [rename[w] for w in m["workloads"]]} if "workloads" in m else {})}
        for m in REAL["end_to_end"]
    ]
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return root, bench


def args(workload: str, seed: int = 7, seconds: float = 0.5, trace: int = 0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds, trace=trace)
