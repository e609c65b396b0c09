"""Experiment management: directories, seeds, config snapshots, metrics.

Rebuilds the reference's harness_utils
(/root/reference/utils/harness_utils.py): ``gen_expt_dir`` (config-encoding
prefix + uuid/timestamp, fixed subdir layout, :49-94), ``set_seed`` (:97-114),
``save_config`` (:148-156), the pandas CSV metric channels
(standard_pruning_harness.py:243-269), the rich console panels
(harness_utils.py:248-351), and a ``resume_experiment`` that actually works
(the reference's is called with the wrong arity, run_experiment.py:61 —
SURVEY.md §5 "Failure detection").
"""

from __future__ import annotations

import dataclasses
import os
import random
import uuid
from datetime import datetime
from pathlib import Path
from typing import Optional

import numpy as np
import pandas as pd
import yaml

from ..config.schema import MainConfig, config_to_dict

SUBDIRS = ("checkpoints", "metrics", "metrics/level_wise_metrics", "artifacts")


def expt_prefix(cfg: MainConfig) -> str:
    """Config-encoding experiment name (reference builds the same kind of
    stub from dataset/model/prune knobs, harness_utils.py:64-82)."""
    pp = cfg.pruning_params
    parts = [
        cfg.dataset_params.dataset_name.lower(),
        cfg.model_params.model_name,
        pp.prune_method.replace(" ", "_"),
        pp.training_type,
        f"sp{pp.target_sparsity:g}",
        f"seed{cfg.experiment_params.seed}",
    ]
    if cfg.cyclic_training.num_cycles > 1:
        parts.append(f"cyc{cfg.cyclic_training.num_cycles}")
    return "_".join(parts)


def gen_expt_dir(cfg: MainConfig) -> tuple[str, str]:
    """(prefix, expt_dir); creates the fixed subdir layout
    (harness_utils.py:87-94)."""
    prefix = expt_prefix(cfg)
    stamp = datetime.now().strftime("%Y%m%d_%H%M%S")
    unique = f"{prefix}__{stamp}_{uuid.uuid4().hex[:8]}"
    expt_dir = Path(cfg.experiment_params.base_dir) / unique
    for sub in SUBDIRS:
        (expt_dir / sub).mkdir(parents=True, exist_ok=True)
    return prefix, str(expt_dir)


def resume_experiment(cfg: MainConfig) -> tuple[str, str, int]:
    """(prefix, expt_dir, resume_level) for an existing experiment dir.

    Requires ``experiment_params.resume_experiment_stuff`` with the dir name
    under base_dir. Returns the level to CONTINUE FROM (training resumes at
    ``resume_level``, consuming ``model_level_{resume_level-1}``) — the
    reference intended exactly this but the code path was unreachable
    (harness_utils.py:368-386)."""
    stuff = cfg.experiment_params.resume_experiment_stuff
    if stuff is None or not stuff.resume_expt_name:
        raise ValueError(
            "resume_experiment=true requires "
            "experiment_params.resume_experiment_stuff.resume_expt_name"
        )
    expt_dir = Path(cfg.experiment_params.base_dir) / stuff.resume_expt_name
    if not expt_dir.exists():
        raise FileNotFoundError(f"cannot resume: {expt_dir} does not exist")
    for sub in SUBDIRS:
        (expt_dir / sub).mkdir(parents=True, exist_ok=True)
    prefix = stuff.resume_expt_name.split("__")[0]
    return prefix, str(expt_dir), stuff.resume_level


def set_seed(seed: int, deterministic: bool = False) -> None:
    """Host-side seeding (reference set_seed, harness_utils.py:97-114).
    Device-side randomness is explicit-key JAX PRNG and needs no global
    seeding; this covers numpy/python used by data pipelines."""
    os.environ["PYTHONHASHSEED"] = str(seed)
    random.seed(seed)
    np.random.seed(seed)
    del deterministic  # XLA is deterministic-by-default for our op set


def config_fingerprint(cfg: MainConfig) -> str:
    """Short content hash of the TRAINING-RELEVANT config, used to stamp the
    mid-level checkpoint slot: a resume whose config diverged (lr, epoch
    budget, loader type, ...) must not silently restore mid-trajectory state
    trained under the old config.

    Excluded from the hash: the resume knobs themselves (a resumed run
    flips ``resume_experiment`` and MUST still match its own slot) and the
    serve group (serving knobs don't touch training)."""
    import hashlib
    import json

    d = config_to_dict(cfg)
    ep = d.get("experiment_params") or {}
    ep.pop("resume_experiment", None)
    ep.pop("resume_experiment_stuff", None)
    d.pop("serve", None)
    blob = json.dumps(d, sort_keys=True, default=str).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def save_config(expt_dir: str, cfg: MainConfig) -> Path:
    """Snapshot the composed config (reference save_config,
    harness_utils.py:148-156)."""
    out = Path(expt_dir) / "expt_config.yaml"
    with open(out, "w") as f:
        yaml.safe_dump(config_to_dict(cfg), f, sort_keys=False)
    return out


class MetricsLogger:
    """The reference's CSV metric channels (standard_pruning_harness.py:
    243-269): per-level ``metrics/level_wise_metrics/level_{L}_metrics.csv``
    rows of epoch/train/test stats, plus an append-mode
    ``metrics/{prefix}_summary.csv`` with one row per level."""

    def __init__(self, expt_dir: str, prefix: str):
        self.expt_dir = Path(expt_dir)
        self.prefix = prefix
        self.level_rows: list[dict] = []

    def log_epoch(self, row: dict) -> None:
        self.level_rows.append(dict(row))

    def finish_level(self, level: int, summary_extra: Optional[dict] = None) -> dict:
        """Write the level CSV, append the summary row, reset the buffer.
        File writes are host-0-only (the reference's rank-0 logging rule,
        standard_pruning_harness.py:243); every host still gets the summary
        dict back."""
        import jax

        df = pd.DataFrame(self.level_rows)
        summary = {}
        if len(df):
            last = df.iloc[-1].to_dict()
            summary.update(last)
            if "test_acc" in df:
                summary["max_test_acc"] = float(df["test_acc"].max())
        # After the row merge: pandas floatifies ints (level 0 -> 0.0).
        summary["level"] = level
        summary.update(summary_extra or {})

        if jax.process_index() == 0:
            level_dir = self.expt_dir / "metrics" / "level_wise_metrics"
            level_dir.mkdir(parents=True, exist_ok=True)
            df.to_csv(level_dir / f"level_{level}_metrics.csv", index=False)
            summary_path = self.expt_dir / "metrics" / f"{self.prefix}_summary.csv"
            pd.DataFrame([summary]).to_csv(
                summary_path,
                mode="a",
                header=not summary_path.exists(),
                index=False,
            )
        self.level_rows = []
        return summary

    def log_level_timing(self, row: dict) -> None:
        """Append one level's ``[time]`` line as a row of
        ``metrics/level_timing.csv`` (``tracing.timing_row``; the columns are
        fixed, so a resumed run appends to the same file). Host 0 only."""
        import csv

        import jax

        if jax.process_index() != 0:
            return
        path = self.expt_dir / "metrics" / "level_timing.csv"
        path.parent.mkdir(parents=True, exist_ok=True)
        new = not path.exists()
        with open(path, "a", newline="") as fh:
            writer = csv.DictWriter(fh, fieldnames=list(row))
            if new:
                writer.writeheader()
            writer.writerow(row)


def display_training_info(cfg: MainConfig, level: int, density: float) -> None:
    """Rich config/level panels (reference display_training_info,
    harness_utils.py:248-351); degrades to prints when rich is absent."""
    try:
        from rich.console import Console
        from rich.panel import Panel
        from rich.table import Table
    except ImportError:
        # Only a MISSING rich degrades to the plain print — a render error
        # with rich present propagates (it would mean the config itself is
        # broken, which must not be swallowed).
        print(f"[level {level}] density={density:.4f}")
        return

    console = Console()
    t = Table(title=f"Level {level} — density {density:.4f}")
    t.add_column("knob")
    t.add_column("value")
    for section in (
        "dataset_params",
        "model_params",
        "pruning_params",
        "optimizer_params",
    ):
        sub = getattr(cfg, section)
        for f in dataclasses.fields(sub):
            t.add_row(f"{section}.{f.name}", str(getattr(sub, f.name)))
    console.print(Panel(t, border_style="cyan", expand=False))
