"""Finds what a cell needs by the names in ``BENCHMARK.json``.

A configuration is ``configs/<name>.json``, a cell ``workloads/<name>.json``,
a job ``jobs/<name>.py`` (a module with ``run(ctx)``), a metric
``metrics/<name>.py`` (a module with ``read(obs)``), and the chips' peaks are
``peaks.json``. A later PR adds files and entries and edits none: an unknown
name is an error here, and a new file is found without a change to this one.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class UnknownName(KeyError):
    pass


def _path(bench_dir: Path, kind: str, name: str, suffix: str) -> Path:
    if not _NAME.match(name):
        raise UnknownName(f"{kind} name {name!r} is not a name")
    path = Path(bench_dir) / kind / f"{name}{suffix}"
    if not path.is_file():
        raise UnknownName(f"no {kind[:-1]} named {name!r}: {path} does not exist")
    return path


def _json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _module(path: Path, kind: str) -> ModuleType:
    # Metric names may hold '.' and '-', so a module is loaded by its path.
    ident = re.sub(r"\W", "_", f"benchmarks_{kind}_{path.stem}")
    spec = importlib.util.spec_from_file_location(ident, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_benchmark(repo_root: Path) -> dict:
    return _json(Path(repo_root) / "BENCHMARK.json")


def load_workload(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _json(_path(bench_dir, "workloads", name, ".json"))


def load_config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _json(_path(bench_dir, "configs", name, ".json"))


def load_job(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    job = _module(_path(bench_dir, "jobs", name, ".py"), "jobs")
    if not callable(getattr(job, "run", None)):
        raise UnknownName(f"job {name!r} has no run(ctx)")
    return job


def load_metric(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    metric = _module(_path(bench_dir, "metrics", name, ".py"), "metrics")
    if not callable(getattr(metric, "read", None)):
        raise UnknownName(f"metric {name!r} has no read(obs)")
    return metric


def load_peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """Peaks of exactly that ``device_kind``. A kind nobody looked up is an
    error, never a guess."""
    table = _json(Path(bench_dir) / "peaks.json")["device_kinds"]
    if device_kind not in table:
        raise UnknownName(
            f"device kind {device_kind!r} is not in peaks.json "
            f"(known: {sorted(table)}): look its peaks up and add it"
        )
    return table[device_kind]


def cell_entry(benchmark: dict, name: str) -> dict:
    for cell in benchmark["workloads"]:
        if cell["name"] == name:
            return cell
    raise UnknownName(
        f"no workload named {name!r} in BENCHMARK.json "
        f"(known: {[c['name'] for c in benchmark['workloads']]})"
    )


def metrics_for(benchmark: dict, cell: str, traced: bool) -> list[dict]:
    """The entries of ``per_layer`` (traced) or ``end_to_end`` whose
    ``workloads`` key, where there is one, lists the cell."""
    entries = benchmark["per_layer" if traced else "end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]
