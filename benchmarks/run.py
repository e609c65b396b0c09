#!/usr/bin/env python3
"""Runs one cell of the benchmark once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run is a fresh process. It refuses to start without the TPU chips the cell
asks for (there is no CPU fall-back and no option that allows one), sets up and
warms through the program's own entry point, measures for about ``--seconds``,
decides ``correct`` after the window, and prints one JSON object as the last
line of its standard output. Everything else worth reading is on earlier lines.

What belongs to one cell, configuration, job or metric is a file of its own,
found by the name in ``BENCHMARK.json`` (see ``registry.py``); this file knows
none of them.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # before anything heavy is imported

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Optional  # noqa: E402

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from benchmarks import registry  # noqa: E402

EXIT_NO_DEVICE = 3


def say(msg: str) -> None:
    print(msg, flush=True)


@dataclass
class Context:
    """What a job gets: the cell's and the configuration's files, the
    arguments of the run, and the recorders."""

    cell: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    scratch: Path
    trace_dir: Path
    spans: Any
    say: Any = say


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def device_report(platform: str, chips: int) -> Optional[dict]:
    """The devices as JAX reports them, or None where they are not what the
    cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < chips:
        say(
            f"[run] refusing to run: the cell needs {chips} {platform} device(s), "
            f"JAX reports {len(devices)} x {devices[0].platform} "
            f"({devices[0].device_kind})"
        )
        return None
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def run_cell(
    args,
    platform: str = "tpu",
    repo_root: Path = REPO_ROOT,
    bench_dir: Path = registry.BENCH_DIR,
    t_start: float = T_START,
    after=None,
) -> Optional[dict]:
    """One run of one cell; the result line as a dict, or None where the
    devices are not the cell's. ``platform`` is for the tests only: ``main``
    always asks for a TPU. ``after(result)`` is for the control
    (``control.py``): it gets what the job returned, while its state lives."""
    benchmark = registry.load_benchmark(repo_root)
    entry = registry.cell_entry(benchmark, args.workload)
    cell = registry.load_workload(args.workload, bench_dir)
    config = registry.load_config(entry["config"], bench_dir)
    job = registry.load_job(cell["job"], bench_dir)

    device = device_report(platform, entry["chips"])
    if device is None:
        return None

    from benchmarks.observe import CompileLog, Spans
    from benchmarks import trace_reduce

    scratch = Path(tempfile.mkdtemp(prefix="bench_"))
    trace_dir = scratch / "trace"
    try:
        with CompileLog() as log:
            ctx = Context(
                cell=cell,
                config=config,
                seed=args.seed,
                seconds=args.seconds,
                trace=bool(args.trace),
                t_start=t_start,
                scratch=scratch,
                trace_dir=trace_dir,
                spans=Spans(),
            )
            result = job.run(ctx)
            if after is not None:
                after(result)
        obs = result["obs"]
        t0, t1 = obs["window"]
        obs["spans"] = ctx.spans
        obs["compile"] = {
            "setup_s": log.seconds_before(t0),
            "window_modules": log.modules_between(t0, t1),
            "cache_hits": log.cache_hits,
            "cache_misses": log.cache_misses,
            "modules": len(log.modules),
        }
        obs["device"] = device
        obs["peaks"] = registry.load_peaks(device["kind"], bench_dir) if platform == "tpu" else None
        obs["trace"] = None
        if args.trace:
            xplane = trace_reduce.find_xplane(trace_dir)
            say(f"[run] trace {xplane} ({xplane.stat().st_size} bytes)")
            obs["trace"] = trace_reduce.reduce_trace(xplane)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    c = obs["compile"]
    say(
        f"[run] compile: {c['modules']} modules reached XLA, {c['setup_s']:.1f} s "
        f"lowering and compiling before the window; persistent cache {c['cache_hits']} hits / "
        f"{c['cache_misses']} misses; {len(c['window_modules'])} modules inside "
        f"the window {[n for n, _ in c['window_modules']][:12]}"
    )
    m = obs["memory"]
    say(
        "[run] device memory: "
        + ", ".join(
            f"{k} {m[k] / 2**30:.3f} GiB"
            for k in ("peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit", "largest_alloc_size")
            if k in m
        )
    )
    if obs["trace"] is not None:
        programs = sorted(
            ((sum(d), len(d), n) for n, d in obs["trace"]["modules"].items()), reverse=True
        )[:6]
        say(
            "[run] traced programs by device time: "
            + "; ".join(f"{n.split('(')[0]} {sec:.3f} s in {k} runs" for sec, k, n in programs)
        )
    for check in result["checks"]:
        say(check.line())

    metrics = {}
    for spec in registry.metrics_for(benchmark, args.workload, bool(args.trace)):
        value = registry.load_metric(spec["name"], bench_dir).read(obs)
        if value is not None:
            metrics[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    # Both peaks come out of one limit (a program's temporaries are reserved
    # at the bottom of memory, apart from the buffers in use), so the chip is
    # as full as their sum.
    device["memory_peak_bytes"] = int(
        m.get("peak_bytes_in_use", 0) + m.get("peak_bytes_reserved", 0)
    )
    say(
        f"[run] {result['attempted']} {obs['unit']}s in the window; a median "
        f"per {obs['unit']} is the median of them"
    )
    line = {
        "correct": all(ch.ok for ch in result["checks"]),
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": metrics,
        "device": device,
    }
    if obs["trace"] is not None:
        device["busy_s"] = obs["trace"]["busy_s"]
        device["window_s"] = obs["trace"]["window_s"]
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in obs["trace"]["device_ops"]],
            "idle_gaps": [[n, s] for n, s in obs["trace"]["idle_gaps"]],
        }
    return line


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    line = run_cell(args)
    if line is None:
        return EXIT_NO_DEVICE
    say(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
