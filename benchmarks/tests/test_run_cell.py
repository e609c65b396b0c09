"""A whole run at tiny size on the CPU, through everything but the look for a
chip: a sound run comes out correct, and a run whose timed path is broken
underneath does not. None of the numbers is a device number."""

import json
from unittest import mock

import pytest

from benchmarks import run
from benchmarks.tests import tiny


def _run(tmp_path, cell, **kw):
    root, bench = tiny.make_bench(tmp_path)
    return run.run_cell(
        tiny.args(cell, **kw), platform="cpu", repo_root=root, bench_dir=bench
    )


def test_main_refuses_a_backend_that_is_not_a_tpu(capsys):
    rc = run.main(
        ["--workload", "r18-cifar10-imp-ladder", "--seed", "1", "--seconds", "1", "--trace", "0"]
    )
    out = capsys.readouterr().out
    assert rc == run.EXIT_NO_DEVICE
    assert "refusing to run" in out and '"correct"' not in out


def test_a_sound_ladder_run_is_correct_and_reports_its_cells_metrics(tmp_path):
    line = _run(tmp_path, "tiny-ladder", seed=2**31 + 11)
    assert line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_img_per_s", "level_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"  # and so no device number
    json.dumps(line)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(tmp_path):
    from turboprune_tpu.harness import pruning_harness

    real = pruning_harness.make_train_step

    def broken(model, tx, schedule=None):
        step = real(model, tx, schedule)

        def train_step(state, batch):
            _, metrics = step(state, batch)
            return state, metrics

        return train_step

    with mock.patch.object(pruning_harness, "make_train_step", broken):
        line = _run(tmp_path, "tiny-dense", trace=1)
    assert line["correct"] is False
    # The traced line carries the per-layer metrics a CPU run can read.
    assert {"compile_s", "window_compiles", "epoch_gap_ms"} <= set(line["metrics"])
    assert "step_ms" not in line["metrics"] and "busy_s" not in line["device"]


def test_a_part_of_the_batch_left_out_is_not_correct(tmp_path):
    from turboprune_tpu.data.cifar import DeviceCifarLoader

    real = DeviceCifarLoader.epoch_arrays

    def half(self):
        images, labels = real(self)
        n = images.shape[1] // 2
        return images[:, :n], labels[:, :n]

    with mock.patch.object(DeviceCifarLoader, "epoch_arrays", half):
        line = _run(tmp_path, "tiny-dense")
    assert line["correct"] is False


def test_an_optimizer_that_takes_another_step_is_not_correct(tmp_path, capsys):
    """Twice the learning rate under the scanned epoch: the followed steps'
    parameter change is twice the plain SGD's, and only that number says so."""
    from turboprune_tpu.harness import pruning_harness

    real = pruning_harness.create_schedule

    def doubled(*args, **kwargs):
        schedule = real(*args, **kwargs)
        return lambda step: 2.0 * schedule(step)

    root, bench = tiny.make_bench(tmp_path)
    cell = bench / "workloads" / "tiny-dense.json"
    spec = json.loads(cell.read_text())
    spec["limits"]["update_norm_gap"] = ["max", 0.5]
    cell.write_text(json.dumps(spec))
    run_it = lambda: run.run_cell(
        tiny.args("tiny-dense"), platform="cpu", repo_root=root, bench_dir=bench
    )
    assert run_it()["correct"] is True
    with mock.patch.object(pruning_harness, "create_schedule", doubled):
        line = run_it()
    assert line["correct"] is False
    out = capsys.readouterr().out
    assert "update_norm_gap" in out and out.count("NOT CORRECT") == 1
