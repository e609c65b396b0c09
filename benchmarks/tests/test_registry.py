"""Configurations, cells, jobs, metrics and peaks are found by name: an
unknown name is an error, a new file is found without an edit."""

import json

import pytest

from benchmarks import registry
from benchmarks.tests import tiny


def test_every_name_in_benchmark_json_has_its_file():
    b = tiny.REAL
    for cell in b["workloads"]:
        spec = registry.load_workload(cell["name"])
        # What BENCHMARK.json says of a cell is said there alone.
        assert not {"name", "config", "chips", "why"} & set(spec)
        registry.load_job(spec["job"])
    for cfg in b["configs"]:
        spec = registry.load_config(cfg["name"])
        assert spec["reduced"] == cfg["reduced"] and spec["source"] == cfg["source"]
        assert cfg["file"] == f"benchmarks/configs/{cfg['name']}.json"
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(registry.load_metric(m["name"]).read)
        assert m.get("moves", "setup_s") in e2e


@pytest.mark.parametrize(
    "load, name",
    [
        (registry.load_workload, "no-such-cell"),
        (registry.load_config, "no-such-config"),
        (registry.load_job, "no_such_job"),
        (registry.load_metric, "no_such_metric"),
        (registry.load_metric, "../run"),
        (registry.load_peaks, "TPU v9 imaginary"),
        (registry.load_peaks, "cpu"),
    ],
)
def test_an_unknown_name_is_an_error(load, name):
    with pytest.raises(registry.UnknownName):
        load(name)


def test_peaks_are_keyed_by_the_exact_device_kind():
    peaks = registry.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["source"]


def test_a_new_metric_and_cell_are_found_without_an_edit(tmp_path):
    root, bench = tiny.make_bench(tmp_path)
    (bench / "metrics" / "epochs.in-window.py").write_text(
        "def read(obs):\n    return len(obs['boundaries']) - 1\n"
    )
    (bench / "metrics" / "nothing_to_read.py").write_text("def read(obs):\n    return None\n")
    cell = json.loads((bench / "workloads" / "tiny-dense.json").read_text())
    cell["name"] = "tiny-dense-2"
    (bench / "workloads" / "tiny-dense-2.json").write_text(json.dumps(cell))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "tiny-dense-2", "config": "tiny-resnet18", "traffic": "d2", "chips": 1})
    b["per_layer"] += [
        {"name": "epochs.in-window", "unit": "count", "workloads": ["tiny-dense-2"]},
        {"name": "nothing_to_read", "unit": "count", "workloads": ["tiny-dense-2"]},
    ]
    assert registry.load_workload("tiny-dense-2", bench)["job"] == "imp_ladder"
    names = [m["name"] for m in registry.metrics_for(b, "tiny-dense-2", traced=True)]
    # A metric with no ``workloads`` key is every cell's; its reader returns
    # None where the cell gives it nothing to read.
    assert "epochs.in-window" in names and "prune_s" in names
    assert registry.load_metric("prune_s", bench).read(
        {"spans": __import__("benchmarks.observe", fromlist=["Spans"]).Spans(), "window": (0, 1)}
    ) is None
    assert registry.load_metric("epochs.in-window", bench).read({"boundaries": [0, 1, 2]}) == 2
    assert [m["name"] for m in registry.metrics_for(b, "tiny-dense-2", traced=False)] == [
        "train_img_per_s",
        "setup_s",
    ]


@pytest.mark.parametrize(
    "name, obs, want",
    [
        ("augment_ms", {"trace": {"modules": {"jit_augment_epoch(1)": [0.5, 1.5], "jit_other": [9.0]}}, "augment_program": "jit_augment_epoch"}, 1000.0),
        ("augment_ms", {"trace": {"modules": {"jit_other": [9.0]}}, "augment_program": "jit_augment_epoch"}, None),
        ("augment_ms", {"trace": None, "augment_program": "jit_augment_epoch"}, None),
        ("step_ms", {"trace": {"modules": {"jit_scan_chunk": [0.8]}}, "step_program": "jit_scan_chunk", "steps_per_epoch": 8}, 100.0),
        ("step_ms", {"trace": {"modules": {}}}, None),
        ("train_img_per_s", {"window": (0.0, 2.0), "images": 512}, 256.0),
        ("train_img_per_s", {"window": (0.0, 2.0)}, None),
        ("level_s", {"unit": "epoch", "boundaries": [0, 1]}, None),
        ("level_s", {"unit": "level", "boundaries": [0.0, 1.0, 4.0, 6.0]}, 2.0),
    ],
)
def test_a_reader_returns_its_number_or_nothing(name, obs, want):
    assert registry.load_metric(name).read(obs) == want
