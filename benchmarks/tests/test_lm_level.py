"""The language-model cell's job (``jobs/lm_level.py``) at a test's size on
the CPU: a sound run is correct and reports what its cell declares; a run
whose timed path is broken underneath is not; the float8 control reads above
a sound run; the operation counts and the trace's split by scope are what
their definitions say. None of the numbers is a device number."""

import json
import shutil
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import granite_flops, registry, run, scope_times
from benchmarks.tests import tiny

BENCH = tiny.BENCH
CELL = "tiny-lm-level"
REAL_CELL = "granite-h-micro-dense-level-8k"
SPEC = {
    "embedding_multiplier": 12.0, "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
    "logits_scaling": 8.0, "rms_norm_eps": 1e-5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "mamba_chunk_size": 16,
}  # fmt: skip
OVERRIDES = [
    "model_params.model_name=hybrid_lm_tiny",
    "model_params.num_hidden_layers=0",
    "dataset_params.seq_len=64",
    "dataset_params.num_classes=96",
    "dataset_params.total_batch_size=2",
    "dataset_params.synthetic_num_train=8",
    "dataset_params.synthetic_num_test=3",
    "dataset_params.doc_len_mu=2.5",
    "dataset_params.doc_len_min=2",
    "experiment_params.num_devices=1",
    "experiment_params.epochs_per_level=200",
    # float32, so that a sound run sits at rounding and the float8 control
    # far above it: at this size bfloat16 is itself at the comparison's floor.
    "experiment_params.training_precision=float32",
]


def make_bench(root, limits=None):
    """A scratch benchmark with one tiny language-model cell: the real job,
    metrics and peaks, copied; the configuration and the cell written here."""
    bench = root / "benchmarks"
    for sub in ("jobs", "metrics"):
        shutil.copytree(BENCH / sub, bench / sub)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    (bench / "configs").mkdir()
    (bench / "workloads").mkdir()
    config = {"name": "tiny-lm", "entry_config": "granite_h_micro_imp", "overrides": OVERRIDES, **SPEC}
    (bench / "configs" / "tiny-lm.json").write_text(json.dumps(config))
    real = json.loads((BENCH / "workloads" / f"{REAL_CELL}.json").read_text())
    cell = {
        "job": "lm_level",
        "params": {**real["params"], "warmup": 1, "trace_units": 1, "probes": 3, "probe_positions": 5, "overrides": []},
        "limits": {**{k: [v[0], 1e9] for k, v in real["limits"].items() if v[0] == "max"},
                   "nonfinite_losses": ["max", 0], "images_miscounted": ["max", 0],
                   "ladder_excess_weights": ["max", 0], "param_change": ["min", 1e-7], **(limits or {})},
    }  # fmt: skip
    (bench / "workloads" / f"{CELL}.json").write_text(json.dumps(cell))
    benchmark = dict(tiny.REAL)
    benchmark["configs"] = [{"name": "tiny-lm"}]
    benchmark["workloads"] = [{"name": CELL, "config": "tiny-lm", "traffic": CELL, "chips": 1}]
    swap = lambda m: {**m, "workloads": [CELL if w == REAL_CELL else w for w in m["workloads"]]} if "workloads" in m else m
    benchmark["end_to_end"] = [swap(m) for m in tiny.REAL["end_to_end"]]
    benchmark["per_layer"] = [swap(m) for m in tiny.REAL["per_layer"]]
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return root, bench


def _run(tmp_path, limits=None, after=None, **kw):
    root, bench = make_bench(tmp_path, limits)
    return run.run_cell(tiny.args(CELL, **kw), platform="cpu", repo_root=root, bench_dir=bench, after=after)


def test_the_real_cell_declares_what_the_issue_names():
    benchmark = registry.load_benchmark(BENCH.parent)
    entry = registry.cell_entry(benchmark, REAL_CELL)
    assert (entry["config"], entry["chips"]) == ("granite-4.0-h-micro", 1)
    assert [m["name"] for m in registry.metrics_for(benchmark, REAL_CELL, False)] == ["train_img_per_s", "setup_s"]
    traced = {m["name"] for m in registry.metrics_for(benchmark, REAL_CELL, True)}
    assert {"ssd_ms", "ssd_roofline_pct", "flash_causal_roofline_pct", "step_ms", "step_mfu_pct"} <= traced
    assert "augment_ms" not in traced
    for old in ("r50-imagenet-dense-level", "r18-cifar10-imp-ladder"):
        names = {m["name"] for m in registry.metrics_for(benchmark, old, True)}
        assert "augment_ms" in names and not names & {"ssd_ms", "ssd_roofline_pct", "flash_causal_roofline_pct"}
    config = registry.load_config("granite-4.0-h-micro")
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["vocab_size"], config["hidden_size"]) == (10, 12544, 2048)
    assert len(config["layer_types"]) == 40 and config["layer_types"][:10].count("attention") == 1
    cell = registry.load_workload(REAL_CELL)
    assert set(cell["limits"]) == {
        "eval_probe_loss_gap", "eval_loss_gap", "train_loss_gap", "momentum_norm_gap", "update_norm_gap",
        "param_change", "images_miscounted", "nonfinite_losses", "ladder_excess_weights",
    }  # fmt: skip


def test_a_sound_run_is_correct_and_the_control_reads_above_it(tmp_path, capsys):
    got = {}

    def after(result):
        got["control"] = result["final"]["control_numbers"](result["final"])
        got["sound"] = {c.name: c.value for c in result["checks"]}

    line = _run(tmp_path, after=after, seed=2**31 + 11, trace=1)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert {"compile_s", "harness_init_s", "epoch_gap_ms", "epoch_log_ms", "window_compiles"} <= set(line["metrics"])
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert not {"ssd_ms", "step_ms", "level_s", "augment_ms"} & set(line["metrics"])  # no device, no level
    out = capsys.readouterr().out
    assert "target tokens/s" in out and "tokens_per_step 128" in out
    sound, control = got["sound"], got["control"]
    assert set(control) == {"eval_loss_gap", "eval_probe_loss_gap", "train_loss_gap", "momentum_norm_gap", "update_norm_gap"}
    # The float32 program agrees with the float32 reference far better than
    # the float8 reference does.
    for name in ("eval_probe_loss_gap", "eval_loss_gap", "update_norm_gap", "momentum_norm_gap"):
        assert 5 * sound[name] < control[name], name
    assert sound["eval_loss_gap"] < 1e-6 and sound["update_norm_gap"] < 1e-4
    assert sound["images_miscounted"] == 0 and sound["ladder_excess_weights"] == 0
    json.dumps(line)


def test_an_untraced_run_reports_the_two_end_to_end_metrics(tmp_path):
    line = _run(tmp_path, limits={"update_norm_gap": ["max", 0.5]})
    assert line["correct"] is True
    assert set(line["metrics"]) == {"train_img_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_a_step_that_returns_its_state_unchanged_is_not_correct(tmp_path):
    from turboprune_tpu.harness import pruning_harness

    real = pruning_harness.make_train_step

    def broken(model, tx, schedule=None):
        step = real(model, tx, schedule)
        return lambda state, batch: (state, step(state, batch)[1])

    with mock.patch.object(pruning_harness, "make_train_step", broken):
        line = _run(tmp_path, limits={"update_norm_gap": ["max", 0.5]})
    assert line["correct"] is False


def test_an_optimizer_that_takes_another_step_is_not_correct(tmp_path, capsys):
    from turboprune_tpu.harness import pruning_harness

    real = pruning_harness.create_schedule

    def doubled(*args, **kwargs):
        schedule = real(*args, **kwargs)
        return lambda step: 2.0 * schedule(step)

    with mock.patch.object(pruning_harness, "create_schedule", doubled):
        line = _run(tmp_path, limits={"update_norm_gap": ["max", 0.5]})
    assert line["correct"] is False
    out = capsys.readouterr().out
    assert "update_norm_gap" in out and out.count("NOT CORRECT") == 1


def test_targets_left_out_are_miscounted(tmp_path):
    from turboprune_tpu.data.tokens import PackedTokenLoader

    real = PackedTokenLoader.epoch_arrays

    def half(self):
        tokens, targets = real(self)
        return tokens, targets.at[:, :, 32:].set(-1)

    with mock.patch.object(PackedTokenLoader, "epoch_arrays", half):
        line = _run(tmp_path)
    assert line["correct"] is False


def test_the_counts_are_the_definitions():
    heads, p, n, q = 64, 64, 128, 256
    assert granite_flops.ssd_forward_flops(1, heads, p, n, q) == 2 * q * n + 2 * q * p * heads + 4 * n * p * heads == 4259840
    assert granite_flops.ssd_forward_bytes(1, heads, p, n) == 2 * (2 * 4096 + 2 * 128 + 64)
    assert granite_flops.causal_pairs([1, 3, 4]) == 1 + 6 + 10
    seg = np.array([[[0, 0, 0, 1, 1, 2, 2, 2]]])
    assert [l.tolist() for l in granite_flops.document_lengths(seg)] == [[3, 2, 3]]
    assert granite_flops.attention_forward_flops(10.0, 32, 64) == 4 * 64 * 32 * 10
    assert granite_flops.attention_forward_bytes(8192, 32, 8, 64) == 8192 * 2 * 64 * 80
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert granite_flops.roofline_seconds(200.0, 10.0, peaks) == 2.0  # compute sets it
    assert granite_flops.roofline_seconds(200.0, 50.0, peaks) == 5.0  # memory sets it


def test_a_step_of_the_published_cut_is_about_39_teraflops():
    """Shapes only: ten layers at published widths, 12,544 ids, one packed
    sequence of 8,192 tokens in the cell's own layout."""
    from turboprune_tpu.data.tokens import document_layout
    from turboprune_tpu.models import create_model

    model = create_model("granite_4_0_h_micro", 12544, num_layers=10)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 2, 256), jnp.int32))["params"]
    seg = document_layout(6, 8192, 6.5, 1.2, 16, 8192, 0).reshape(6, 1, 8192)
    config = registry.load_config("granite-4.0-h-micro")
    counts = granite_flops.step_counts(shapes, config, seg, config["mamba_chunk_size"])
    assert counts["tokens_per_step"] == 8192
    assert 38.5e12 < counts["step_flops"] < 39.5e12
    assert counts["ssd_flops"] == 3 * 9 * 8192 * 4259840
    # The MLPs hold two thirds of the operations, the scan under a thirtieth.
    mlp = 3 * 2 * 8192 * 10 * 3 * 2048 * 8192
    assert 0.6 < mlp / counts["step_flops"] < 0.7 and counts["ssd_flops"] / counts["step_flops"] < 1 / 30
    assert counts["flash_causal_flops"] < counts["ssd_flops"]


def test_scopes_join_events_to_labels_through_the_modules_text():
    hlo = """
  %fusion.12 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(scan_chunk)/while/body/jvp(forward)/HybridLM/layers_0/mixer/ssd/mul" source_file="x.py" source_line=3}
  ROOT %transpose_jvp_flash_causal_dq__.1 = bf16[4]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(scan_chunk)/while/body/transpose(jvp(forward))/HybridLM/checkpoint/rematted_computation/layers_5/mixer/attn/flash/flash_causal_dq" source_file="y.py"}
  %convolution.3 = bf16[4]{0} convolution(%a, %b), metadata={op_name="jit(scan_chunk)/while/body/jvp(forward)/HybridLM/layers_0/mixer/mamba/in_proj/in_proj/dot_general"}
  %copy.1 = f32[2]{0} copy(%a)
"""
    scopes = scope_times.instruction_scopes(hlo)
    assert set(scopes) == {"fusion.12", "transpose_jvp_flash_causal_dq__.1", "convolution.3"}
    labels = ("ssd", "attn/flash", "mamba/in_proj", "mlp")
    name = scope_times.instruction_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop")
    assert name == "fusion.12" and scope_times.label_of(scopes[name], labels) == "ssd"
    assert scope_times.label_of(scopes["transpose_jvp_flash_causal_dq__.1"], labels) == "attn/flash"
    assert scope_times.label_of(scopes["convolution.3"], labels) == "mamba/in_proj"
    assert scope_times.label_of(scopes.get("copy.1"), labels) == scope_times.OTHER
    assert scope_times.label_of("a/b/ssdx/mul", labels) == scope_times.OTHER  # whole segments only


def test_the_roofline_readers_divide_the_least_time_by_the_measured():
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    counts = {"ssd_flops": 2e9, "ssd_bytes": 1e8, "flash_causal_flops": 1e9, "flash_causal_bytes": 4e8}
    obs = {"scope_ms": {"ssd": 8.0, "attn/flash": 8.0}, "kernel_counts": counts, "peaks": peaks}
    read = lambda name: registry.load_metric(name).read(obs)
    assert read("ssd_ms") == 8.0
    assert read("ssd_roofline_pct") == pytest.approx(100 * 2e-3 / 8e-3)  # compute-bound
    assert read("flash_causal_roofline_pct") == pytest.approx(100 * 4e-3 / 8e-3)  # memory-bound
    for name in ("ssd_ms", "ssd_roofline_pct", "flash_causal_roofline_pct"):
        assert registry.load_metric(name).read({"peaks": peaks, "trace": None}) is None
