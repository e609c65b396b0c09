"""The power-retention cell's job (``jobs/brumby_level.py``) at a test's size
on the CPU: a sound run is correct and reports what its cell declares; the
float8 control and the reference with its carry cut both read above a sound
run; the operation counts are a hand count's and the tree's; the registry
finds every new name, each by its name. None of the numbers is a device
number."""

import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import brumby_flops, registry, run
from benchmarks.tests import tiny

BENCH = tiny.BENCH
CELL = "tiny-brumby-level"
REAL_CELL = "brumby-14b-retention-level-32k"
REAL_CONFIG = "brumby-14b-base"
NEW = ("retention_ms", "retention_roofline_pct", "loss_blocks_ms")
# The tiny preset as one chip of two holds it: a key/value head with its two query heads.
SPEC = {
    "rms_norm_eps": 1e-6, "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 8,
    "rope_theta": 1e6, "retention_eps": 1e-16, "retention_chunk": 16,
}  # fmt: skip
OVERRIDES = [
    "model_params.model_name=brumby_tiny",
    "model_params.num_hidden_layers=2",
    "model_params.tensor_parallel=2",
    "dataset_params.seq_len=64",
    "dataset_params.num_classes=96",
    "dataset_params.total_batch_size=2",
    "dataset_params.synthetic_num_train=8",
    "dataset_params.synthetic_num_test=3",
    "dataset_params.doc_len_mu=3.5",
    "dataset_params.doc_len_min=2",
    "experiment_params.num_devices=1",
    "experiment_params.epochs_per_level=200",
    # float32, so that a sound run sits at rounding and the float8 control
    # far above it (as tests/test_lm_level.py).
    "experiment_params.training_precision=float32",
]


def make_bench(root):
    """A scratch benchmark with one tiny cell: the real job, metrics and
    peaks, copied; the configuration and the cell written here."""
    bench = root / "benchmarks"
    for sub in ("jobs", "metrics"):
        shutil.copytree(BENCH / sub, bench / sub)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    (bench / "configs").mkdir()
    (bench / "workloads").mkdir()
    config = {"name": "tiny-brumby", "entry_config": "brumby_14b_imp", "overrides": OVERRIDES, **SPEC}
    (bench / "configs" / "tiny-brumby.json").write_text(json.dumps(config))
    real = json.loads((BENCH / "workloads" / f"{REAL_CELL}.json").read_text())
    cell = {
        "job": real["job"],
        "params": {**real["params"], "warmup": 1, "trace_units": 1, "probes": 3, "probe_positions": 5, "overrides": []},
        # In float32 at this size a sound run's five gaps read 1e-7 or less and both controls'
        # 1e-4 or more. Retention is XLA's and the logits are whole: that count is the test's to read.
        "limits": {**{k: [v[0], 1e-5] for k, v in real["limits"].items() if v[0] == "max"},
                   "kernels_bypassed": ["max", 1e9], "nonfinite_losses": ["max", 0], "images_miscounted": ["max", 0],
                   "ladder_excess_weights": ["max", 0], "param_change": ["min", 1e-7]},
    }  # fmt: skip
    (bench / "workloads" / f"{CELL}.json").write_text(json.dumps(cell))
    benchmark = dict(tiny.REAL)
    benchmark["configs"] = [{"name": "tiny-brumby"}]
    benchmark["workloads"] = [{"name": CELL, "config": "tiny-brumby", "traffic": CELL, "chips": 1}]
    swap = lambda m: {**m, "workloads": [CELL if w == REAL_CELL else w for w in m["workloads"]]} if "workloads" in m else m
    benchmark["end_to_end"] = [swap(m) for m in tiny.REAL["end_to_end"]]
    benchmark["per_layer"] = [swap(m) for m in tiny.REAL["per_layer"]]
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return root, bench


def test_the_real_cell_declares_what_the_issue_names():
    benchmark = registry.load_benchmark(BENCH.parent)
    entry = registry.cell_entry(benchmark, REAL_CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (REAL_CONFIG, "dense-level-32k", 1)
    assert len(entry["why"]) <= 200 and "retention" in entry["why"]
    by_name = {c["name"]: c for c in benchmark["configs"]}
    assert by_name[REAL_CONFIG]["file"] == f"benchmarks/configs/{REAL_CONFIG}.json"
    assert by_name[REAL_CONFIG]["source"] == registry.load_config(REAL_CONFIG)["source"]
    assert by_name[REAL_CONFIG]["reduced"] == registry.load_config(REAL_CONFIG)["reduced"]
    assert [m["name"] for m in registry.metrics_for(benchmark, REAL_CELL, False)] == ["train_img_per_s", "setup_s"]
    traced = {m["name"] for m in registry.metrics_for(benchmark, REAL_CELL, True)}
    assert set(NEW) | {"step_ms", "step_mfu_pct", "device_idle_pct", "peak_hbm_gib", "compile_s"} <= traced
    assert not traced & {"ssd_ms", "flash_causal_roofline_pct", "moe_ms", "moe_swiglu_ms", "shortconv_ms", "augment_ms"}
    metrics = {m["name"]: m for m in benchmark["per_layer"]}
    for name in NEW:
        m = metrics[name]
        assert m["workloads"] == [REAL_CELL] and m["moves"] == "train_img_per_s" and m["source"] == "device_trace"
        assert m["layer"] == ("step" if name == "loss_blocks_ms" else "kernels")
        assert (m["unit"], m["better"]) == (("%", "higher") if name.endswith("_roofline_pct") else ("ms", "lower"))
        assert callable(registry.load_metric(name).read)
    for cell in benchmark["workloads"]:
        if cell["name"] != REAL_CELL:
            assert not set(NEW) & {m["name"] for m in registry.metrics_for(benchmark, cell["name"], True)}
    # The issue's traffic, letter for letter; the granite cell's limits' names and one of its own.
    cell, other = registry.load_workload(REAL_CELL), registry.load_workload("granite-h-micro-dense-level-8k")
    assert cell["job"] == "brumby_level" and cell["params"]["overrides"] == [
        "dataset_params.seq_len=32768", "dataset_params.total_batch_size=1", "dataset_params.synthetic_num_train=6",
        "dataset_params.synthetic_num_test=2", "dataset_params.doc_len_mu=9.0", "dataset_params.doc_len_sigma=1.2",
        "dataset_params.doc_len_min=16", "dataset_params.layout_seed=0", "experiment_params.epochs_per_level=40",
    ]  # fmt: skip
    assert {k: v for k, v in cell["params"].items() if k != "overrides"} == {
        k: v for k, v in other["params"].items() if k != "overrides"
    }
    assert set(cell["limits"]) == set(other["limits"]) | {"kernels_bypassed"} and cell["limits"]["kernels_bypassed"] == ["max", 0]
    assert set(cell["limits_why"]) >= {"readings"} and all(len(v) > 20 for v in cell["limits_why"].values())
    registry.load_job(cell["job"])


def test_the_configuration_keeps_every_published_number_but_the_reduced():
    config = registry.load_config(REAL_CONFIG)
    published = {
        "head_dim": 128, "hidden_size": 5120, "intermediate_size": 17408, "max_position_embeddings": 32768,
        "max_window_layers": 40, "num_attention_heads": 40, "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rms_norm_eps": 1e-06, "rope_theta": 1000000, "vocab_size": 151936,
    }  # fmt: skip
    here = {"num_hidden_layers": 6, "num_attention_heads": 5, "num_key_value_heads": 1, "vocab_size": 18992}
    assert config["reduced"] == list(here) and config["published"] == {k: published[k] for k in here}
    for key, value in published.items():
        assert config[key] == here.get(key, value), key
    assert (config["attention_bias"], config["tie_word_embeddings"], config["use_sliding_window"]) == (False, False, False)
    assert (config["model_type"], config["hidden_act"], config["rope_scaling"], config["sliding_window"]) == ("brumby", "silu", None, None)
    assert not any(w in k for k in here for w in ("hidden_size", "intermediate", "latent", "state", "_dim", "_rank", "expand", "per_tok"))
    d = config["deployment"]
    assert (d["chips_a_layer"], d["tensor_parallel"], d["expert_parallel"], d["expert_rank"]) == (8, 8, 1, 0)
    assert {"retention_degree", "gate", "shared_state", "score_scale", "retention_eps", "query_key_norm_and_rotary",
            "no_sink_no_output_gate", "initialisation", "optimizer", *here} <= set(config["assumed"])  # fmt: skip
    assert 4 <= config["num_hidden_layers"] and config["vocab_size"] * 8 >= published["vocab_size"]  # the guide's floors
    # What the model builds from the entry config and the file's overrides is the file's share.
    from turboprune_tpu.config import compose
    from turboprune_tpu.models import brumby, create_model

    cfg = compose(config["entry_config"], config["overrides"])
    mp = cfg.model_params
    model = create_model(mp.model_name, cfg.dataset_params.num_classes, num_layers=mp.num_hidden_layers, share=mp.share)
    held = brumby.held(model.cfg, model.share)
    assert model.layers == config["num_hidden_layers"] and model.vocab_size == config["vocab_size"]
    assert (held["query_heads"], held["kv_heads"]) == (config["num_attention_heads"], config["num_key_value_heads"])
    assert held["dense_columns"] == config["dense_columns_here"]
    assert (brumby.DEGREE, brumby.RETENTION_EPS, model.cfg.retention_chunk) == (
        config["retention_degree"], config["retention_eps"], config["retention_chunk"]
    )
    for key in ("hidden_size", "intermediate_size", "head_dim", "rms_norm_eps", "rope_theta"):
        assert getattr(model.cfg, key) == config[key], key
    assert cfg.dataset_params.seq_len == config["max_position_embeddings"]


def test_a_sound_run_is_correct_and_both_controls_read_above_it(tmp_path, capsys):
    got = {}

    def after(result):
        got["control"] = result["final"]["control_numbers"](result["final"])
        got["controls"] = result["final"]["controls"]
        got["sound"] = {c.name: c.value for c in result["checks"]}
        got["obs"] = result["obs"]

    root, bench = make_bench(tmp_path)
    line = run.run_cell(
        tiny.args(CELL, seed=2**31 + 11, trace=1), platform="cpu", repo_root=root, bench_dir=bench, after=after
    )
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert {"compile_s", "harness_init_s", "epoch_gap_ms", "window_compiles"} <= set(line["metrics"])
    assert line["metrics"]["window_compiles"]["value"] == 0
    # No device: nothing of a trace, and nothing under another cell's names.
    assert not {*NEW, "step_ms", "level_s", "ssd_ms", "moe_ms", "shortconv_ms"} & set(line["metrics"])
    out = capsys.readouterr().out
    assert "retention_xla_calls" in out and "carried_chunks_per_step 16" in out
    sound, control, obs = got["sound"], got["control"], got["obs"]
    # At this size both of the cell's paths are bypassed, and the run says so: the forward,
    # the eval and the step each traced two layers' retention, XLA's, and the logits are whole.
    assert sound["kernels_bypassed"] >= 2 + 1 and obs["loss_blocks"] == 0
    assert obs["kernel_counts"]["tokens_per_step"] == 128
    assert set(control) == {"eval_loss_gap", "eval_probe_loss_gap", "train_loss_gap", "momentum_norm_gap", "update_norm_gap"}
    for name in ("eval_probe_loss_gap", "eval_loss_gap", "update_norm_gap", "momentum_norm_gap"):
        assert 5 * sound[name] < control[name], name
    assert sound["eval_loss_gap"] < 1e-6 and sound["update_norm_gap"] < 1e-4
    # Both controls come out not correct by the cell's own limits, judged as the run was; the
    # reference that loses its state every 16 tokens fails every limit a sum can move.
    float8, cut = got["controls"]["float8"], got["controls"]["carry_cut"]
    assert float8["numbers"] == control and float8["correct"] is False
    assert {"eval_loss_gap", "eval_probe_loss_gap"} <= set(float8["failed"])
    assert cut["correct"] is False and set(cut["failed"]) == set(control)
    assert "[control] carry_cut: correct=False, fails eval_loss_gap, " in out
    assert 100 * sound["eval_probe_loss_gap"] < cut["numbers"]["eval_probe_loss_gap"]
    json.dumps(line)


def test_a_step_of_the_published_cut_is_the_hand_count():
    """Shapes only: the six layers at published widths as this chip holds
    them, 18,992 ids, one packed sequence of 32,768 tokens in the cell's own
    layout."""
    from turboprune_tpu.config import compose
    from turboprune_tpu.data.tokens import document_layout
    from turboprune_tpu.models import create_model

    config = registry.load_config(REAL_CONFIG)
    cfg = compose(config["entry_config"], config["overrides"])
    mp = cfg.model_params
    model = create_model(mp.model_name, 18992, num_layers=mp.num_hidden_layers, share=mp.share)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 2, 256), jnp.int32))["params"]
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == 344_965_120 + 97_337_862  # tests/test_brumby.py: prunable and the rest
    tokens = 32768
    seg = document_layout(6, tokens, 9.0, 1.2, 16, tokens, 0).reshape(6, 1, tokens)
    counts = brumby_flops.step_counts(shapes, config, seg)
    layer, head, gate = 41_287_680, 97_239_040, 5120
    per_token = 6 * (layer + gate) + head
    # Retention by hand: a state of 8,256 x 129, read for 5 query heads and written for 1.
    assert brumby_flops.symmetric_features(128) == 8256
    carried = 2 * 8256 * 129 * 6  # a token, read and write
    lengths = [np.bincount(row) for row in seg.reshape(6, tokens) - seg.reshape(6, tokens).min(axis=1, keepdims=True)]
    by_hand = 0.0
    for row in lengths:
        for n in (int(n) for n in row if n):
            by_hand += min(4 * 128 * 5 * n * (n + 1) / 2, n * carried)
    by_hand *= 6 / 6  # six layers, the mean of six steps
    assert counts["retention_flops"] == pytest.approx(4 * by_hand, rel=1e-12)
    assert counts["step_flops"] == pytest.approx(3 * (2 * tokens * per_token + by_hand), rel=1e-12)
    assert counts["retention_bytes"] == 4 * 6 * tokens * (2 * 128 * (2 * 5 + 2) + 4)
    # A document is cheaper carried from 2 x 8,256 x 129 x 6 / (4 x 128 x 5) x 2 = 9,984 tokens on.
    assert brumby_flops.retention_document_flops(9982, 5, 1, 128) == 4 * 128 * 5 * 9982 * 9983 / 2
    assert brumby_flops.retention_document_flops(9984, 5, 1, 128) == 9984 * carried
    assert 0 < counts["carried_tokens_per_step"] < tokens
    # 73.9 TFLOP a step (ISSUE 44 said about 100; its own parameter counts give 67.8 before
    # retention): the MLPs 53 % of it, the head 26 %, retention 8 %.
    mlp, head = 3 * 2 * tokens * 6 * (22_282_240 + 11_141_120), 3 * 2 * tokens * 97_239_040
    assert 73e12 < counts["step_flops"] < 75e12 and 0.52 < mlp / counts["step_flops"] < 0.55
    assert 0.25 < head / counts["step_flops"] < 0.27 and 0.07 < 3 * by_hand / counts["step_flops"] < 0.09
    assert counts["tokens_per_step"] == tokens


def test_the_new_readers_read_their_split_or_nothing():
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    counts = {"retention_flops": 2e9, "retention_bytes": 1e8}
    split = {"retention/scan": 8.0, "retention/qkv": 2.0, "lm_head": 5.0, "loss": 0.5, "mlp": 9.0}
    obs = {"scope_ms": split, "kernel_counts": counts, "peaks": peaks, "loss_blocks": 16}
    read = lambda name, o=obs: registry.load_metric(name).read(o)
    assert read("retention_ms") == 8.0  # the scan's scope alone, not the projections around it
    assert read("retention_roofline_pct") == pytest.approx(100 * 2e-3 / 8e-3)
    assert read("loss_blocks_ms") == 5.5
    # A program without the layer (the parent, another model), a step whose logits are whole,
    # a run without a trace: nothing, and no error.
    others = {"scope_ms": {"ssd": 3.0, "lm_head": 5.0, "loss": 2.0, "attn/flash": 2.0},
              "kernel_counts": {"ssd_flops": 1.0, "flash_causal_flops": 1.0, "flash_causal_bytes": 1.0}}  # fmt: skip
    for name in NEW:
        assert read(name, {"peaks": peaks, "trace": None}) is None
        assert read(name, {"peaks": peaks, "trace": None, **others}) is None
    assert read("loss_blocks_ms", {**obs, "loss_blocks": 0}) is None
