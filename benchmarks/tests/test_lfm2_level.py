"""The convolution-hybrid sparse-expert cell's job (``jobs/lfm2_level.py``) at
a test's size on the CPU: a sound run is correct, counts its pairs and rounds
and drops none, and reports what its cell declares; the float8 control reads
above a sound run; the operation counts are a hand count's and the registry
finds every new name, each by its name. None of the numbers is a device
number."""

import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import lfm2_flops, registry, run
from benchmarks.tests import tiny

BENCH = tiny.BENCH
CELL = "tiny-lfm2-level"
REAL_CELL = "lfm2-8b-a1b-moe-level-8k"
REAL_CONFIG = "lfm2-8b-a1b"
NEW = (
    "shortconv_ms", "shortconv_roofline_pct", "lfm2_moe_ms", "lfm2_moe_experts_roofline_pct",
    "lfm2_moe_load_max_over_mean", "lfm2_flash_causal_roofline_pct",
)  # fmt: skip
# The tiny preset as one chip of four holds it: half of the heads, channels
# and columns (two tensor-parallel chips), experts 4-7 of 16.
SPEC = {
    "norm_eps": 1e-5, "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 8,
    "rope_theta": 1e6, "num_experts_per_tok": 4, "routed_scaling_factor": 1.0, "expert_offset": 4,
    "num_experts": 4,
}  # fmt: skip
OVERRIDES = [
    "model_params.model_name=lfm2_moe_tiny",
    "model_params.num_hidden_layers=3",
    "model_params.tensor_parallel=2",
    "model_params.expert_parallel=4",
    "model_params.expert_rank=1",
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
    # far above it (as tests/test_lm_level.py).
    "experiment_params.training_precision=float32",
]


def make_bench(root, limits=None):
    """A scratch benchmark with one tiny cell: the real job, metrics and
    peaks, copied; the configuration and the cell written here."""
    bench = root / "benchmarks"
    for sub in ("jobs", "metrics"):
        shutil.copytree(BENCH / sub, bench / sub)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    (bench / "configs").mkdir()
    (bench / "workloads").mkdir()
    config = {"name": "tiny-lfm2", "entry_config": "lfm2_8b_a1b_imp", "overrides": OVERRIDES, **SPEC}
    (bench / "configs" / "tiny-lfm2.json").write_text(json.dumps(config))
    real = json.loads((BENCH / "workloads" / f"{REAL_CELL}.json").read_text())
    cell = {
        "job": real["job"],
        "params": {**real["params"], "warmup": 1, "trace_units": 1, "probes": 3, "probe_positions": 5, "overrides": []},
        "limits": {**{k: [v[0], 1e9] for k, v in real["limits"].items() if v[0] == "max"},
                   "nonfinite_losses": ["max", 0], "images_miscounted": ["max", 0], "moe_dropped_pairs": ["max", 0],
                   "routing_mismatch": ["max", 0.0],
                   "ladder_excess_weights": ["max", 0], "param_change": ["min", 1e-7], **(limits or {})},
    }  # fmt: skip
    (bench / "workloads" / f"{CELL}.json").write_text(json.dumps(cell))
    benchmark = dict(tiny.REAL)
    benchmark["configs"] = [{"name": "tiny-lfm2"}]
    benchmark["workloads"] = [{"name": CELL, "config": "tiny-lfm2", "traffic": CELL, "chips": 1}]
    swap = lambda m: {**m, "workloads": [CELL if w == REAL_CELL else w for w in m["workloads"]]} if "workloads" in m else m
    benchmark["end_to_end"] = [swap(m) for m in tiny.REAL["end_to_end"]]
    benchmark["per_layer"] = [swap(m) for m in tiny.REAL["per_layer"]]
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return root, bench


def test_the_real_cell_declares_what_the_issue_names():
    benchmark = registry.load_benchmark(BENCH.parent)
    entry = registry.cell_entry(benchmark, REAL_CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (REAL_CONFIG, "dense-level-8k", 1)
    by_name = {c["name"]: c for c in benchmark["configs"]}
    assert by_name[REAL_CONFIG]["file"] == f"benchmarks/configs/{REAL_CONFIG}.json"
    assert by_name[REAL_CONFIG]["source"] == registry.load_config(REAL_CONFIG)["source"]
    assert [m["name"] for m in registry.metrics_for(benchmark, REAL_CELL, False)] == ["train_img_per_s", "setup_s"]
    traced = {m["name"] for m in registry.metrics_for(benchmark, REAL_CELL, True)}
    assert set(NEW) | {"step_ms", "step_mfu_pct", "device_idle_pct", "peak_hbm_gib", "compile_s"} <= traced
    assert not traced & {"ssd_ms", "flash_causal_roofline_pct", "moe_ms", "moe_swiglu_ms", "noise_ms", "augment_ms"}
    metrics = {m["name"]: m for m in benchmark["per_layer"]}
    for name in NEW:
        m = metrics[name]
        assert m["workloads"] == [REAL_CELL] and m["moves"] == "train_img_per_s"
        assert m["layer"] == ("step" if name == "lfm2_moe_load_max_over_mean" else "kernels")
        assert (m["unit"] == "%") == name.endswith("_roofline_pct")
        assert callable(registry.load_metric(name).read)
    for cell in benchmark["workloads"]:
        if cell["name"] != REAL_CELL:
            assert not set(NEW) & {m["name"] for m in registry.metrics_for(benchmark, cell["name"], True)}
    # The sparse-expert cell's traffic, letter for letter, and its limits' names.
    cell, other = registry.load_workload(REAL_CELL), registry.load_workload("nemotron3-super-moe-level-8k")
    assert cell["params"] == other["params"] and cell["job"] == "lfm2_level"
    assert set(cell["limits"]) == set(other["limits"]) and cell["limits"]["moe_dropped_pairs"] == ["max", 0]
    assert set(cell["limits_why"]) >= {"readings"} and all(len(v) > 20 for v in cell["limits_why"].values())
    registry.load_job(cell["job"])


def test_the_configuration_keeps_every_published_number_but_the_reduced():
    config = registry.load_config(REAL_CONFIG)
    published = {
        "conv_L_cache": 3, "hidden_size": 2048, "intermediate_size": 7168,
        "max_position_embeddings": 128000, "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "vocab_size": 65536,
    }  # fmt: skip
    here = {"num_hidden_layers": 10, "num_experts": 8, "vocab_size": 16384, "num_attention_heads": 8, "num_key_value_heads": 2}
    assert config["reduced"] == list(here) and config["published"] == {k: published[k] for k in here}
    for key, value in published.items():
        assert config[key] == here.get(key, value), key
    assert (config["conv_bias"], config["norm_topk_prob"], config["use_expert_bias"]) == (False, True, True)
    assert config["model_type"] == "lfm2_moe" and len(config["layer_types"]) == 24  # kept whole
    assert [i for i, k in enumerate(config["layer_types"]) if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert not any(w in k for k in here for w in ("hidden_size", "intermediate", "latent", "state", "_dim", "_rank", "expand", "per_tok"))
    d = config["deployment"]
    assert (d["chips_a_layer"], d["tensor_parallel"], d["expert_parallel"], d["expert_rank"]) == (4, 4, 4, 0)
    assert {"tie_word_embeddings", "initialisation", "optimizer", "use_expert_bias", "short_convolution"} <= set(config["assumed"])
    # What the model builds from the entry config and the file's overrides is the file's share.
    from turboprune_tpu.config import compose
    from turboprune_tpu.models import create_model, lfm2

    cfg = compose(config["entry_config"], config["overrides"])
    mp = cfg.model_params
    model = create_model(mp.model_name, cfg.dataset_params.num_classes, num_layers=mp.num_hidden_layers, share=mp.share)
    held = lfm2.held(model.cfg, model.share)
    assert model.layers == config["num_hidden_layers"] and model.vocab_size == config["vocab_size"]
    assert list(model.cfg.layer_types) == config["layer_types"] and model.cfg.head_dim == config["head_dim"]
    assert (held["query_heads"], held["kv_heads"]) == (config["num_attention_heads"], config["num_key_value_heads"])
    assert (held["conv_channels"], held["dense_columns"]) == (config["conv_channels_here"], config["dense_columns_here"])
    assert (held["experts_here"], held["expert_offset"]) == (config["num_experts"], config["expert_offset"])
    assert model.cfg.num_experts == config["router_outputs"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "norm_eps", "rope_theta",
                "conv_L_cache", "num_dense_layers", "num_experts_per_tok", "routed_scaling_factor"):  # fmt: skip
        assert getattr(model.cfg, key) == config[key], key


def test_a_sound_run_is_correct_and_the_control_reads_above_it(tmp_path, capsys):
    got = {}

    def after(result):
        got["control"] = result["final"]["control_numbers"](result["final"])
        got["sound"] = {c.name: c.value for c in result["checks"]}
        got["obs"] = result["obs"]

    root, bench = make_bench(tmp_path)
    line = run.run_cell(
        tiny.args(CELL, seed=2**31 + 11, trace=1), platform="cpu", repo_root=root, bench_dir=bench, after=after
    )
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert {"compile_s", "harness_init_s", "epoch_gap_ms", "window_compiles", "lfm2_moe_load_max_over_mean"} <= set(line["metrics"])
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert 1.0 <= line["metrics"]["lfm2_moe_load_max_over_mean"]["value"] < 4.0
    # No device: nothing of a trace, and nothing under another cell's names.
    assert not {"shortconv_ms", "lfm2_moe_ms", "step_ms", "level_s", "moe_ms", "moe_swiglu_ms"} & set(line["metrics"])
    out = capsys.readouterr().out
    assert "(token, expert) pairs in 2 routed layers of 4 experts held" in out and "moe_dropped_pairs 0" in out
    sound, control, moe = got["sound"], got["control"], got["obs"]["lfm2_moe"]
    # 128 tokens a step choose 4 of 16 experts in each of 2 layers; 4 are held: about 256 pairs.
    assert 128 < moe["moe_pairs"] < 512 and moe["moe_dropped_pairs"] == 0 and moe["layers"] == 2
    assert moe["moe_rounds"] == 2  # a round a layer a step
    assert got["obs"]["kernel_counts"]["moe_pairs_per_step"] == moe["moe_pairs"]
    assert sound["moe_dropped_pairs"] == 0 and sound["routing_mismatch"] == 0.0
    assert set(control) == {"eval_loss_gap", "eval_probe_loss_gap", "train_loss_gap", "momentum_norm_gap", "update_norm_gap"}
    for name in ("eval_probe_loss_gap", "eval_loss_gap", "update_norm_gap", "momentum_norm_gap"):
        assert 5 * sound[name] < control[name], name
    assert sound["eval_loss_gap"] < 1e-6 and sound["update_norm_gap"] < 1e-4
    json.dumps(line)


def test_a_step_of_the_published_cut_is_the_hand_count():
    """Shapes only: the ten layers at published widths as this chip holds
    them, 16,384 ids, one packed sequence of 8,192 tokens in the cell's own
    layout, 8 x 8,192 pairs a step."""
    from turboprune_tpu.config import compose
    from turboprune_tpu.data.tokens import document_layout
    from turboprune_tpu.models import create_model

    config = registry.load_config(REAL_CONFIG)
    cfg = compose(config["entry_config"], config["overrides"])
    mp = cfg.model_params
    model = create_model(mp.model_name, 16384, num_layers=mp.num_hidden_layers, share=mp.share)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 2, 256), jnp.int32))["params"]
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == 765_460_480 + 34_134_528
    seg = document_layout(6, 8192, 6.5, 1.2, 16, 8192, 0).reshape(6, 1, 8192)
    tokens, pairs = 8192, 8 * 8192.0
    counts = lfm2_flops.step_counts(shapes, config, seg, pairs)
    conv, attn, dense, expert = 4_194_304, 2_621_440, 11_010_048, 11_010_048
    per_token = 8 * conv + 2 * attn + 2 * dense + 8 * 2048 * 32 + 16384 * 2048
    experts = pairs * 2 * expert
    gates = 8 * tokens * 512 * (2 * 3 + 2)
    want = 3 * (2 * tokens * per_token + gates + experts) + counts["flash_causal_flops"]
    assert counts["step_flops"] == pytest.approx(want, rel=1e-12)
    assert lfm2_flops.expert_pair_flops(2048, 1792) == 2 * expert == 22_020_096
    # ISSUE 40's arithmetic: 8.99 TFLOP and attention; experts 4.33 of it, the head 1.65.
    assert 8.99e12 < counts["step_flops"] < 9.15e12
    assert 3 * experts == pytest.approx(4.33e12, rel=2e-3) and 3 * 2 * tokens * 16384 * 2048 == pytest.approx(1.65e12, rel=2e-3)
    assert counts["lfm2_experts_flops"] == 4 * experts
    assert counts["lfm2_experts_bytes"] == 4 * 2 * (8 * 3 * 8 * 2048 * 1792 + pairs * 3 * (2048 + 1792))
    # The mixer: both projections with the gates and taps; its input, output and kernels, nothing of B, C, u.
    assert counts["shortconv_flops"] == 3 * (2 * tokens * 8 * conv + gates) == pytest.approx(1.65e12, rel=1e-2)
    assert counts["shortconv_bytes"] == 3 * 8 * 2 * (2 * tokens * 2048 + conv)
    assert lfm2_flops.gates_forward_flops(1, 512, 3) == 512 * 8
    # Attention as the granite cell counts it: 8 query heads on 2 key/value heads of 64, two layers.
    per_pair = 4 * 64 * 8
    assert counts["flash_causal_flops"] == 3 * 2 * per_pair * counts["causal_pairs_per_step"]
    assert counts["flash_causal_bytes"] == 3 * 2 * tokens * 2 * 64 * (2 * 8 + 2 * 2)
    assert counts["tokens_per_step"] == tokens and counts["moe_pairs_per_step"] == pairs


def test_the_new_readers_read_their_split_or_nothing():
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    counts = {
        "lfm2_experts_flops": 2e9, "lfm2_experts_bytes": 1e8, "shortconv_flops": 1e6, "shortconv_bytes": 2e8,
        "flash_causal_flops": 3e9, "flash_causal_bytes": 1e7,
    }  # fmt: skip
    split = {"moe/experts": 8.0, "moe/dispatch": 2.0, "moe/router": 1.0, "conv/gate_conv": 4.0,
             "conv/in_proj": 9.0, "attn/flash": 6.0}  # fmt: skip
    obs = {"scope_ms": split, "kernel_counts": counts, "peaks": peaks,
           "lfm2_moe": {"moe_pairs": 100.0, "moe_load_max": 30.0, "experts_here": 4, "layers": 1}}  # fmt: skip
    read = lambda name, o=obs: registry.load_metric(name).read(o)
    assert read("lfm2_moe_ms") == 11.0 and read("shortconv_ms") == 13.0  # every conv/* scope
    assert read("lfm2_moe_experts_roofline_pct") == pytest.approx(100 * 2e-3 / 8e-3)
    assert read("shortconv_roofline_pct") == pytest.approx(100 * 2e-3 / 13e-3)  # here its bytes bound it
    assert read("lfm2_flash_causal_roofline_pct") == pytest.approx(100 * 3e-3 / 6e-3)
    assert read("lfm2_moe_load_max_over_mean") == pytest.approx(30.0 * 4 / 100.0)
    # A program without the layer (the parent, another model), a run without a trace: nothing, and no error.
    others = {"scope_ms": {"ssd": 3.0, "moe/experts": 5.0, "attn/flash": 2.0}, "moe": {"moe_pairs": 1.0},
              "kernel_counts": {"ssd_flops": 1.0, "experts_flops": 1.0, "flash_causal_flops": 1.0, "flash_causal_bytes": 1.0}}  # fmt: skip
    for name in NEW:
        assert read(name, {"peaks": peaks, "trace": None}) is None
        assert read(name, {"peaks": peaks, "trace": None, **others}) is None
