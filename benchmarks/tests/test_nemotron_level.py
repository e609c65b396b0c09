"""The sparse-expert cell's job (``jobs/nemotron_level.py``) at a test's size
on the CPU: a sound run is correct, counts its pairs and drops none, and
reports what its cell declares; a router in a lower precision and a dispatch
that drops pairs are not correct; the float8 control reads above a sound run;
the operation counts are what their definitions say and the registry finds
every new name. None of the numbers is a device number."""

import json
import shutil
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import nemotron_flops, registry, run
from benchmarks.tests import tiny

BENCH = tiny.BENCH
CELL = "tiny-moe-level"
REAL_CELL = "nemotron3-super-moe-level-8k"
REAL_CONFIG = "nemotron-3-super-120b-a12b"
# The tiny preset as one chip of four holds it: half of every mixer's heads
# and of the shared expert's columns, experts 4-7 of 16.
SPEC = {
    "layer_norm_epsilon": 1e-5, "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 8,
    "n_groups": 1, "num_experts_per_tok": 4, "routed_scaling_factor": 5.0, "expert_offset": 4,
    "chunk_size": 16, "n_routed_experts": 4,
}  # fmt: skip
OVERRIDES = [
    "model_params.model_name=nemotron_h_tiny",
    "model_params.layer_pattern=EM*",
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
    """A scratch benchmark with one tiny sparse-expert cell: the real job,
    metrics and peaks, copied; the configuration and the cell written here."""
    bench = root / "benchmarks"
    for sub in ("jobs", "metrics"):
        shutil.copytree(BENCH / sub, bench / sub)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    (bench / "configs").mkdir()
    (bench / "workloads").mkdir()
    config = {"name": "tiny-moe", "entry_config": "nemotron3_super_imp", "overrides": OVERRIDES, **SPEC}
    (bench / "configs" / "tiny-moe.json").write_text(json.dumps(config))
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
    benchmark["configs"] = [{"name": "tiny-moe"}]
    benchmark["workloads"] = [{"name": CELL, "config": "tiny-moe", "traffic": CELL, "chips": 1}]
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
    assert (entry["config"], entry["traffic"], entry["chips"]) == (REAL_CONFIG, "dense-level-8k", 1)
    assert benchmark["workloads"][-1] == entry and benchmark["configs"][-1]["name"] == REAL_CONFIG
    assert [m["name"] for m in registry.metrics_for(benchmark, REAL_CELL, False)] == ["train_img_per_s", "setup_s"]
    traced = {m["name"] for m in registry.metrics_for(benchmark, REAL_CELL, True)}
    new = {"moe_ms", "moe_experts_roofline_pct", "moe_load_max_over_mean"}
    assert new | {"step_ms", "step_mfu_pct", "device_idle_pct", "peak_hbm_gib", "compile_s"} <= traced
    assert not traced & {"ssd_ms", "ssd_roofline_pct", "flash_causal_roofline_pct", "augment_ms"}
    assert [m["name"] for m in benchmark["per_layer"][-3:]] == ["moe_ms", "moe_experts_roofline_pct", "moe_load_max_over_mean"]
    for m in benchmark["per_layer"][-3:]:
        assert m["workloads"] == [REAL_CELL] and m["moves"] == "train_img_per_s"
        assert callable(registry.load_metric(m["name"]).read)
    for old in ("r50-imagenet-dense-level", "r18-cifar10-imp-ladder", "granite-h-micro-dense-level-8k"):
        assert not new & {m["name"] for m in registry.metrics_for(benchmark, old, True)}
    # The granite cell's traffic, letter for letter; the limits its names and two more.
    cell, granite = registry.load_workload(REAL_CELL), registry.load_workload("granite-h-micro-dense-level-8k")
    assert cell["params"] == granite["params"] and cell["job"] == "nemotron_level"
    assert set(cell["limits"]) == set(granite["limits"]) | {"moe_dropped_pairs", "routing_mismatch"}
    assert cell["limits"]["moe_dropped_pairs"] == ["max", 0]
    registry.load_job(cell["job"])


def test_the_configuration_keeps_every_published_number_but_the_reduced():
    """The catalog's ``config`` is the source's as the guide holds it: every
    number of it is in the file under its key, changed only where ``reduced``
    says, with the published value beside it; no width is among those."""
    config = registry.load_config(REAL_CONFIG)
    published = {
        "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128, "hidden_size": 4096,
        "intermediate_size": 2688, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_num_heads": 128, "max_position_embeddings": 262144, "moe_intermediate_size": 2688,
        "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 512, "n_shared_experts": 1, "norm_eps": 1e-05,
        "num_attention_heads": 32, "num_experts_per_tok": 22, "num_hidden_layers": 88,
        "num_key_value_heads": 2, "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rope_theta": 10000, "routed_scaling_factor": 5,
        "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "vocab_size": 131072,
    }  # fmt: skip
    here = {
        "num_hidden_layers": 11, "n_routed_experts": 16, "vocab_size": 16384, "mamba_num_heads": 16,
        "n_groups": 1, "num_attention_heads": 4, "num_key_value_heads": 1, "num_nextn_predict_layers": 0,
    }  # fmt: skip
    assert config["reduced"] == list(here) and config["published"] == {k: published[k] for k in here}
    for key, value in published.items():
        assert config[key] == here.get(key, value), key
    assert not any(w in k for k in here for w in ("hidden_size", "intermediate", "latent", "state", "_dim", "_rank", "expand", "per_tok"))
    d = config["deployment"]
    assert (d["chips_a_layer"], d["tensor_parallel"], d["expert_parallel"], d["expert_rank"]) == (32, 8, 32, 0)
    assert {"latent_moe_layout", "initialisation", "optimizer", "selection_bias", "rope_theta"} <= set(config["assumed"])
    assert config["layers_run"].startswith("EMEMEMEMEM*") and "EMEMEMEMEM*" in config["hybrid_override_pattern"]
    assert config["hybrid_override_pattern"][26:37] == "EMEMEMEMEM*"
    # What the model builds from the entry config and the file's overrides is the file's share.
    from turboprune_tpu.config import compose
    from turboprune_tpu.models import create_model

    cfg = compose(config["entry_config"], config["overrides"])
    mp = cfg.model_params
    model = create_model(mp.model_name, cfg.dataset_params.num_classes, layer_pattern=mp.layer_pattern, share=mp.share)
    held = model.share.of(model.cfg)
    assert model.pattern == "EMEMEMEMEM*" and model.vocab_size == config["vocab_size"]
    assert (held["mamba_heads"], held["mamba_groups"], held["query_heads"], held["kv_heads"]) == (16, 1, 4, 1)
    assert (held["experts_here"], held["expert_offset"], held["shared_columns"]) == (16, config["expert_offset"], config["moe_shared_expert_columns_here"])


def test_a_sound_run_is_correct_and_the_control_reads_above_it(tmp_path, capsys):
    got = {}

    def after(result):
        got["control"] = result["final"]["control_numbers"](result["final"])
        got["sound"] = {c.name: c.value for c in result["checks"]}
        got["obs"] = result["obs"]

    line = _run(tmp_path, after=after, seed=2**31 + 11, trace=1)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert {"compile_s", "harness_init_s", "epoch_gap_ms", "epoch_log_ms", "window_compiles", "moe_load_max_over_mean"} <= set(line["metrics"])
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert 1.0 <= line["metrics"]["moe_load_max_over_mean"]["value"] < 4.0
    assert not {"moe_ms", "moe_experts_roofline_pct", "step_ms", "level_s", "ssd_ms"} & set(line["metrics"])  # no device
    out = capsys.readouterr().out
    assert "(token, expert) pairs in 1 layers of 4 experts held" in out and "moe_dropped_pairs 0" in out
    sound, control, moe = got["sound"], got["control"], got["obs"]["moe"]
    # 128 tokens a step choose 4 of 16 experts; 4 are held: about 128 pairs.
    assert 64 < moe["moe_pairs"] < 256 and moe["moe_dropped_pairs"] == 0 and moe["layers"] == 1
    assert got["obs"]["kernel_counts"]["moe_pairs_per_step"] == moe["moe_pairs"]
    assert sound["moe_dropped_pairs"] == 0 and sound["routing_mismatch"] == 0.0
    assert set(control) == {"eval_loss_gap", "eval_probe_loss_gap", "train_loss_gap", "momentum_norm_gap", "update_norm_gap"}
    for name in ("eval_probe_loss_gap", "eval_loss_gap", "update_norm_gap", "momentum_norm_gap"):
        assert 5 * sound[name] < control[name], name
    assert sound["eval_loss_gap"] < 1e-6 and sound["update_norm_gap"] < 1e-4
    json.dumps(line)


def test_a_router_in_a_lower_precision_is_not_correct(tmp_path, capsys):
    """Sixteen experts lie far apart where 512 lie close: what bfloat16 does
    to the real router, float8 does to this one."""
    from turboprune_tpu.ops import moe

    real = moe.route
    low = lambda logits, *a: real(logits.astype(jnp.float8_e4m3fn).astype(jnp.float32), *a)
    with mock.patch.object(moe, "route", low):
        line = _run(tmp_path, limits={"routing_mismatch": ["max", 0.002]})
    assert line["correct"] is False
    out = capsys.readouterr().out
    assert "routing_mismatch" in out and out.count("NOT CORRECT") >= 1


def test_a_dispatch_that_drops_pairs_is_not_correct(tmp_path):
    """A buffer of one tile of rows and the loop over the further rounds cut
    off (for the whole run: the loop is traced when the step is
    differentiated): the counter says how many pairs no product computed."""
    from turboprune_tpu.ops import moe

    class NoFurtherRound:
        def __getattr__(self, name):
            return getattr(jax.lax, name)

        @staticmethod
        def while_loop(pending, further, state):
            return state

    with mock.patch.object(moe, "lax", NoFurtherRound()), mock.patch.object(
        moe, "pair_capacity", lambda tokens, top_k, experts, held: moe.pair_tile(tokens, top_k, experts)
    ):
        line = _run(tmp_path)
    assert line["correct"] is False


def test_the_counts_are_the_definitions():
    assert nemotron_flops.expert_pair_flops(1024, 2688) == 2 * 2 * 1024 * 2688
    heads, p, n, q = 16, 64, 128, 128
    assert nemotron_flops.ssd_forward_flops(1, heads, p, n, 1, q) == 2 * q * n + 2 * q * p * heads + 4 * n * p * heads
    assert nemotron_flops.ssd_forward_flops(1, 128, p, n, 8, q) - nemotron_flops.ssd_forward_flops(1, 128, p, n, 1, q) == 7 * 2 * q * n
    assert nemotron_flops.ssd_forward_bytes(1, heads, p, n, 1) == 2 * (2 * 1024 + 2 * 128 + 16)


def test_a_step_of_the_published_cut_is_the_hand_count():
    """Shapes only: the eleven layers at published widths as this chip holds
    them, 16,384 ids, one packed sequence of 8,192 tokens in the cell's own
    layout, 5 x 5,632 pairs a step."""
    from turboprune_tpu.config import compose
    from turboprune_tpu.data.tokens import document_layout
    from turboprune_tpu.models import create_model

    config = registry.load_config(REAL_CONFIG)
    mp = compose(config["entry_config"], config["overrides"]).model_params
    model = create_model(mp.model_name, 16384, layer_pattern=mp.layer_pattern, share=mp.share)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 2, 256), jnp.int32))["params"]
    sizes = {k: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(v)) for k, v in shapes.items()}
    # ISSUE 34's arithmetic: M 13.70 M, * 5.24 M, E 104.1 M, embedding and head 67.1 M each.
    near = lambda name, millions: abs(sizes[name] / 1e6 - millions) < 0.05  # the kernels, and a norm and a convolution
    assert near("layers_1", 13.70) and near("layers_10", 5.24) and near("layers_0", 104.1)
    assert sizes["embedding"] == sizes["lm_head"] == 16384 * 4096
    total = sum(sizes.values())
    assert 727e6 < total < 729e6
    seg = document_layout(6, 8192, 6.5, 1.2, 16, 8192, 0).reshape(6, 1, 8192)
    pairs = 5 * 5632.0
    counts = nemotron_flops.step_counts(shapes, config, seg, pairs)
    tokens = 8192
    per_token = (
        5 * (4096 * 2320 + 1024 * 4096)  # M: in_proj, out_proj
        + (4096 * 512 + 2 * 4096 * 128 + 512 * 4096)  # *: q, k and v, o
        + 5 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 672)  # E: router, latent down and up, shared
        + 4096 * 16384  # head
    )
    ssd = 5 * tokens * (2 * 128 * 128 + 2 * 128 * 64 * 16 + 4 * 128 * 64 * 16)
    experts = pairs * 4 * 1024 * 2688
    want = 3 * (2 * tokens * per_token + ssd + experts) + counts["flash_causal_flops"]
    assert counts["step_flops"] == pytest.approx(want, rel=1e-12)
    # ISSUE 34's 240 M multiply-adds a token forward: 11.8 TFLOP a step.
    assert 11.0e12 < counts["step_flops"] < 12.5e12
    assert counts["experts_flops"] == 4 * experts
    assert counts["experts_bytes"] == 4 * 2 * (5 * 2 * 16 * 1024 * 2688 + pairs * 2 * (1024 + 2688))
    assert counts["ssd_flops"] == 3 * ssd and counts["tokens_per_step"] == tokens


def test_the_new_readers_read_their_split_or_nothing():
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    counts = {"experts_flops": 2e9, "experts_bytes": 1e8}
    split = {"moe/experts": 8.0, "moe/dispatch": 2.0, "moe/router": 1.0, "ssd": 5.0}
    obs = {"scope_ms": split, "kernel_counts": counts, "peaks": peaks,
           "moe": {"moe_pairs": 100.0, "moe_load_max": 30.0, "experts_here": 4, "layers": 1}}  # fmt: skip
    read = lambda name, o=obs: registry.load_metric(name).read(o)
    assert read("moe_ms") == 11.0
    assert read("moe_experts_roofline_pct") == pytest.approx(100 * 2e-3 / 8e-3)
    assert read("moe_load_max_over_mean") == pytest.approx(30.0 * 4 / 100.0)
    # A program without the layer, a run without a trace: nothing, and no error.
    for name in ("moe_ms", "moe_experts_roofline_pct", "moe_load_max_over_mean"):
        assert read(name, {"peaks": peaks, "trace": None}) is None
        assert read(name, {"peaks": peaks, "trace": None, "scope_ms": {"ssd": 3.0}, "kernel_counts": {"ssd_flops": 1.0}}) is None
