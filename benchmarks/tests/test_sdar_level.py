"""The block-diffusion cell's job (``jobs/sdar_level.py``) at a test's size on
the CPU: a sound run is correct, counts its masked targets against the arrays
it was fed, drops no pair, and reports what its cell declares; the float8
control reads above it; a mask that lets a noised row see its own clean
tokens is not correct by orders of magnitude; the operation counts are what
their definitions say and the registry finds every new name. None of the
numbers is a device number."""

import json
import shutil
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import registry, run, sdar_flops
from benchmarks.reference import sdar_moe as reference
from benchmarks.tests import tiny

BENCH = tiny.BENCH
CELL = "tiny-blockdiff-level"
REAL_CELL = "sdar-30b-a3b-blockdiff-level-8k"
REAL_CONFIG = "sdar-30b-a3b-chat"
# The tiny preset as one chip of four holds it: two query heads with the
# key/value head they read, experts 4-7 of 16.
SPEC = {
    "rms_norm_eps": 1e-6, "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 8,
    "rope_theta": 1000000, "num_experts_per_tok": 4, "expert_offset": 4, "num_experts": 4,
    "mask_row_scale": 0.01,
}  # fmt: skip
OVERRIDES = [
    "model_params.model_name=sdar_moe_tiny",
    "model_params.num_hidden_layers=2",
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
    """A scratch benchmark with one tiny block-diffusion cell: the real job,
    metrics and peaks, copied; the configuration and the cell written here."""
    bench = root / "benchmarks"
    for sub in ("jobs", "metrics"):
        shutil.copytree(BENCH / sub, bench / sub)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    (bench / "configs").mkdir()
    (bench / "workloads").mkdir()
    config = {"name": "tiny-sdar", "entry_config": "sdar_30b_a3b_imp", "overrides": OVERRIDES, **SPEC}
    (bench / "configs" / "tiny-sdar.json").write_text(json.dumps(config))
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
    benchmark["configs"] = [{"name": "tiny-sdar"}]
    benchmark["workloads"] = [{"name": CELL, "config": "tiny-sdar", "traffic": CELL, "chips": 1}]
    swap = lambda m: {**m, "workloads": [CELL if w == REAL_CELL else w for w in m["workloads"]]} if "workloads" in m else m
    benchmark["end_to_end"] = [swap(m) for m in tiny.REAL["end_to_end"]]
    benchmark["per_layer"] = [swap(m) for m in tiny.REAL["per_layer"]]
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return root, bench


def _run(tmp_path, limits=None, after=None, **kw):
    root, bench = make_bench(tmp_path, limits)
    return run.run_cell(tiny.args(CELL, **kw), platform="cpu", repo_root=root, bench_dir=bench, after=after)


NEW = [
    "blockdiff_attn_ms", "flash_blockdiff_roofline_pct", "moe_swiglu_ms",
    "moe_swiglu_experts_roofline_pct", "moe_softmax_load_max_over_mean", "noise_ms",
]  # fmt: skip


def test_the_real_cell_declares_what_the_issue_names():
    benchmark = registry.load_benchmark(BENCH.parent)
    entry = registry.cell_entry(benchmark, REAL_CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (REAL_CONFIG, "blockdiff-level-8k", 1)
    # By name, not by place: a later PR appends after these.
    config_entry = next(c for c in benchmark["configs"] if c["name"] == REAL_CONFIG)
    assert all(len(x["why"]) <= 200 for x in benchmark["workloads"] + benchmark["configs"])
    assert len(config_entry["source"]) <= 200 and config_entry["file"] == f"benchmarks/configs/{REAL_CONFIG}.json"
    assert config_entry["reduced"] == registry.load_config(REAL_CONFIG)["reduced"]
    assert [m["name"] for m in registry.metrics_for(benchmark, REAL_CELL, False)] == ["train_img_per_s", "setup_s"]
    traced = {m["name"] for m in registry.metrics_for(benchmark, REAL_CELL, True)}
    unlisted = {m["name"] for m in benchmark["per_layer"] if "workloads" not in m and m["moves"] in ("train_img_per_s", "setup_s")}
    assert set(NEW) | unlisted <= traced and {"step_ms", "step_mfu_pct", "peak_hbm_gib", "setup_cache_miss_s"} <= unlisted
    assert not traced & {"ssd_ms", "flash_causal_roofline_pct", "augment_ms", "moe_ms", "moe_experts_roofline_pct", "moe_load_max_over_mean"}
    for m in (m for m in benchmark["per_layer"] if m["name"] in NEW):
        assert REAL_CELL in m["workloads"] and m["moves"] == "train_img_per_s"
        assert callable(registry.load_metric(m["name"]).read)
    for old in ("r50-imagenet-dense-level", "r18-cifar10-imp-ladder", "granite-h-micro-dense-level-8k", "nemotron3-super-moe-level-8k"):
        assert not set(NEW) & {m["name"] for m in registry.metrics_for(benchmark, old, True)}
    # The sparse-expert cell's traffic, letter for letter; its limits' names.
    cell, other = registry.load_workload(REAL_CELL), registry.load_workload("nemotron3-super-moe-level-8k")
    assert cell["params"] == other["params"] and cell["job"] == "sdar_level"
    assert set(cell["limits"]) == set(other["limits"]) and cell["limits"]["moe_dropped_pairs"] == ["max", 0]
    assert set(cell["limits"]) < set(cell["limits_why"]) | {"nonfinite_losses", "images_miscounted", "ladder_excess_weights", "moe_dropped_pairs"}
    registry.load_job(cell["job"])


def test_the_configuration_keeps_every_published_number_but_the_reduced():
    """The catalog's ``config`` is the source's as the guide holds it: every
    key of it is in the file, changed only where ``reduced`` says, with the
    published value beside it; no width is among those."""
    config = registry.load_config(REAL_CONFIG)
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936,
    }  # fmt: skip
    here = {"num_hidden_layers": 8, "num_experts": 16, "vocab_size": 18992, "num_attention_heads": 4, "num_key_value_heads": 1}
    assert config["reduced"] == list(here) and config["published"] == {k: published[k] for k in here}
    for key, value in published.items():
        assert config[key] == here.get(key, value), key
    assert not any(w in k for k in here for w in ("hidden_size", "intermediate", "_dim", "_rank", "per_tok"))
    # The floors: four layers, eight experts, an eighth of the vocabulary.
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8 and config["vocab_size"] * 8 >= published["vocab_size"]
    from turboprune_tpu.models import sdar

    d = config["deployment"]
    assert (d["chips_a_layer"], d["tensor_parallel"], d["expert_parallel"], d["expert_rank"]) == (8, 8, 8, 0)
    assert {"block_length", "schedule_and_weight", "query_key_norm", "initialisation", "optimizer",
            "recomputation", "pair_buffer", "token_ids"} <= set(config["assumed"])  # fmt: skip
    assert config["router_outputs"] == 128 and config["mask_token_id"] == config["vocab_size"] - 1
    assert config["mask_row_scale"] == sdar.MASK_ROW and "mask_row_scale" in config["assumed"]
    # What the model builds from the entry config and the file's overrides is the file's share.
    from turboprune_tpu.config import compose
    from turboprune_tpu.models import create_model, sdar
    from turboprune_tpu.ops import moe

    cfg = compose(config["entry_config"], config["overrides"])
    mp = cfg.model_params
    model = create_model(mp.model_name, cfg.dataset_params.num_classes, num_layers=mp.num_hidden_layers, share=mp.share)
    held = sdar.held(model.cfg, model.share)
    assert model.layers == config["num_hidden_layers"] and model.vocab_size == config["vocab_size"]
    assert (held["query_heads"], held["kv_heads"], held["experts_here"], held["expert_offset"]) == (4, 1, 16, config["expert_offset"])
    assert cfg.dataset_params.block_length == config["block_length"] and cfg.optimizer_params.lr == 0.002
    assert (model.cfg.hidden_size, model.cfg.head_dim, model.cfg.moe_intermediate_size, model.cfg.num_experts,
            model.cfg.num_experts_per_tok, model.cfg.rope_theta, model.cfg.rms_norm_eps) == (2048, 128, 768, 128, 8, 1e6, 1e-6)  # fmt: skip
    assert moe.pair_capacity(16384, 8, 128, 16) == 26624 and moe.pair_tile(16384, 8, 128) == 128
    # The arithmetic of bytes_per_parameter, from the tree.
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 5, 8), jnp.int32))["params"]
    from turboprune_tpu.ops import masking

    sizes = [(masking.is_prunable_path(p), int(np.prod(x.shape))) for p, x in jax.tree_util.tree_leaves_with_path(shapes)]
    prunable, rest = sum(n for yes, n in sizes if yes), sum(n for yes, n in sizes if not yes)
    assert (prunable, rest) == (663_846_912, 41_029_632)
    assert f"{prunable:,}" in config["bytes_per_parameter"] and f"{rest:,}" in config["bytes_per_parameter"]
    assert abs((13 * prunable + 12 * rest) / 2**30 - 8.50) < 0.01


def test_a_sound_run_is_correct_and_the_control_reads_above_it(tmp_path, capsys):
    got = {}

    def after(result):
        got["control"] = result["final"]["control_numbers"](result["final"])
        got["sound"] = {c.name: c.value for c in result["checks"]}
        got["obs"] = result["obs"]

    line = _run(tmp_path, after=after, seed=2**31 + 11, trace=1)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert {"compile_s", "harness_init_s", "epoch_gap_ms", "epoch_log_ms", "window_compiles",
            "moe_softmax_load_max_over_mean", "setup_cache_miss_s", "first_epoch_s"} <= set(line["metrics"])  # fmt: skip
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert 1.0 <= line["metrics"]["moe_softmax_load_max_over_mean"]["value"] < 4.0
    assert not {"moe_swiglu_ms", "blockdiff_attn_ms", "step_ms", "level_s", "moe_ms", "moe_load_max_over_mean"} & set(line["metrics"])  # no device
    out = capsys.readouterr().out
    assert "(row, expert) pairs in 2 layers of 4 experts held" in out and "moe_dropped_pairs 0" in out
    assert "rounds 1.000" in out and "masked targets" in out
    sound, control, moe = got["sound"], got["control"], got["obs"]["moe_softmax"]
    # 256 rows a step choose 4 of 16 experts; 4 are held, in 2 layers: about 512 pairs.
    assert 256 < moe["moe_pairs"] < 1024 and moe["moe_dropped_pairs"] == 0 and moe["layers"] == 2
    assert 32 < moe["masked_targets"] < 96  # a half of 128 tokens a step, about
    counts = got["obs"]["kernel_counts"]
    assert counts["moe_pairs_per_step"] == moe["moe_pairs"] and counts["rows_per_step"] == 256
    assert sound["moe_dropped_pairs"] == 0 and sound["routing_mismatch"] == 0.0 and sound["images_miscounted"] == 0
    assert set(control) == {"eval_loss_gap", "eval_probe_loss_gap", "train_loss_gap", "momentum_norm_gap", "update_norm_gap"}
    for name in ("eval_probe_loss_gap", "eval_loss_gap", "update_norm_gap", "momentum_norm_gap"):
        assert 5 * sound[name] < control[name], name
    assert sound["eval_loss_gap"] < 1e-6 and sound["update_norm_gap"] < 1e-4
    json.dumps(line)


def test_a_mask_that_shows_a_noised_row_its_own_clean_tokens_is_not_correct(tmp_path, capsys):
    """The reference's rule with ``<`` made ``<=`` for a noised query on clean
    keys: the answer is then in sight of every masked row, and the eval gaps
    move by orders of magnitude."""
    real = reference.keep_rows

    def leaky(doc, blk, rows):
        t = doc.shape[1]
        blk2 = jnp.concatenate([blk, blk], axis=1)
        noised = jnp.arange(2 * t) >= t
        own = (blk2[:, rows, None] == blk2[:, None, :]) & noised[rows][None, :, None] & ~noised[None, None, :]
        doc2 = jnp.concatenate([doc, doc], axis=1)
        return real(doc, blk, rows) | (own & (doc2[:, rows, None] == doc2[:, None, :]))

    with mock.patch.object(reference, "keep_rows", leaky):
        line = _run(tmp_path, limits={"eval_loss_gap": ["max", 1e-4], "eval_probe_loss_gap": ["max", 1e-4]})
    assert line["correct"] is False
    out = capsys.readouterr().out
    gaps = {l.split()[1]: float(l.split()[3]) for l in out.splitlines() if l.startswith("[correct]")}
    # A sound run reads under 1e-6 (the test above): two orders and more, with
    # untrained weights whose attention writes little.
    assert gaps["eval_loss_gap"] > 1e-4 and gaps["eval_probe_loss_gap"] > 1e-4, gaps
    assert out.count("NOT CORRECT") >= 2


def test_an_epochs_targets_are_held_to_the_arrays_it_was_fed(tmp_path):
    """A model whose own count of masked rows is off by one a step is not
    correct: ``images_miscounted`` reads the difference."""
    from turboprune_tpu.models import sdar

    real = jnp.sum
    with mock.patch.object(sdar.jnp, "sum", lambda x, **kw: real(x, **kw) + (1 if kw.get("dtype") == jnp.int32 and x.dtype == jnp.bool_ and x.ndim == 2 else 0)):
        line = _run(tmp_path)
    assert line["correct"] is False


def test_the_counts_are_the_definitions():
    assert sdar_flops.expert_pair_flops(2048, 768) == 6 * 2048 * 768 == 9_437_184
    assert sdar_flops.attention_forward_flops(10, 4, 128) == 4 * 128 * 4 * 10
    assert sdar_flops.attention_forward_bytes(16384, 4, 1, 128) == 16384 * 2 * 128 * 10
    # One document of 10 tokens in blocks of 4 (4, 4, 2): a clean query keeps
    # the keys to its block's end (4 x 4 + 4 x 8 + 2 x 10), a noised one as many.
    seg = np.zeros((1, 1, 10), np.int32)
    assert sdar_flops.kept_pairs(seg, 4) == 2 * (16 + 32 + 20)
    # Against the rule, written out, on a packed layout.
    from turboprune_tpu.data.tokens import blockdiff_kept_pairs, block_ordinals, document_layout

    seg = document_layout(3, 64, 2.5, 1.2, 2, 64, 0)
    blk = block_ordinals(seg, 3)[0]
    keep = np.asarray(reference.keep_rows(jnp.asarray(seg), jnp.asarray(blk), jnp.arange(128)))
    assert sdar_flops.kept_pairs(seg, 3) == keep.sum() == blockdiff_kept_pairs(seg, 3)


def test_a_step_of_the_published_cut_is_the_hand_count():
    """Shapes only: the eight layers at published widths as this chip holds
    them, 18,992 ids, one packed sequence of 8,192 tokens in the cell's own
    layout as 16,384 rows, 8 x 16,384 pairs a step."""
    from turboprune_tpu.config import compose
    from turboprune_tpu.data.tokens import document_layout
    from turboprune_tpu.models import create_model

    config = registry.load_config(REAL_CONFIG)
    cfg = compose(config["entry_config"], config["overrides"])
    mp = cfg.model_params
    model = create_model(mp.model_name, 18992, num_layers=mp.num_hidden_layers, share=mp.share)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 5, 256), jnp.int32))["params"]
    seg = document_layout(6, 8192, 6.5, 1.2, 16, 8192, 0).reshape(6, 1, 8192)
    pairs = 8 * 16384.0
    counts = sdar_flops.step_counts(shapes, config, seg, 4, pairs)
    rows, tokens = 16384, 8192
    per_row = 8 * (2048 * 512 + 2 * 2048 * 128 + 512 * 2048 + 2048 * 128)  # q, k and v, o, router
    experts = pairs * 6 * 2048 * 768
    attention = 8 * 4 * 128 * 4 * counts["kept_pairs_per_step"]
    want = 3 * (2 * rows * per_row + 2 * tokens * 2048 * 18992 + experts + attention)
    assert counts["step_flops"] == pytest.approx(want, rel=1e-12)
    # ISSUE 38: 8.6-8.7 TFLOP a step (layers 2.25 forward, head 0.64, three passes).
    assert 8.3e12 < counts["step_flops"] < 9.0e12
    assert 2 * tokens * 2048 * 18992 == pytest.approx(0.637e12, rel=0.01)
    assert counts["swiglu_experts_flops"] == 4 * experts
    assert counts["swiglu_experts_bytes"] == 4 * 2 * (8 * 3 * 16 * 2048 * 768 + pairs * 3 * (2048 + 768))
    assert counts["flash_blockdiff_flops"] == 3 * attention
    assert counts["flash_blockdiff_bytes"] == 3 * 8 * rows * 2 * 128 * (2 * 4 + 2 * 1)
    assert counts["tokens_per_step"] == tokens and counts["rows_per_step"] == rows


def test_the_new_readers_read_their_split_or_nothing():
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    counts = {"swiglu_experts_flops": 2e9, "swiglu_experts_bytes": 1e8, "flash_blockdiff_flops": 1e9, "flash_blockdiff_bytes": 4e8}
    split = {"moe/experts": 8.0, "moe/dispatch": 2.0, "moe/router": 1.0, "attn/flash": 5.0}
    trace = {"modules": {"jit_noise_epoch(123)": [0.002, 0.004], "jit_scan_chunk(9)": [1.0]}}
    obs = {"scope_ms": split, "kernel_counts": counts, "peaks": peaks, "trace": trace, "noise_program": "jit_noise_epoch",
           "moe_softmax": {"moe_pairs": 100.0, "moe_load_max": 30.0, "experts_here": 4, "layers": 1}}  # fmt: skip
    read = lambda name, o=obs: registry.load_metric(name).read(o)
    assert read("moe_swiglu_ms") == 11.0 and read("blockdiff_attn_ms") == 5.0
    assert read("moe_swiglu_experts_roofline_pct") == pytest.approx(100 * 2e-3 / 8e-3)
    assert read("flash_blockdiff_roofline_pct") == pytest.approx(100 * 4e-3 / 5e-3)  # bound by bytes
    assert read("moe_softmax_load_max_over_mean") == pytest.approx(30.0 * 4 / 100.0)
    assert read("noise_ms") == pytest.approx(3.0)
    # A program without the layer (the parent's), a run without a trace: nothing, and no error.
    for name in NEW:
        assert read(name, {"peaks": peaks, "trace": None}) is None
        other = {"peaks": peaks, "trace": {"modules": {}}, "scope_ms": {"attn/flash": 3.0, "moe/experts": 2.0},
                 "kernel_counts": {"flash_causal_flops": 1.0, "experts_flops": 1.0}, "moe": {"moe_pairs": 1.0}}  # fmt: skip
        assert read(name, other) is None


def test_the_forward_count_of_one_whole_document_is_the_hand_count():
    """The case ISSUE 38 asks of ``test_model_flops.py``, kept here because a
    PR outside the benchmark may add files under ``benchmarks/`` and edit
    none: the cut, one 8,192-token document as 16,384 rows under a uniform
    router, a forward pass by hand, and a step three of them."""
    from turboprune_tpu.models import create_model

    model = create_model("sdar_30b_a3b", 18992, num_layers=8, share=(8, 8, 0))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 5, 64), jnp.int32))["params"]
    spec = {"num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 128}
    counts = sdar_flops.step_counts(shapes, spec, np.zeros((1, 1, 8192), np.int32), 4, 8 * 16384.0)
    # Blocks of 4 in one document: a query of block b keeps 4 (b + 1) keys, both copies.
    kept = 2 * 4 * sum(4 * (b + 1) for b in range(2048))
    assert counts["kept_pairs_per_step"] == kept == 67_141_632
    forward = (
        16384 * 8 * 2 * (2 * 2048 * 512 + 2 * 2048 * 128 + 2048 * 128)  # q and o, k and v, the router
        + 8 * 16384 * 6 * 2048 * 768  # a pair a row and layer, three products
        + 8 * 4 * 128 * 4 * kept  # q k^T and the weighted sum, four heads
        + 8192 * 2 * 2048 * 18992  # the head, the noised rows alone
    )
    assert counts["step_flops"] == 3 * forward
    assert forward / 1e12 == pytest.approx(3.73, abs=0.01)  # of it 1.10 attention: one document is the worst layout
