"""The registry's table of language models (models/__init__.py) and the rule
models/blocks.py stands for: what two models share is written in a file that
none of them owns. The three public tuples are the table's, in the order they
always had; the sparsity planner's answer for every language model is the
language models' (sparse/graph.py asks the registry); no model file imports
another, and blocks.py imports none of them."""

import ast
import pathlib

import pytest

from turboprune_tpu import models
from turboprune_tpu.sparse.graph import CompactionError, build_graph

MODELS = pathlib.Path(models.__file__).parent
FILES = ("granite", "nemotron_h", "sdar", "lfm2", "brumby")


def _imported(name: str) -> set:
    """The sibling modules ``models/<name>.py`` imports."""
    found = set()
    for node in ast.walk(ast.parse((MODELS / f"{name}.py").read_text())):
        if isinstance(node, ast.ImportFrom) and node.level == 1:
            found |= {node.module} if node.module else {a.name for a in node.names}
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            names = [node.module or ""] if isinstance(node, ast.ImportFrom) else [a.name for a in node.names]
            found |= {n.rpartition(".")[2] for n in names if n.startswith("turboprune_tpu.models")}
    return found


def test_no_model_file_imports_another_and_blocks_imports_none():
    for name in FILES:
        assert _imported(name) == {"blocks"}, name
    assert not _imported("blocks") & {*FILES, "models"}


def test_the_public_tuples_are_the_tables_in_the_order_they_had():
    assert models.LANGUAGE_MODELS == (
        "granite_4_0_h_micro", "hybrid_lm_tiny", "nemotron_3_super_120b_a12b", "nemotron_h_tiny",
        "sdar_30b_a3b", "sdar_moe_tiny", "lfm2_8b_a1b", "lfm2_moe_tiny", "brumby_14b_base", "brumby_tiny",
    )  # fmt: skip
    assert models.SHARED_MODELS == models.LANGUAGE_MODELS[2:]
    assert models.BLOCK_DIFFUSION_MODELS == ("sdar_30b_a3b", "sdar_moe_tiny")
    table = {lm.name: lm for lm in models.LANGUAGE_TABLE}
    assert tuple(table) == models.LANGUAGE_MODELS
    for name, lm in table.items():
        assert models.MODEL_REGISTRY[name] is lm.factory
        assert (name in models.SHARED_MODELS, name in models.BLOCK_DIFFUSION_MODELS) == lm[1:]
    assert not models.is_language_model(models.create_model("resnet18", 10))


@pytest.mark.parametrize("name", ["hybrid_lm_tiny", "nemotron_h_tiny", "sdar_moe_tiny", "lfm2_moe_tiny", "brumby_tiny"])
def test_the_planner_is_told_why_a_language_model_runs_masked(name):
    """Each of the five files' models gets the language models' reason and
    not "compaction supports ResNet, VGG, DenseNet and ViT"."""
    model = models.create_model(name, 50)
    assert models.is_language_model(model)
    with pytest.raises(CompactionError, match="hybrid language models have no propagation graph yet"):
        build_graph(model, {})
