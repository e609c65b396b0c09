import pytest

from turboprune_tpu.config import ConfigError, compose, compose_dict


def test_compose_cifar10_imp():
    cfg = compose("cifar10_imp")
    assert cfg.dataset_params.dataset_name == "CIFAR10"
    assert cfg.dataset_params.num_classes == 10
    assert cfg.dataset_params.image_size == 32
    assert cfg.pruning_params.prune_method == "mag"
    assert cfg.pruning_params.training_type == "imp"
    assert cfg.optimizer_params.lr == 0.2
    assert cfg.optimizer_params.weight_decay == 5e-4
    assert cfg.experiment_params.epochs_per_level == 150
    assert cfg.cyclic_training.num_cycles == 1


def test_compose_all_toplevel_configs():
    from turboprune_tpu.config import DEFAULT_CONFIG_PATH

    names = [p.stem for p in DEFAULT_CONFIG_PATH.glob("*.yaml")]
    assert len(names) >= 12
    for name in names:
        cfg = compose(name)
        cfg.validate()


def test_overrides():
    cfg = compose(
        "cifar10_imp",
        overrides=[
            "optimizer_params.lr=0.01",
            "experiment_params.epochs_per_level=2",
            "dataset_params.total_batch_size=64",
        ],
    )
    assert cfg.optimizer_params.lr == 0.01
    assert cfg.experiment_params.epochs_per_level == 2
    assert cfg.dataset_params.total_batch_size == 64


def test_unknown_key_rejected():
    with pytest.raises(ConfigError):
        compose("cifar10_imp", overrides=["optimizer_params.typo_knob=1"])


def test_bad_choice_rejected():
    with pytest.raises(ConfigError):
        compose("cifar10_imp", overrides=["pruning_params.prune_method=bogus"])


def test_wr_requires_rewind_epoch():
    with pytest.raises(ConfigError):
        compose(
            "cifar10_imp",
            overrides=[
                "pruning_params.training_type=wr",
                "pruning_params.rewind_epoch=null",
            ],
        )


def test_imagenet_defaults():
    d = compose_dict("imagenet_imp")
    assert d["experiment_params"]["distributed"] is True
    cfg = compose("imagenet_imp")
    assert cfg.dataset_params.num_classes == 1000
    assert cfg.dataset_params.image_size == 224


def test_rewind_epoch_must_fit_level_budget():
    # Out-of-range rewind would silently never save model_rewind, then
    # crash at the level-1 rewind after burning level 0's compute.
    with pytest.raises(ConfigError, match="outside level 0"):
        compose(
            "cifar10_imp",
            overrides=[
                "pruning_params.training_type=wr",
                "pruning_params.rewind_epoch=150",
                "experiment_params.epochs_per_level=150",
            ],
        )
    # Cyclic: the budget is cycle 0's epochs, not the whole level.
    with pytest.raises(ConfigError, match="outside level 0"):
        compose(
            "cifar10_imp",
            overrides=[
                "pruning_params.training_type=wr",
                "pruning_params.rewind_epoch=100",
                "experiment_params.epochs_per_level=160",
                "cyclic_training.num_cycles=4",
                "cyclic_training.strategy=constant",
            ],
        )
    # In range passes.
    cfg = compose(
        "cifar10_imp",
        overrides=[
            "pruning_params.training_type=wr",
            "pruning_params.rewind_epoch=5",
        ],
    )
    assert cfg.pruning_params.rewind_epoch == 5


def test_rewind_optimizer_requires_wr():
    with pytest.raises(ConfigError, match="only meaningful for wr"):
        compose(
            "cifar10_imp", overrides=["pruning_params.rewind_optimizer=true"]
        )
    cfg = compose(
        "cifar10_imp",
        overrides=[
            "pruning_params.training_type=wr",
            "pruning_params.rewind_epoch=5",
            "pruning_params.rewind_optimizer=true",
        ],
    )
    assert cfg.pruning_params.rewind_optimizer is True


def test_group_override_and_dotted_order_independent():
    a = compose(
        "cifar10_imp",
        overrides=[
            "dataset_params.num_workers=4",
            "dataset_params=dp_synthetic_cifar10",
        ],
    )
    b = compose(
        "cifar10_imp",
        overrides=[
            "dataset_params=dp_synthetic_cifar10",
            "dataset_params.num_workers=4",
        ],
    )
    assert a.dataset_params.num_workers == b.dataset_params.num_workers == 4
    assert a.dataset_params.dataloader_type == "synthetic"


def test_required_group_cannot_be_null():
    with pytest.raises(ConfigError, match="required config group"):
        compose("cifar10_imp", overrides=["dataset_params=null"])


def test_group_override_keeps_primary_config_tweaks(tmp_path):
    """A CLI group override substitutes which option file the defaults list
    selects — composition still runs in defaults-list order, so a primary
    yaml whose ``_self_`` comes AFTER the group keeps its direct tweaks
    (Hydra reapplies primary-config values per defaults-list order)."""
    (tmp_path / "dataset_params").mkdir()
    (tmp_path / "dataset_params" / "opt_a.yaml").write_text(
        "dataset_name: CIFAR10\ntotal_batch_size: 128\nnum_workers: 2\n"
    )
    (tmp_path / "dataset_params" / "opt_b.yaml").write_text(
        "dataset_name: CIFAR100\ntotal_batch_size: 256\nnum_workers: 8\n"
    )
    (tmp_path / "main.yaml").write_text(
        "defaults:\n"
        "  - dataset_params: opt_a\n"
        "  - _self_\n"
        "dataset_params:\n"
        "  total_batch_size: 999\n"
    )
    base = compose_dict("main", config_path=tmp_path)
    assert base["dataset_params"]["total_batch_size"] == 999
    over = compose_dict(
        "main", overrides=["dataset_params=opt_b"], config_path=tmp_path
    )
    assert over["dataset_params"]["dataset_name"] == "CIFAR100"
    assert over["dataset_params"]["num_workers"] == 8
    # the primary config's direct tweak survives the group override
    assert over["dataset_params"]["total_batch_size"] == 999

    # With _self_ FIRST (this repo's conf/ style), the group option wins
    # over primary values — including when chosen by a CLI group override.
    (tmp_path / "main_self_first.yaml").write_text(
        "defaults:\n"
        "  - _self_\n"
        "  - dataset_params: opt_a\n"
        "dataset_params:\n"
        "  total_batch_size: 999\n"
    )
    sf = compose_dict(
        "main_self_first", overrides=["dataset_params=opt_b"], config_path=tmp_path
    )
    assert sf["dataset_params"]["total_batch_size"] == 256


def test_group_override_not_in_defaults_rejected(tmp_path):
    """Overriding a group the defaults list doesn't select errors (Hydra
    semantics); '+group=option' appends it explicitly."""
    (tmp_path / "extra_group").mkdir()
    (tmp_path / "extra_group" / "opt.yaml").write_text("k: 1\n")
    (tmp_path / "main.yaml").write_text("defaults:\n  - _self_\nfoo: 2\n")
    with pytest.raises(ConfigError, match="not in main.yaml's defaults"):
        compose_dict("main", overrides=["extra_group=opt"], config_path=tmp_path)
    added = compose_dict(
        "main", overrides=["+extra_group=opt"], config_path=tmp_path
    )
    assert added["extra_group"] == {"k": 1}
    with pytest.raises(ConfigError, match="not a config group"):
        compose_dict("main", overrides=["+nonexistent=opt"], config_path=tmp_path)


def test_fp16_precision_accepted():
    cfg = compose(
        "cifar10_imp", overrides=["experiment_params.training_precision=float16"]
    )
    assert cfg.experiment_params.training_precision == "float16"


# ------------------------------------------------- compose edge cases (PR 3)


def test_duplicate_yaml_key_rejected(tmp_path):
    """pyyaml silently keeps the LAST duplicate key; _load_yaml must refuse
    instead — the clobbered value is config drift with no trace."""
    (tmp_path / "dup.yaml").write_text(
        "defaults:\n  - _self_\nseed: 1\nseed: 2\n"
    )
    with pytest.raises(ConfigError, match="duplicate config key 'seed'"):
        compose_dict("dup", config_path=tmp_path)


def test_duplicate_nested_yaml_key_rejected(tmp_path):
    (tmp_path / "dup.yaml").write_text(
        "experiment_params:\n  seed: 1\n  seed: 2\n"
    )
    with pytest.raises(ConfigError, match="duplicate config key 'seed'"):
        compose_dict("dup", config_path=tmp_path)


def test_dotted_override_unknown_group_rejected():
    """A dotted override can invent a whole new top-level group; the schema
    must reject it as an unknown MainConfig key, not absorb it."""
    with pytest.raises(ConfigError, match="unknown config keys for MainConfig"):
        compose("cifar10_imp", overrides=["bogus_group.lr=0.1"])


def test_override_with_empty_value():
    """``group.key=`` parses as the empty string: fine for str fields,
    a loud coercion error (not a silent 0) for int fields."""
    cfg = compose("cifar10_imp", overrides=["experiment_params.base_dir="])
    assert cfg.experiment_params.base_dir == ""
    with pytest.raises(ConfigError, match="cannot coerce seed=''"):
        compose("cifar10_imp", overrides=["experiment_params.seed="])


def test_non_mapping_group_file_rejected(tmp_path):
    """A group option file containing a list (or scalar) must fail at load
    with the offending path, not produce a half-merged config."""
    import shutil

    from turboprune_tpu.config import DEFAULT_CONFIG_PATH

    conf = tmp_path / "conf"
    shutil.copytree(DEFAULT_CONFIG_PATH, conf)
    (conf / "model_params" / "broken.yaml").write_text("- a\n- b\n")
    with pytest.raises(ConfigError, match="must contain a mapping"):
        compose(
            "cifar10_er_erk",
            overrides=["model_params=broken"],
            config_path=conf,
        )


def test_override_key_schema_rejects():
    """Overriding a key that exists in no dataclass of the targeted group
    dies with the group name in the message."""
    with pytest.raises(
        ConfigError, match="unknown config keys for ExperimentConfig"
    ):
        compose("cifar10_imp", overrides=["experiment_params.bogus=1"])


# ---------------------------------------------------- N:M sparsity (PR 6)


def test_nm_sparsity_valid_patterns():
    for pat in ("2:4", "4:8"):
        cfg = compose(
            "cifar10_imp",
            overrides=[f"experiment_params.nm_sparsity='{pat}'"],
        )
        assert cfg.experiment_params.nm_sparsity == pat
        assert cfg.experiment_params.nm_transposable is True


def test_nm_sparsity_unquoted_is_yaml_base60_int():
    """YAML 1.1 parses an unquoted 2:4 as the sexagesimal integer 124;
    the error must say to quote the value, not report a baffling int."""
    with pytest.raises(ConfigError, match="base-60"):
        compose(
            "cifar10_imp", overrides=["experiment_params.nm_sparsity=2:4"]
        )


@pytest.mark.parametrize(
    "bad,msg",
    [
        ("'0:4'", "0 < N < M"),  # N=0 zeroes every block
        ("'5:4'", "0 < N < M"),  # N>M impossible
        ("'4:4'", "0 < N < M"),  # N=M is dense, not a pattern
        ("'2:1'", "M must be >= 2"),
        ("'2:4:8'", "not of the form"),
        ("'a:b'", "must be integers"),
    ],
)
def test_nm_sparsity_malformed_rejected(bad, msg):
    with pytest.raises(ConfigError, match=msg):
        compose(
            "cifar10_imp",
            overrides=[f"experiment_params.nm_sparsity={bad}"],
        )


def test_nm_sparsity_unsupported_pattern_rejected():
    # parses fine but is outside NM_SPARSITY_PATTERNS (the literal set
    # graftlint's conf-bad-choice rule cross-checks)
    with pytest.raises(ConfigError):
        compose(
            "cifar10_imp", overrides=["experiment_params.nm_sparsity='1:4'"]
        )


def test_nm_prune_method_requires_pattern():
    with pytest.raises(
        ConfigError, match="requires experiment_params.nm_sparsity"
    ):
        compose(
            "cifar10_imp", overrides=["pruning_params.prune_method=nm"]
        )
    cfg = compose(
        "cifar10_imp",
        overrides=[
            "pruning_params.prune_method=nm",
            "experiment_params.nm_sparsity='2:4'",
        ],
    )
    assert cfg.pruning_params.prune_method == "nm"


def test_nm_sparsity_composes_with_compact_train():
    cfg = compose(
        "cifar10_imp",
        overrides=[
            "experiment_params.nm_sparsity='4:8'",
            "experiment_params.nm_transposable=false",
            "experiment_params.compact_train=true",
        ],
    )
    assert cfg.experiment_params.nm_sparsity == "4:8"
    assert cfg.experiment_params.nm_transposable is False
    assert cfg.experiment_params.compact_train is True


@pytest.mark.parametrize("doc", ["README.md", "PARITY.md"])
def test_a_document_names_only_files_that_exist(doc):
    """Every source file, document or directory the README and the parity map
    name in backticks, and every ``python <file>`` of a command block, is in
    the tree: a README that tells its reader to run a deleted program, or a
    parity row whose evidence is gone, fails here."""
    import re
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    text = (root / doc).read_text(encoding="utf-8")
    source = r"[\w./-]*\w\.(?:py|md|json|jsonl|sh|cpp|ini|toml)"
    named = set(re.findall(rf"python3? ({source})", text))
    for token in re.findall(r"`([^`\n]+)`", text):
        # `path`, `path:line`, `path::test`, `python path`, `conf/x.yaml`, `dir/`
        m = re.fullmatch(
            rf"(?:python3? )?({source}|conf/[\w./-]+|(?:\w+/)+)(?:::?[\w:.-]+)?", token.strip()
        )
        if m:
            named.add(m.group(1))
    assert len(named) > 40  # the pattern still finds what the document names

    def exists(name: str) -> bool:
        if any((base / name).exists() for base in (root, root / "turboprune_tpu")):
            return True
        return "/" not in name and any((root / "turboprune_tpu").rglob(name))

    # experiments/<dir> is made at run time; path/to/ is the linter's example;
    # tp/ is the prefix of the program's spans.
    made_up = ("experiments/", "path/to/", "tp/")
    missing = sorted(n for n in named if not exists(n) and not n.startswith(made_up))
    assert not missing, f"{doc} names files that are not in the tree: {missing}"
