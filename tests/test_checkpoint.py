"""Checkpoint/rewind + experiment-utils tests (SURVEY.md §4: rewind and
checkpoint round-trips are a prescribed test area; the reference had none)."""

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from turboprune_tpu.config.compose import compose
from turboprune_tpu.models import create_model
from turboprune_tpu.ops import masking
from turboprune_tpu.train import create_optimizer, create_train_state
from turboprune_tpu.utils import (
    ExperimentCheckpoints,
    MetricsLogger,
    expt_prefix,
    gen_expt_dir,
    reset_weights,
    resume_experiment,
    restore_pytree,
    save_config,
    save_pytree,
)


@pytest.fixture(scope="module")
def small_state():
    model = create_model("resnet18", 10, "CIFAR10")
    tx = create_optimizer("SGD", 0.1, momentum=0.9, weight_decay=5e-4)
    state = create_train_state(model, tx, jax.random.PRNGKey(0), (1, 32, 32, 3))
    return model, tx, state


def _first_param(tree):
    return jax.tree.leaves(tree)[0]


class TestMidLevelSlot:
    def test_roundtrip_and_torn_save_guard(self, small_state, tmp_path):
        """The slot embeds a (level, epoch) tag inside the atomically-written
        Orbax tree; load_mid_level returns None when the caller's
        header-derived expectation disagrees (a preemption between the state
        write and the header write), so a mixed restore can never happen."""
        _, _, state = small_state
        ckpts = ExperimentCheckpoints(tmp_path)
        ckpts.save_mid_level(
            2, 3, state, meta={"max_test_acc": 42.0, "train_loader_epoch": 13}
        )
        meta = ckpts.peek_mid_level()
        assert (meta["level"], meta["epoch"]) == (2, 3)
        assert meta["train_loader_epoch"] == 13

        got = ckpts.load_mid_level(state, expect_level=2, expect_epoch=3)
        assert got is not None
        np.testing.assert_array_equal(
            _first_param(got["params"]), _first_param(state.params)
        )
        # Stale header (older save) -> refuse, don't mix.
        assert ckpts.load_mid_level(state, expect_level=2, expect_epoch=1) is None
        assert ckpts.load_mid_level(state, expect_level=1, expect_epoch=3) is None

        ckpts.clear_mid_level()
        assert ckpts.peek_mid_level() is None
        assert not ckpts.mid_level_path().exists()

    def test_stream_blob_tag_roundtrip_and_mismatch(self, tmp_path):
        """Per-host stream blobs are tagged with (level, epoch): a blob from
        a different save (torn write between state and stream) or a missing
        file returns None, and clear_mid_level removes every host's file."""
        ckpts = ExperimentCheckpoints(tmp_path)
        ckpts.save_mid_level_stream(3, 1, b"grain-state-host0", pid=0)
        ckpts.save_mid_level_stream(3, 1, b"grain-state-host1", pid=1)
        assert ckpts.load_mid_level_stream(3, 1, pid=0) == b"grain-state-host0"
        assert ckpts.load_mid_level_stream(3, 1, pid=1) == b"grain-state-host1"
        assert ckpts.load_mid_level_stream(3, 3, pid=0) is None  # other save
        assert ckpts.load_mid_level_stream(2, 1, pid=0) is None
        assert ckpts.load_mid_level_stream(3, 1, pid=7) is None  # no file
        ckpts.clear_mid_level()
        assert ckpts.load_mid_level_stream(3, 1, pid=0) is None
        assert ckpts.load_mid_level_stream(3, 1, pid=1) is None

    def test_peek_tolerates_corrupt_header(self, small_state, tmp_path):
        _, _, state = small_state
        ckpts = ExperimentCheckpoints(tmp_path)
        ckpts.save_mid_level(0, 1, state, meta={})
        ckpts._mid_level_meta_path().write_text("{truncated")
        assert ckpts.peek_mid_level() is None  # no JSONDecodeError escape


class TestPackedMasks:
    """ISSUE-5 satellite: mask payloads are bit-packed (uint8 bitfields +
    shape metadata) in model checkpoints — 8x smaller — and legacy
    checkpoints with raw bool masks still load."""

    def test_pack_roundtrip_and_size(self, small_state):
        from turboprune_tpu.utils import pack_mask_tree, unpack_mask_tree

        _, _, state = small_state
        masks = masking.mask_where(
            state.masks, lambda m: jnp.asarray(np.random.default_rng(0).random(m.shape) < 0.5)
        )
        packed = pack_mask_tree(masks)
        bits = sum(
            int(leaf["bits"].size)
            for leaf in jax.tree.leaves(
                packed, is_leaf=lambda x: isinstance(x, dict) and "bits" in x
            )
            if isinstance(leaf, dict)
        )
        total = sum(int(m.size) for m in masking.mask_leaves(masks))
        assert bits <= total // 8 + len(masking.mask_leaves(masks))  # ~8x
        back = unpack_mask_tree(packed)
        for a, b in zip(
            masking.mask_leaves(masks), masking.mask_leaves(back)
        ):
            assert np.asarray(b).dtype == np.bool_
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_model_checkpoint_roundtrip_is_packed(self, small_state, tmp_path):
        from turboprune_tpu.utils.checkpoint import _has_packed_masks

        _, _, state = small_state
        pruned = state.replace(
            masks=masking.mask_where(
                state.masks,
                lambda m: jnp.asarray(
                    np.random.default_rng(1).random(m.shape) < 0.3
                ),
            )
        )
        ck = ExperimentCheckpoints(tmp_path)
        ck.save_model("model_init", pruned)
        assert _has_packed_masks(ck.model_path("model_init").resolve())
        back = ck.load_model("model_init", pruned)
        for a, b in zip(
            masking.mask_leaves(pruned.masks), masking.mask_leaves(back["masks"])
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(_first_param(back["params"])),
            np.asarray(_first_param(pruned.params)),
        )

    def test_legacy_unpacked_checkpoint_still_loads(self, small_state, tmp_path):
        """A checkpoint written BEFORE the packing change (raw bool mask
        leaves) must restore through the same load path."""
        _, _, state = small_state
        ck = ExperimentCheckpoints(tmp_path)
        # Legacy writer: raw model_state tree, no packing.
        save_pytree(ck.model_path("model_init"), ck.model_state(state))
        back = ck.load_model("model_init", state)
        assert set(back) == {"params", "masks", "batch_stats"}
        for a, b in zip(
            masking.mask_leaves(state.masks), masking.mask_leaves(back["masks"])
        ):
            assert np.asarray(b).dtype == np.bool_
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_mid_level_slot_packs_masks_too(self, small_state, tmp_path):
        from turboprune_tpu.utils.checkpoint import _has_packed_masks

        _, _, state = small_state
        ck = ExperimentCheckpoints(tmp_path)
        ck.save_mid_level(1, 2, state, meta={})
        assert _has_packed_masks(ck.mid_level_path().resolve())
        got = ck.load_mid_level(state, expect_level=1, expect_epoch=2)
        assert got is not None
        for a, b in zip(
            masking.mask_leaves(state.masks), masking.mask_leaves(got["masks"])
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestPytreeRoundTrip:
    def test_masks_none_leaves_and_bool_dtype_survive(self, small_state, tmp_path):
        _, _, state = small_state
        save_pytree(tmp_path / "m", state.masks)
        back = restore_pytree(tmp_path / "m", state.masks)
        lv_in = jax.tree.leaves(state.masks, is_leaf=lambda x: x is None)
        lv_out = jax.tree.leaves(back, is_leaf=lambda x: x is None)
        assert len(lv_in) == len(lv_out)
        for a, b in zip(lv_in, lv_out):
            if a is None:
                assert b is None
            else:
                assert b.dtype == jnp.bool_
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_opt_state_container_types_restored(self, small_state, tmp_path):
        _, _, state = small_state
        save_pytree(tmp_path / "o", state.opt_state)
        back = restore_pytree(tmp_path / "o", state.opt_state)
        assert jax.tree.structure(back) == jax.tree.structure(state.opt_state)

    def test_overwrite_existing(self, small_state, tmp_path):
        _, _, state = small_state
        save_pytree(tmp_path / "p", {"x": jnp.ones(3)})
        save_pytree(tmp_path / "p", {"x": jnp.zeros(3)})
        back = restore_pytree(tmp_path / "p")
        assert float(back["x"].sum()) == 0.0


class TestRewindSemantics:
    def test_imp_restores_init_but_keeps_pruned_masks(self, small_state, tmp_path):
        _, _, state = small_state
        ck = ExperimentCheckpoints(tmp_path)
        ck.save_model("model_init", state)
        pruned_masks = masking.mask_where(
            state.masks, lambda m: jnp.zeros_like(m)
        )
        trained = state.replace(
            params=jax.tree.map(lambda x: x + 1.0, state.params),
            masks=pruned_masks,
        )
        back = reset_weights("imp", trained, ck)
        np.testing.assert_allclose(
            np.asarray(_first_param(back.params)),
            np.asarray(_first_param(state.params)),
        )
        assert masking.overall_sparsity(back.masks) == 100.0  # masks NOT rewound

    def test_wr_restores_rewind_checkpoint(self, small_state, tmp_path):
        _, _, state = small_state
        ck = ExperimentCheckpoints(tmp_path)
        rewind = state.replace(
            params=jax.tree.map(lambda x: x * 3.0, state.params)
        )
        ck.save_model("model_rewind", rewind)
        back = reset_weights("wr", state, ck)
        np.testing.assert_allclose(
            np.asarray(_first_param(back.params)),
            np.asarray(_first_param(rewind.params)),
        )

    @pytest.mark.parametrize("ttype", ["lrr", "at_init"])
    def test_lrr_and_at_init_are_noops(self, small_state, tmp_path, ttype):
        _, _, state = small_state
        ck = ExperimentCheckpoints(tmp_path)
        trained = state.replace(
            params=jax.tree.map(lambda x: x + 5.0, state.params)
        )
        back = reset_weights(ttype, trained, ck)
        np.testing.assert_allclose(
            np.asarray(_first_param(back.params)),
            np.asarray(_first_param(trained.params)),
        )

    def test_level_roundtrip_and_listing(self, small_state, tmp_path):
        _, _, state = small_state
        ck = ExperimentCheckpoints(tmp_path)
        ck.save_level(0, state)
        ck.save_level(2, state)
        assert ck.saved_levels() == [0, 2]
        assert ck.has_level(2) and not ck.has_level(1)
        back = ck.load_level(0, state)
        assert set(back) == {"params", "masks", "batch_stats"}


def _reads_during(fn):
    """(what ``fn`` returned, how many Orbax restores ran inside it)."""
    from turboprune_tpu.utils import tracing

    with tracing.span("t/reads") as whole:
        out = fn()
    return out, len(tracing.recorded("ckpt/read", whole.start, whole.end))


def _assert_trees_equal(got, want):
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)), got, want
    )


def _source_of(fn):
    """(what ``fn`` returned, the ``source`` the rewind noted on the open span)."""
    from turboprune_tpu.utils import tracing

    with tracing.span("t/rewind") as sp:
        out = fn()
    return out, sp.attrs.get("source")


class TestResidentRewindTargets:
    """The process that saves the target its run rewinds to keeps it and
    rewinds from memory; a process that did not (a resume) reads it from disk
    once. Either way what it keeps is a host tree."""

    @pytest.mark.parametrize("ttype, role", [("imp", "model_init"), ("wr", "model_rewind")])
    def test_the_writer_rewinds_without_a_read_and_a_resumed_process_reads_once(
        self, small_state, tmp_path, ttype, role
    ):
        from turboprune_tpu.utils import rewind_roles

        _, _, state = small_state
        assert rewind_roles(ttype) == {role}
        writer = ExperimentCheckpoints(tmp_path, keep=rewind_roles(ttype))
        writer.save_model(role, state)
        trained = state.replace(params=jax.tree.map(lambda x: x + 1.0, state.params))
        for _ in range(2):
            (back, source), reads = _reads_during(
                lambda: _source_of(lambda: reset_weights(ttype, trained, writer))
            )
            assert (reads, source) == (0, "resident")
            _assert_trees_equal(back.params, state.params)
            _assert_trees_equal(back.batch_stats, state.batch_stats)

        # Same directory, another process.
        resumed = ExperimentCheckpoints(tmp_path, keep=rewind_roles(ttype))
        for want in ((1, "disk"), (0, "resident")):
            (back, source), reads = _reads_during(
                lambda: _source_of(lambda: reset_weights(ttype, trained, resumed))
            )
            assert (reads, source) == want
            _assert_trees_equal(back.params, state.params)

    @pytest.mark.parametrize(
        "ttype, rewind_optimizer, kept",
        [
            ("imp", False, {"model_init"}),
            ("wr", False, {"model_rewind"}),
            ("wr", True, {"model_rewind", "optimizer_rewind"}),
            ("lrr", False, set()),
            ("at_init", False, set()),
        ],
    )
    def test_a_process_keeps_only_what_its_run_rewinds_to(
        self, small_state, tmp_path, ttype, rewind_optimizer, kept
    ):
        """``lrr`` never rewinds, ``wr`` never to ``model_init``: a save of a
        role the run does not rewind to fetches and holds nothing extra."""
        from turboprune_tpu.utils import rewind_roles

        _, _, state = small_state
        assert rewind_roles(ttype, rewind_optimizer) == kept
        ck = ExperimentCheckpoints(tmp_path, keep=rewind_roles(ttype, rewind_optimizer))
        for role in ("model_init", "model_rewind"):
            ck.save_model(role, state)
        for role in ("optimizer_init", "optimizer_rewind"):
            ck.save_optimizer(role, state.opt_state)
        assert set(ck._resident) == kept
        assert ExperimentCheckpoints(tmp_path)._keep == frozenset()  # the server's

    def test_a_later_save_replaces_the_resident_target(self, small_state, tmp_path):
        _, _, state = small_state
        ck = ExperimentCheckpoints(tmp_path, keep={"model_init"})
        ck.save_model("model_init", state)
        second = state.replace(params=jax.tree.map(lambda x: x * 2.0, state.params))
        ck.save_model("model_init", second)
        _assert_trees_equal(reset_weights("imp", state, ck).params, second.params)
        _assert_trees_equal(ck.load_model("model_init", state)["params"], second.params)

    @pytest.mark.parametrize("from_disk", [False, True], ids=["writer", "resumed"])
    def test_the_resident_target_is_a_host_tree_of_what_the_save_wrote(
        self, small_state, tmp_path, from_disk
    ):
        """Params, packed masks and batch statistics on disk are the trees
        the state held: keeping the fetched tree changed nothing written.
        What a process keeps is numpy whether it wrote the target or read it:
        Orbax restores onto the devices of the state it is shown, and a
        device tree kept here would be aliased by ``replicate`` and deleted
        by the donating step."""
        _, _, state = small_state
        ck = ExperimentCheckpoints(tmp_path, keep={"model_init"})
        ck.save_model("model_init", state)
        on_disk = ck.load_model("model_init", state)
        _assert_trees_equal(on_disk, ck.model_state(state))
        if from_disk:
            assert any(isinstance(x, jax.Array) for x in jax.tree.leaves(on_disk["params"]))
            ck = ExperimentCheckpoints(tmp_path)
        held = ck.rewind_model("model_init", state)
        assert set(held) == {"params", "batch_stats"}  # the masks are never rewound
        _assert_trees_equal(held, {k: on_disk[k] for k in held})
        assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(held))  # host, not HBM

    def test_optimizer_rewind_is_resident_in_the_writer_and_read_once_elsewhere(
        self, small_state, tmp_path
    ):
        _, _, state = small_state
        warm = jax.tree.map(lambda x: x + 1, state.opt_state)
        writer = ExperimentCheckpoints(tmp_path, keep={"optimizer_rewind"})
        writer.save_optimizer("optimizer_init", state.opt_state)
        writer.save_optimizer("optimizer_rewind", warm)
        got, reads = _reads_during(lambda: writer.rewind_optimizer(state.opt_state))
        assert reads == 0 and type(got) is type(state.opt_state)
        _assert_trees_equal(got, warm)
        resumed = ExperimentCheckpoints(tmp_path, keep={"optimizer_rewind"})
        for want_reads in (1, 0):
            got, reads = _reads_during(lambda: resumed.rewind_optimizer(state.opt_state))
            assert reads == want_reads and type(got) is type(state.opt_state)
            _assert_trees_equal(got, warm)
            assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(got))


class TestExperimentUtils:
    def _cfg(self, tmp_path):
        return compose(
            "cifar10_imp",
            overrides=[
                f"experiment_params.base_dir={tmp_path}",
                "dataset_params.dataloader_type=synthetic",
            ],
        )

    def test_gen_expt_dir_layout_and_prefix(self, tmp_path):
        cfg = self._cfg(tmp_path)
        prefix, expt_dir = gen_expt_dir(cfg)
        assert prefix == expt_prefix(cfg)
        for sub in ("checkpoints", "metrics/level_wise_metrics", "artifacts"):
            assert (tmp_path / expt_dir.split("/")[-1] / sub.split("/")[0]).exists()
        assert "cifar10" in prefix and "mag" in prefix and "imp" in prefix

    def test_save_config_snapshot_is_reloadable(self, tmp_path):
        import yaml

        cfg = self._cfg(tmp_path)
        _, expt_dir = gen_expt_dir(cfg)
        p = save_config(expt_dir, cfg)
        with open(p) as f:
            snap = yaml.safe_load(f)
        assert snap["pruning_params"]["prune_method"] == "mag"
        assert snap["dataset_params"]["dataloader_type"] == "synthetic"

    def test_resume_finds_existing_dir(self, tmp_path):
        cfg = self._cfg(tmp_path)
        _, expt_dir = gen_expt_dir(cfg)
        name = expt_dir.split("/")[-1]
        cfg2 = compose(
            "cifar10_imp",
            overrides=[
                f"experiment_params.base_dir={tmp_path}",
                "experiment_params.resume_experiment=true",
                f"experiment_params.resume_experiment_stuff.resume_expt_name={name}",
                "experiment_params.resume_experiment_stuff.resume_level=2",
            ],
        )
        prefix, got_dir, level = resume_experiment(cfg2)
        assert got_dir == expt_dir
        assert level == 2
        assert prefix == expt_prefix(cfg)

    def test_resume_missing_dir_raises(self, tmp_path):
        cfg2 = compose(
            "cifar10_imp",
            overrides=[
                f"experiment_params.base_dir={tmp_path}",
                "experiment_params.resume_experiment=true",
                "experiment_params.resume_experiment_stuff.resume_expt_name=nope",
            ],
        )
        with pytest.raises(FileNotFoundError):
            resume_experiment(cfg2)

    def test_metrics_logger_level_csv_and_summary_append(self, tmp_path):
        logger = MetricsLogger(str(tmp_path), "pfx")
        (tmp_path / "metrics").mkdir()
        for lvl in range(2):
            for ep in range(3):
                logger.log_epoch(
                    {"epoch": ep, "train_loss": 1.0 - ep * 0.1, "test_acc": 50 + ep}
                )
            s = logger.finish_level(lvl, {"sparsity": 20.0 * lvl})
            assert s["max_test_acc"] == 52
        lv = pd.read_csv(tmp_path / "metrics/level_wise_metrics/level_1_metrics.csv")
        assert len(lv) == 3
        summary = pd.read_csv(tmp_path / "metrics/pfx_summary.csv")
        assert list(summary["level"]) == [0, 1]
        assert list(summary["sparsity"]) == [0.0, 20.0]


class TestMidLevelSlotIdentity:
    """ADVICE r5: the mid-level slot is stamped with a config hash + run id;
    a restore under a changed config is refused (level replays instead) and
    the driver clears the slot at run completion."""

    def _cfg(self, base, *extra):
        return compose(
            "cifar10_imp",
            overrides=[
                f"experiment_params.base_dir={base}",
                "dataset_params.dataloader_type=synthetic",
                "dataset_params.total_batch_size=16",
                "dataset_params.synthetic_num_train=64",
                "dataset_params.synthetic_num_test=32",
                "experiment_params.epochs_per_level=2",
                "experiment_params.max_steps_per_epoch=1",
                "experiment_params.checkpoint_every_epochs=1",
                "pruning_params.target_sparsity=0.2",
                *extra,
            ],
        )

    def test_config_fingerprint_semantics(self, tmp_path):
        from turboprune_tpu.utils import config_fingerprint

        base = config_fingerprint(self._cfg(tmp_path))
        # The resume knobs MUST NOT change the hash (a resumed run flips
        # them and still has to match its own slot)...
        assert (
            config_fingerprint(
                self._cfg(tmp_path, "experiment_params.resume_experiment=true")
            )
            == base
        )
        # ...while any training-relevant knob must.
        assert (
            config_fingerprint(self._cfg(tmp_path, "optimizer_params.lr=0.1"))
            != base
        )
        assert (
            config_fingerprint(
                self._cfg(tmp_path, "experiment_params.epochs_per_level=3")
            )
            != base
        )

    def test_restore_refused_on_config_change_honored_on_match(self, tmp_path):
        import pandas as pd

        from turboprune_tpu.harness import PruningHarness
        from turboprune_tpu.utils import gen_expt_dir

        cfg = self._cfg(tmp_path)
        prefix, expt_dir = gen_expt_dir(cfg)
        save_config(expt_dir, cfg)
        harness = PruningHarness(cfg, (prefix, expt_dir))
        meta = {
            "max_test_acc": 0.0,
            "train_loader_epoch": 0,
            "level_rows": [],
            "run_id": harness.run_id,
        }

        # Slot stamped with a DIFFERENT config hash: refused -> the level
        # replays from epoch 0, so the level CSV has all epochs_per_level
        # rows (an honored restore would skip epoch 0).
        harness.ckpts.save_mid_level(
            0, 0, harness.state, meta={**meta, "config_hash": "bogus"}
        )
        harness.train_one_level(2, 0)
        csv = (
            f"{expt_dir}/metrics/level_wise_metrics/level_0_metrics.csv"
        )
        assert list(pd.read_csv(csv)["epoch"]) == [0, 1]

        # Slot stamped with the MATCHING hash: honored -> re-enters at
        # epoch 1, only one fresh row.
        harness.ckpts.save_mid_level(
            0, 0, harness.state,
            meta={**meta, "config_hash": harness.config_hash},
        )
        harness.train_one_level(2, 0)
        assert list(pd.read_csv(csv)["epoch"]) == [1]

    def test_driver_clears_slot_at_run_completion(self, tmp_path):
        from turboprune_tpu.driver import run

        cfg = self._cfg(tmp_path)
        expt_dir, summaries = run(cfg)
        assert len(summaries) == 2
        ckpts = ExperimentCheckpoints(expt_dir)
        assert ckpts.peek_mid_level() is None
        assert not ckpts.mid_level_path().exists()


def test_check_state_equality_exact_single_process_noop():
    """exact=True adds a full-fingerprint allgather on multi-host runs; on
    one process it must remain a no-op (no device chatter in unit tests)."""
    from turboprune_tpu.parallel import check_state_equality

    check_state_equality({"a": np.ones(3, np.float32)}, exact=True)
