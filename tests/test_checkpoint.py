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


def _tiny_state(scale=1.0):
    """A state of three small leaves: what ``save_level`` takes of one."""
    import types

    return types.SimpleNamespace(
        params={"w": scale * jnp.arange(12.0).reshape(3, 4)},
        masks={"w": jnp.arange(12).reshape(3, 4) % 3 > 0},
        batch_stats={"mean": jnp.full(4, scale)},
    )


class _HeldWriter:
    """``checkpoint._write_tree`` held at a gate: the writer's thread stands
    in it until ``release()``; ``entered`` says the write has been handed over
    (``start_write()``) and begun."""

    def __init__(self, monkeypatch):
        import threading

        from turboprune_tpu.utils import checkpoint

        self.gate, self.entered, real = threading.Event(), threading.Event(), checkpoint._write_tree

        def held(path, tree, **attrs):
            self.entered.set()
            assert self.gate.wait(60), "the test never opened the gate"
            real(path, tree, **attrs)

        monkeypatch.setattr(checkpoint, "_write_tree", held)

    def release(self):
        self.gate.set()


def _blocked_until_released(held, fn):
    """``fn``, on a thread of the test's own, stands still while the writer is
    held and ends once it is let go; returns what ``fn`` returned."""
    from concurrent.futures import ThreadPoolExecutor, TimeoutError

    with ThreadPoolExecutor(1) as pool:
        call = pool.submit(fn)
        try:
            with pytest.raises(TimeoutError):  # it waits for the write in flight
                call.result(0.3)
        finally:
            held.release()
        return call.result(60)


class TestWriteBehind:
    """A level save returns after the fetch; one writer holds the one write in
    flight; every reader, the next save and ``wait()`` stand until it is
    committed (ISSUE 30)."""

    READERS = {
        "has_level": lambda ck: ck.has_level(0),
        "saved_levels": lambda ck: ck.saved_levels() == [0],
        "has_model": lambda ck: not ck.has_model("model_init") and ck.level_path(0).exists(),
        "load_level": lambda ck: bool(
            np.array_equal(ck.load_level(0, _tiny_state())["params"]["w"], _tiny_state().params["w"])
        ),
    }

    @pytest.mark.parametrize("reader", sorted(READERS))
    def test_save_level_returns_with_nothing_on_disk_and_a_reader_waits_for_it(
        self, tmp_path, monkeypatch, reader
    ):
        held = _HeldWriter(monkeypatch)
        ck = ExperimentCheckpoints(tmp_path)
        ck.save_level(0, _tiny_state())  # returned, with the tree fetched and kept
        assert ck._behind._pool is None and not held.entered.is_set()
        ck.start_write()  # the epoch loop's, once the next epoch is dispatched
        assert held.entered.wait(60)
        assert not ck.level_path(0).exists()
        assert [p.name for p in ck.checkpoints_dir.iterdir()] == []  # not even a temporary name
        assert _blocked_until_released(held, lambda: self.READERS[reader](ck)) is True
        assert ck.level_path(0).exists()

    SAVES = {
        "save_level": lambda ck, st: ck.save_level(1, st),
        "save_model": lambda ck, st: ck.save_model("model_rewind", st),
        "save_optimizer": lambda ck, st: ck.save_optimizer("optimizer_rewind", {"mu": st.params}),
        "save_mid_level": lambda ck, st: ck.save_mid_level(
            1, 0, type(st)(**vars(st), opt_state={"mu": st.params}, step=jnp.int32(3)), {}
        ),
    }

    @pytest.mark.parametrize("save", sorted(SAVES))
    def test_the_next_save_waits_for_the_write_in_flight(self, tmp_path, monkeypatch, save):
        """At most one write and one host tree alive, and the order on disk
        kept: no save of this class starts before the one before it is
        committed."""
        held = _HeldWriter(monkeypatch)
        ck = ExperimentCheckpoints(tmp_path)
        ck.save_level(0, _tiny_state())
        ck.start_write()
        assert held.entered.wait(60)
        _blocked_until_released(held, lambda: self.SAVES[save](ck, _tiny_state(2.0)))
        ck.wait()
        assert ck.saved_levels() == ([0, 1] if save == "save_level" else [0])
        assert len(list(ck.checkpoints_dir.iterdir()) + list(ck.artifacts_dir.iterdir())) >= 2

    @pytest.mark.parametrize("at", ["wait", "save_level", "has_level", "load_level"])
    def test_what_the_writer_raised_is_raised_by_the_next_wait(self, tmp_path, monkeypatch, at):
        from turboprune_tpu.utils import checkpoint

        def full(path, tree, **attrs):
            raise OSError(28, "No space left on device", str(path))

        monkeypatch.setattr(checkpoint, "_write_tree", full)
        ck = ExperimentCheckpoints(tmp_path)
        ck.save_level(0, _tiny_state())  # the hand-over itself says nothing
        calls = {
            "wait": ck.wait,
            "save_level": lambda: ck.save_level(1, _tiny_state()),
            "has_level": lambda: ck.has_level(0),
            "load_level": lambda: ck.load_level(0, _tiny_state()),
        }
        with pytest.raises(OSError, match="No space left"):
            calls[at]()
        ck.wait()  # said once: nothing is in flight any more
        assert not ck.level_path(0).exists()

    @pytest.mark.parametrize("handed_over", [True, False])
    def test_wait_writes_what_nobody_handed_over(self, tmp_path, handed_over):
        """A caller that is no epoch loop never says ``start_write()``: the
        first reader, the next save or ``wait()`` starts the write it then
        waits for. Said twice, it is one write."""
        from turboprune_tpu.utils import tracing

        ck = ExperimentCheckpoints(tmp_path)
        with tracing.span("t/handed") as whole:
            ck.save_level(0, _tiny_state())
            if handed_over:
                ck.start_write()
                ck.start_write()
            assert ck.saved_levels() == [0]
            ck.start_write()  # nothing is held any more
            ck.wait()
        assert len(tracing.recorded("ckpt/write", whole.start, whole.end)) == 1
        assert len(tracing.recorded("ckpt/wait", whole.start, whole.end)) == 1

    def test_a_directory_written_behind_is_the_one_written_in_line(self, tmp_path):
        """Orbax's b-tree is not reproducible from one in-line write of a tree
        to the next: it names its data files at random, cuts them in a number
        that varies, and stamps two times. Everything else is equal byte for
        byte: the names outside the data directories, the tree's manifest
        (``_METADATA``), the stamped metadata but for its times, and every
        leaf read back."""
        import json

        from turboprune_tpu.utils.checkpoint import save_model_tree

        state = _tiny_state()
        ck = ExperimentCheckpoints(tmp_path)
        ck.save_level(0, state)
        ck.wait()
        behind, inline = ck.level_path(0), tmp_path / "inline"
        save_model_tree(inline, ck.model_state(state))

        def names(root):  # files and directories, but for the data files' own names
            return sorted(
                str(p.relative_to(root)) for p in root.rglob("*") if p.parent.name != "d"
            )

        def stamped(root):
            meta = json.loads((root / "_CHECKPOINT_METADATA").read_text())
            return {k: v for k, v in meta.items() if not k.endswith("_timestamp_nsecs")}

        assert names(behind) == names(inline)
        assert {"_METADATA", "manifest.ocdbt", "d", "ocdbt.process_0/d"} <= set(names(behind))
        assert (behind / "_METADATA").read_bytes() == (inline / "_METADATA").read_bytes()
        assert stamped(behind) == stamped(inline)
        got, want = restore_pytree(behind), restore_pytree(inline)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            x, y = np.asarray(x), np.asarray(y)
            assert (x.dtype, x.shape, x.tobytes()) == (y.dtype, y.shape, y.tobytes())

    def test_the_write_is_recorded_on_the_writers_thread_with_the_askers_level(self, tmp_path):
        import threading

        from turboprune_tpu.utils import tracing

        ck = ExperimentCheckpoints(tmp_path)
        with tracing.span("t/behind") as whole:
            with tracing.span("level", level=7, density=0.5):
                with tracing.span("level/save"):
                    ck.save_level(7, _tiny_state())
            with tracing.span("level", level=8):
                with tracing.span("level/save") as second:
                    ck.save_level(8, _tiny_state())
            ck.wait()
        me = threading.get_ident()
        writes = tracing.recorded("ckpt/write", whole.start, whole.end)
        assert [s.attrs for s in writes] == [{"level": 7}, {"level": 8}]
        assert all(s.thread != me and s.parent is None for s in writes)
        assert len({s.thread for s in writes}) == 1  # the one writer
        waits = tracing.recorded("ckpt/wait", whole.start, whole.end)
        # One where level 8's save found level 7's write, one at the end: each
        # on the caller's thread, under the span that stood waiting.
        assert [(s.thread, s.parent, s.attrs) for s in waits] == [
            (me, second.id, {"level": 8}),
            (me, whole.id, {}),
        ]
        fetches = tracing.recorded("ckpt/fetch", whole.start, whole.end)
        assert {s.thread for s in fetches} == {me} and {s.attrs["level"] for s in fetches} == {7, 8}
        # The write that ended during level 8 is level 7's, and the level's
        # breakdown names it beside the level's own time.
        level8 = tracing.recorded("level", whole.start, whole.end)[1]
        assert tracing.breakdown([level8])["behind"] == [(7, writes[0].seconds)]

    def test_a_process_that_never_saved_a_level_has_no_writer_and_waits_on_nothing(
        self, small_state, tmp_path
    ):
        from turboprune_tpu.utils import tracing

        _, _, state = small_state
        writer = ExperimentCheckpoints(tmp_path)
        writer.save_level(0, _tiny_state())
        writer.wait()
        assert writer._behind._pool is not None
        with tracing.span("t/reader") as whole:
            reader = ExperimentCheckpoints(tmp_path)  # the server, a resumed process
            assert reader.saved_levels() == [0] and reader.has_level(0)
            reader.load_level(0, _tiny_state())
            reader.save_model("model_init", state)  # set-up's saves stay in line
            reader.wait()
        assert reader._behind._pool is None and reader._unsettled is None
        assert not tracing.recorded("ckpt/wait", whole.start, whole.end)
        (write,) = tracing.recorded("ckpt/write", whole.start, whole.end)
        assert write.thread == whole.thread

    def test_twenty_saves_back_to_back_leave_twenty_levels(self, tmp_path):
        ck = ExperimentCheckpoints(tmp_path)
        for level in range(20):
            ck.save_level(level, _tiny_state(float(level)))
        assert ck.saved_levels() == list(range(20))
        for level in (0, 19):
            back = ck.load_level(level, _tiny_state())
            np.testing.assert_array_equal(back["batch_stats"]["mean"], np.full(4, float(level)))


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
