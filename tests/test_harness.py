"""End-to-end harness/driver integration tests on the virtual 8-device CPU
mesh (SURVEY.md §4: "integration tests driving 1-2 levels of a tiny model on
synthetic data"). These exercise the FULL experiment loop: density ladder,
prune between levels, rewind, level checkpoints, metrics CSVs, resume."""

import jax
import numpy as np
import pandas as pd
import pytest

from turboprune_tpu.config.compose import compose
from turboprune_tpu.driver import run, run_cyclic


def _cfg(tmp_path, *extra):
    return compose(
        "cifar10_imp",
        overrides=[
            f"experiment_params.base_dir={tmp_path}",
            "dataset_params.dataloader_type=synthetic",
            "dataset_params.total_batch_size=16",
            "dataset_params.synthetic_num_train=64",
            "dataset_params.synthetic_num_test=32",
            "experiment_params.epochs_per_level=2",
            "experiment_params.max_steps_per_epoch=2",
            "pruning_params.target_sparsity=0.36",
            "model_params.model_name=resnet18",
            *extra,
        ],
    )


def _traced_run(cfg, harness_cls=None):
    """``run(cfg)`` with its harness kept and its spans collected; a
    KeyboardInterrupt (the tests' preemption) ends it early."""
    from pathlib import Path
    from unittest import mock

    from turboprune_tpu import driver
    from turboprune_tpu.harness import PruningHarness
    from turboprune_tpu.parallel.multihost import tree_fingerprint
    from turboprune_tpu.utils import restore_pytree, tracing

    held = {}
    rows, reads = [], []  # every epoch's row; (event, level, ``mask_reads`` so far)

    def mark(event, level):
        reads.append((event, level, tracing.gauges().get("mask_reads", 0)))

    class Capturing(harness_cls or PruningHarness):
        def __init__(self, *a, **k):
            held["h"] = self  # before the base: a subclass may die in its own
            super().__init__(*a, **k)

        def train_one_level(self, epochs_per_level, level, **k):
            mark("train>", level)
            try:
                return super().train_one_level(epochs_per_level, level, **k)
            finally:
                mark("train<", level)

        def _train_eval_log(self, row, max_test_acc):
            mark("epoch>", row["level"])
            best = super()._train_eval_log(row, max_test_acc)
            mark("epoch<", row["level"])
            rows.append(dict(row))
            return best

    real_prune = driver.prune_level

    def prune_level(harness, density, level):
        mark("prune>", level)
        real_prune(harness, density, level)
        mark("prune<", level)

    summaries = None
    with tracing.span("t/run") as whole, mock.patch.object(driver, "prune_level", prune_level):
        try:
            _, summaries = run(cfg, harness_cls=Capturing)
        except KeyboardInterrupt:
            pass
    mark("run<", None)
    h, s = held["h"], held["h"].state
    d = Path(h.expt_dir)
    return {
        "cfg": cfg,
        "harness": h,
        "dir": d,
        "summaries": summaries,
        "rows": rows,
        "reads": reads,
        "spans": tracing.recorded(t0=whole.start, t1=whole.end),
        "fingerprint": tree_fingerprint(
            {"params": s.params, "masks": s.masks, "batch_stats": s.batch_stats}
        ),
        # As they are now: a later resume in this directory writes again.
        "timing": pd.read_csv(t) if (t := d / "metrics" / "level_timing.csv").exists() else None,
        "written": {
            f"{sub}/{p.name}": tree_fingerprint(restore_pytree(p))
            for sub in ("checkpoints", "artifacts")
            for p in sorted((d / sub).iterdir())
            if not p.name.startswith("mid_level")  # a slot is three files of its own
        },
    }


@pytest.fixture(scope="module")
def whole_run(tmp_path_factory):
    """The uninterrupted three-level IMP run both classes below read."""
    return _traced_run(_cfg(tmp_path_factory.mktemp("imp")))


def _killed_and_resumed(base, kill_level, *extra, harness_cls=None, in_flight=False):
    """Two runs in one directory: the first dies (the tests' preemption) once
    ``model_level_{kill_level}`` is saved, the second takes it up at the next
    level. ``in_flight``: it dies one ``save_level`` later, when that save has
    returned and its write is still held in the writer, never to be committed:
    what a kill in the first moments of the level after leaves behind."""
    import threading

    from turboprune_tpu.harness import PruningHarness
    from turboprune_tpu.utils import checkpoint

    dead, real_write = threading.Event(), checkpoint._write_tree

    def write_until_killed(path, tree, **attrs):
        if path.name == f"model_level_{kill_level + 1}":
            assert dead.wait(60)  # held until save_level has returned
            raise KeyboardInterrupt("killed with the write in flight")
        real_write(path, tree, **attrs)

    class Killed(harness_cls or PruningHarness):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            save = self.ckpts.save_level

            def dying(level, state):
                save(level, state)
                if level == kill_level + in_flight:
                    assert not self.ckpts.level_path(level).exists()  # behind: not yet
                    dead.set()
                    raise KeyboardInterrupt("simulated preemption")

            self.ckpts.save_level = dying

    with pytest.MonkeyPatch.context() as patch:
        if in_flight:
            patch.setattr(checkpoint, "_write_tree", write_until_killed)
        killed = _traced_run(_cfg(base, *extra), Killed)
    resumed = _traced_run(
        _cfg(
            base,
            *extra,
            "experiment_params.resume_experiment=true",
            f"experiment_params.resume_experiment_stuff.resume_expt_name={killed['dir'].name}",
            f"experiment_params.resume_experiment_stuff.resume_level={kill_level + 1}",
        ),
        harness_cls,
    )
    return killed, resumed


def _named(run_, name):
    return [s for s in run_["spans"] if s.name == name]


def _counted_on_disk(run_, level):
    """Sparsity (%) and density of ``model_level_<level>``'s masks, counted
    in numpy by the formula ``ops/masking.py`` had."""
    h = run_["harness"]
    masks = h.ckpts.load_level(level, h.state)["masks"]
    total = zeros = 0
    for m in jax.tree.leaves(masks):
        total += int(m.size)
        zeros += int(m.size - np.sum(np.asarray(m)))
    sparsity = (zeros / total) * 100.0
    return sparsity, 1.0 - sparsity / 100.0


def _reports_the_masks_on_disk(run_, level):
    """Every epoch row's ``sparsity``, the summary's ``final_sparsity`` and
    ``achieved_density`` of ``level`` against the checkpointed masks: the
    carried numbers are the read ones, float for float."""
    sparsity, density = _counted_on_disk(run_, level)
    rows = [r for r in run_["rows"] if r["level"] == level]
    assert rows and [r["sparsity"] for r in rows] == [sparsity] * len(rows)
    (summary,) = [s for s in run_["summaries"] if s["level"] == level]
    assert summary["final_sparsity"] == sparsity
    assert summary["achieved_density"] == density
    return sparsity


def _mask_reads(run_, level):
    """What ``level`` added to the ``mask_reads`` gauge: in all (from its
    first event to the next level's, or the run's end), inside
    ``train_one_level``, and across its ``_train_eval_log`` calls."""
    log = run_["reads"]
    first = next(i for i, (_, lv, _) in enumerate(log) if lv == level)
    after = next(i for i, (_, lv, _) in enumerate(log) if i > first and lv != level)
    at = {}
    for event, lv, n in log[first:after]:
        at.setdefault(event, []).append(n)
    (begun,), (done,) = at["train>"], at["train<"]
    in_epochs = sum(b - a for a, b in zip(at["epoch>"], at["epoch<"]))
    return log[after][2] - log[first][2], done - begun, in_epochs


class TestIterativeIMP:
    @pytest.fixture(scope="class")
    def imp_run(self, whole_run):
        return whole_run["cfg"], str(whole_run["dir"]), whole_run["summaries"]

    def test_ladder_lengths_and_densities(self, imp_run):
        _, _, summaries = imp_run
        # 1.0, 0.8, 0.64 — stops at target density 0.64
        assert len(summaries) == 3
        np.testing.assert_allclose(
            [s["density"] for s in summaries], [1.0, 0.8, 0.64], atol=1e-6
        )
        np.testing.assert_allclose(
            [s["achieved_density"] for s in summaries],
            [1.0, 0.8, 0.64],
            atol=5e-4,
        )

    def test_artifacts_on_disk(self, imp_run):
        from pathlib import Path

        _, expt_dir, _ = imp_run
        d = Path(expt_dir)
        assert (d / "expt_config.yaml").exists()
        for lvl in range(3):
            assert (d / "checkpoints" / f"model_level_{lvl}").exists()
            assert (
                d / "metrics" / "level_wise_metrics" / f"level_{lvl}_metrics.csv"
            ).exists()
        assert (d / "checkpoints" / "model_init").exists()
        assert (d / "artifacts" / "optimizer_init").exists()

    def test_metrics_csv_contents(self, imp_run):
        from pathlib import Path

        cfg, expt_dir, _ = imp_run
        d = Path(expt_dir)
        lv = pd.read_csv(d / "metrics" / "level_wise_metrics" / "level_1_metrics.csv")
        assert len(lv) == 2  # epochs_per_level
        assert {"epoch", "train_loss", "train_acc", "test_loss", "test_acc",
                "max_test_acc", "sparsity"} <= set(lv.columns)
        assert (lv["sparsity"] > 19).all() and (lv["sparsity"] < 21).all()
        summary_files = list((d / "metrics").glob("*_summary.csv"))
        assert len(summary_files) == 1
        summary = pd.read_csv(summary_files[0])
        assert list(summary["level"]) == [0, 1, 2]

    def test_resume_from_level(self, imp_run, tmp_path):
        from pathlib import Path

        cfg, expt_dir, summaries = imp_run
        name = Path(expt_dir).name
        cfg2 = _cfg(
            Path(expt_dir).parent,
            "experiment_params.resume_experiment=true",
            f"experiment_params.resume_experiment_stuff.resume_expt_name={name}",
            "experiment_params.resume_experiment_stuff.resume_level=2",
        )
        expt_dir2, summaries2 = run(cfg2)
        assert expt_dir2 == expt_dir
        assert len(summaries2) == 1
        assert summaries2[0]["level"] == 2
        np.testing.assert_allclose(summaries2[0]["density"], 0.64, atol=1e-6)


class TestCarriedMaskCount:
    """The sparsity of the masks is read by one compiled reduction where the
    masks change and carried (``PruningHarness.mask_count``): between two
    writes of ``state.masks`` it runs at most once, and never in the epoch
    loop. Level 0 reads the masks it was built with, in its set-up; a steady
    level reads once, after its prune, and its ``before`` is the level
    before's ``after``."""

    @pytest.mark.parametrize("level", [0, 1, 2])
    def test_every_reported_sparsity_is_that_of_the_levels_checkpointed_masks(self, whole_run, level):
        sparsity = _reports_the_masks_on_disk(whole_run, level)
        assert sparsity == pytest.approx([0.0, 20.0, 36.0][level], abs=0.05)
        assert len([r for r in whole_run["rows"] if r["level"] == level]) == 2

    @pytest.mark.parametrize("level, in_set_up", [(0, 1), (1, 0), (2, 0)])
    def test_a_level_reads_the_masks_once_and_no_epoch_reads_them(self, whole_run, level, in_set_up):
        in_all, in_train_one_level, in_epochs = _mask_reads(whole_run, level)
        assert (in_all, in_train_one_level, in_epochs) == (1, in_set_up, 0)

    def test_the_level_csv_holds_the_carried_sparsity(self, whole_run):
        for level in range(3):
            lv = pd.read_csv(
                whole_run["dir"] / "metrics" / "level_wise_metrics" / f"level_{level}_metrics.csv",
                float_precision="round_trip",
            )
            assert list(lv["sparsity"]) == [_counted_on_disk(whole_run, level)[0]] * 2


class TestLevelHandOff:
    """A continuous run hands its state from level to level in memory and
    rewinds from the resident target; the directory is read only by a process
    that does not hold the state. Three runs of one seed: ``whole`` is
    uninterrupted; ``killed`` dies in the first moments of level 2, when level
    1's save has returned and its write is still in flight, so that
    ``model_level_0`` is the last level on disk; ``resumed`` takes it up at
    level 1, reading from disk once what every level used to read, repeats
    that level and goes on to level 2 as the process that wrote it would
    (``test_level_resume.py`` has a kill at a committed save, for ``wr`` with
    its optimizer and for a cyclic run)."""

    @pytest.fixture(scope="class")
    def runs(self, whole_run, tmp_path_factory):
        killed, resumed = _killed_and_resumed(tmp_path_factory.mktemp("handoff"), 0, in_flight=True)
        return {"whole": whole_run, "killed": killed, "resumed": resumed}

    _named = staticmethod(_named)

    def test_a_continuous_run_reads_nothing_back(self, runs):
        whole = runs["whole"]
        assert [s.attrs["level"] for s in self._named(whole, "level")] == [0, 1, 2]
        assert not self._named(whole, "ckpt/read") and not self._named(whole, "level/load")
        rewinds = self._named(whole, "level/rewind")
        assert [(s.attrs["level"], s.attrs["source"]) for s in rewinds] == [
            (1, "resident"),
            (2, "resident"),
        ]
        timing = whole["timing"]
        assert list(timing["level"]) == [0, 1, 2]
        assert (timing["load_s"] == 0).all() and (timing["ckpt_read_s"] == 0).all()

    def test_a_resumed_run_loads_once_and_reads_the_rewind_target_once(self, runs):
        killed, resumed = runs["killed"], runs["resumed"]
        assert [s.attrs["level"] for s in self._named(killed, "level")] == [0, 1]
        assert not self._named(killed, "ckpt/read")
        assert [s.attrs["level"] for s in self._named(resumed, "level")] == [1, 2]
        assert [s.attrs["level"] for s in self._named(resumed, "level/load")] == [1]
        rewinds = self._named(resumed, "level/rewind")
        assert [(s.attrs["level"], s.attrs["source"]) for s in rewinds] == [
            (1, "disk"),
            (2, "resident"),
        ]
        # model_level_0 and model_init, both in the resumed level: level 2
        # reads nothing back.
        assert [s.attrs["level"] for s in self._named(resumed, "ckpt/read")] == [1, 1]

    def test_a_kill_with_a_write_in_flight_costs_that_level_and_no_more(self, runs):
        """Level 1's save had returned when the process died; its write was
        never committed, so the directory holds no ``model_level_1``, under
        no name: a resumed process and the server list committed levels only.
        The wait at the run's end raised what the writer raised."""
        killed, resumed = runs["killed"], runs["resumed"]
        assert [s.attrs["level"] for s in self._named(killed, "level/save")] == [0, 1]
        assert "error" not in self._named(killed, "level/save")[0].attrs
        assert sorted(k for k in killed["written"] if "level" in k) == ["checkpoints/model_level_0"]
        loop = self._named(killed, "level")[0].thread
        behind = [s for s in self._named(killed, "ckpt/write") if s.thread != loop]
        assert [s.attrs["level"] for s in behind] == [0]  # level 1's never ran to its end
        assert self._named(killed, "ckpt/wait")[-1].attrs["error"] == "KeyboardInterrupt"
        # The resumed run repeats level 1 from model_level_0 and writes it.
        assert [s.attrs["level"] for s in self._named(resumed, "ckpt/write")] == [1, 2]
        assert resumed["harness"].ckpts.saved_levels() == [0, 1, 2]

    def test_what_a_process_keeps_is_on_the_host(self, runs):
        """The writer keeps the tree it fetched for the save. The resumed
        process keeps what it read, and Orbax restores onto the devices of the
        state it is shown: kept as that, ``replicate`` would alias it and the
        donating step delete it before the second rewind."""
        for run_ in (runs["whole"], runs["killed"], runs["resumed"]):
            held = run_["harness"].ckpts._resident
            assert set(held) == {"model_init"}
            assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(held))

    def test_killed_and_resumed_ends_where_the_continuous_run_ends(self, runs):
        assert runs["resumed"]["fingerprint"] == runs["whole"]["fingerprint"]
        assert runs["killed"]["fingerprint"] != runs["whole"]["fingerprint"]

    @pytest.mark.parametrize("level, reads", [(1, 2), (2, 1)])
    def test_a_resumed_process_reads_the_masks_it_loaded_and_then_carries(self, runs, level, reads):
        """Its first level holds no carried count: ``level/load`` wrote the
        masks, so the prune's ``before`` is a read and its ``after`` another;
        from then on it is the process that wrote the directory."""
        resumed = runs["resumed"]
        assert _reports_the_masks_on_disk(resumed, level) == _counted_on_disk(runs["whole"], level)[0]
        assert _mask_reads(resumed, level) == (reads, 0, 0)

    def test_the_resident_rewind_target_survives_the_donating_steps(self, runs):
        """Three trained levels donated their state to the step; a rewind
        from the resident tree still gives model_init as it is on disk."""
        from turboprune_tpu.utils import MODEL_INIT, reset_weights

        h = runs["whole"]["harness"]
        back = reset_weights("imp", h.state, h.ckpts)
        on_disk = h.ckpts.load_model(MODEL_INIT, h.state)
        for key in ("params", "batch_stats"):
            jax.tree.map(
                lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
                getattr(back, key),
                on_disk[key],
            )
        trained = jax.tree.leaves(h.state.params)[0]
        assert not np.array_equal(np.asarray(trained), np.asarray(jax.tree.leaves(back.params)[0]))

    @pytest.mark.parametrize(
        "role",
        [
            "checkpoints/model_init",
            "checkpoints/model_level_0",
            "checkpoints/model_level_1",
            "checkpoints/model_level_2",
            "artifacts/optimizer_init",
        ],
    )
    def test_every_checkpoint_holds_what_a_run_that_read_from_disk_wrote(self, runs, role):
        """``model_level_1`` of the continuous run came from memory, that of
        the resumed run from ``model_level_0`` and ``model_init`` on disk, as
        every level's did before: the restored trees are equal in every bit."""
        whole, cut = runs["whole"]["written"], runs["resumed"]["written"]
        assert sorted(whole) == sorted(cut) and len(whole) == 5
        assert whole[role] == cut[role]
        assert runs["killed"]["written"].get(role) in (whole[role], None)


class TestPruneAtInit:
    def test_er_erk_single_level(self, tmp_path):
        cfg = _cfg(
            tmp_path,
            "pruning_params.prune_method=er_erk",
            "pruning_params.training_type=at_init",
            "pruning_params.target_sparsity=0.5",
        )
        expt_dir, summaries = run(cfg)
        assert len(summaries) == 1
        # ERK clamps layer densities at 1 WITHOUT redistribution (reference
        # pruning_utils.py:127), so on resnet18 the achieved density falls
        # short of target; check against the allocation's own expectation
        # (er_* additionally are Bernoulli draws — approximate).
        import jax

        from turboprune_tpu.models import create_model
        from turboprune_tpu.ops import masking
        from turboprune_tpu.pruning import erk_densities
        from turboprune_tpu.train import create_optimizer, create_train_state

        model = create_model("resnet18", 10, "CIFAR10")
        tx = create_optimizer("SGD", 0.1)
        st = create_train_state(model, tx, jax.random.PRNGKey(0), (1, 32, 32, 3))
        alloc = erk_densities(st.masks, 0.5)
        sizes = {
            masking.path_name(p): m.size
            for p, m in masking.mask_leaves_with_path(st.masks)
        }
        expected = sum(alloc[n] * sizes[n] for n in sizes) / sum(sizes.values())
        assert abs(summaries[0]["achieved_density"] - expected) < 0.02

    @pytest.fixture(scope="class")
    def snip_run(self, tmp_path_factory):
        return _traced_run(
            _cfg(
                tmp_path_factory.mktemp("snip"),
                "pruning_params.prune_method=snip",
                "pruning_params.training_type=at_init",
                "pruning_params.target_sparsity=0.5",
            )
        )

    def test_snip_single_level(self, snip_run):
        summaries = snip_run["summaries"]
        assert len(summaries) == 1
        assert abs(summaries[0]["achieved_density"] - 0.5) < 5e-3

    def test_the_level_zero_prune_reads_before_and_after_and_the_level_carries(self, snip_run):
        """Level 0 of pruning at init: the masks the harness was built with
        are read for the prune's ``before``, the pruned ones for its
        ``after``; set-up, both epochs, the summary and the driver carry."""
        assert _reports_the_masks_on_disk(snip_run, 0) == pytest.approx(50.0, abs=0.5)
        assert _mask_reads(snip_run, 0) == (2, 0, 0)


class TestWeightRewinding:
    def test_wr_trains_with_rewind_epoch(self, tmp_path):
        from pathlib import Path

        cfg = _cfg(
            tmp_path,
            "pruning_params.training_type=wr",
            "pruning_params.rewind_epoch=0",
            "pruning_params.target_sparsity=0.2",
        )
        expt_dir, summaries = run(cfg)
        d = Path(expt_dir)
        assert (d / "checkpoints" / "model_rewind").exists()
        assert (d / "artifacts" / "optimizer_rewind").exists()
        assert len(summaries) == 2  # 1.0, 0.8


class TestOptimizerRewind:
    def test_wr_rewind_restores_momentum_but_not_schedule_count(self, tmp_path):
        """rewind_optimizer must restore the momentum trace captured at
        rewind_epoch while the per-level LR schedule restarts at step 0 —
        restoring ScaleByScheduleState.count would fast-forward the fresh
        schedule to rewind_epoch's position (ADVICE r3)."""
        import optax

        from turboprune_tpu.harness import PruningHarness
        from turboprune_tpu.utils import OPTIMIZER_REWIND, gen_expt_dir

        cfg = _cfg(
            tmp_path,
            "pruning_params.training_type=wr",
            "pruning_params.rewind_epoch=0",
            "pruning_params.rewind_optimizer=true",
        )
        h = PruningHarness(cfg, gen_expt_dir(cfg))
        h.setup_level(cfg.experiment_params.epochs_per_level)
        h.train_epoch()  # advance: momentum warm, schedule count > 0
        saved_count = int(optax.tree_utils.tree_get(h.state.opt_state, "count"))
        assert saved_count > 0
        saved_trace = jax.tree.map(
            lambda x: np.asarray(jax.device_get(x)),
            optax.tree_utils.tree_get(h.state.opt_state, "trace"),
        )
        h.ckpts.save_optimizer(OPTIMIZER_REWIND, h.state.opt_state)

        # Twice: the second level trains on (and donates) what the first
        # rewind handed out, and the resident copy must not have moved.
        from turboprune_tpu.utils import tracing

        with tracing.span("t/rewinds") as whole:
            for _ in range(2):
                h.setup_level(cfg.experiment_params.epochs_per_level)  # fresh level
                assert int(optax.tree_utils.tree_get(h.state.opt_state, "count")) == 0
                h.maybe_rewind_optimizer(level=1)
                # momentum buffers came back ...
                got_trace = optax.tree_utils.tree_get(h.state.opt_state, "trace")
                jax.tree.map(
                    lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
                    got_trace,
                    saved_trace,
                )
                # ... but the schedule count did NOT
                assert int(optax.tree_utils.tree_get(h.state.opt_state, "count")) == 0
                h.train_epoch()
        # ... and from the copy this process kept at the save, not from disk.
        assert not tracing.recorded("ckpt/read", whole.start, whole.end)

    def test_adamw_rewind_keeps_bias_correction_count(self, tmp_path):
        """Only the SCHEDULE state resets on rewind: AdamW's
        ScaleByAdamState.count drives bias correction for the restored
        mu/nu moments and must be restored WITH them (code-review r4)."""
        import optax

        from turboprune_tpu.harness import PruningHarness
        from turboprune_tpu.utils import OPTIMIZER_REWIND, gen_expt_dir

        def states_of(tree, typ):
            found = []

            def walk(node):
                if isinstance(node, typ):
                    found.append(node)
                    return
                if isinstance(node, (tuple, list)):
                    for c in node:
                        walk(c)

            walk(tree)
            return found

        cfg = _cfg(
            tmp_path,
            "optimizer_params.optimizer_name=AdamW",
            "pruning_params.training_type=wr",
            "pruning_params.rewind_epoch=0",
            "pruning_params.rewind_optimizer=true",
        )
        h = PruningHarness(cfg, gen_expt_dir(cfg))
        h.setup_level(cfg.experiment_params.epochs_per_level)
        h.train_epoch()
        (adam,) = states_of(h.state.opt_state, optax.ScaleByAdamState)
        saved_adam_count = int(adam.count)
        assert saved_adam_count > 0
        h.ckpts.save_optimizer(OPTIMIZER_REWIND, h.state.opt_state)

        h.setup_level(cfg.experiment_params.epochs_per_level)
        h.maybe_rewind_optimizer(level=1)
        (adam,) = states_of(h.state.opt_state, optax.ScaleByAdamState)
        assert int(adam.count) == saved_adam_count  # bias correction intact
        (sched,) = states_of(h.state.opt_state, optax.ScaleByScheduleState)
        assert int(sched.count) == 0  # schedule restarts


class TestCyclic:
    def test_two_cycles_constant(self, tmp_path):
        from pathlib import Path

        cfg = _cfg(
            tmp_path,
            "cyclic_training.num_cycles=2",
            "cyclic_training.strategy=constant",
            "pruning_params.target_sparsity=0.2",
        )
        expt_dir, summaries = run_cyclic(cfg)
        assert len(summaries) == 2
        lv = pd.read_csv(
            Path(expt_dir) / "metrics" / "level_wise_metrics" / "level_0_metrics.csv"
        )
        assert "cycle" in lv.columns
        assert set(lv["cycle"]) == {0, 1}
