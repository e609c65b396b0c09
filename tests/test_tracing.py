"""utils/tracing.py: the recorder alone, then a tiny two-level ladder through
``run_experiment.main`` with ``profile_dir`` set, read back through the spans,
the ``[time]`` lines, ``level_timing.csv`` and the two profiler sessions. CPU
only: nothing here is a speed."""

import contextlib
import io
import re
import threading
from collections import deque
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pandas as pd
import pytest

from turboprune_tpu.utils import tracing


def _made(name, start, end, parent=None, **attrs):
    s = tracing.Span(name, attrs)
    s.start, s.end, s.parent = start, end, parent.id if parent else None
    return s


class TestRecorder:
    def test_nesting_gives_parent_ids_and_threads_do_not_share_a_stack(self):
        seen = {}

        def worker():
            with tracing.span("t/outer") as outer:
                with tracing.span("t/inner") as inner:
                    pass
            seen["worker"] = (outer, inner)

        with tracing.span("t/main") as main:
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()
            with tracing.span("t/child") as child:
                pass
        outer, inner = seen["worker"]
        assert child.parent == main.id and main.parent is None
        # The worker's spans opened while t/main was open on this thread.
        assert outer.parent is None and inner.parent == outer.id
        assert outer.thread != main.thread
        assert len({main.id, child.id, outer.id, inner.id}) == 4
        names = [s.name for s in tracing.recorded(t0=main.start, t1=main.end)]
        assert names == ["t/inner", "t/outer", "t/child", "t/main"]  # closing order
        assert main.start <= child.start <= child.end <= main.end

    def test_level_and_epoch_are_inherited_and_nothing_else(self):
        with tracing.span("level", level=3, density=0.5):
            with tracing.span("epoch", epoch=7, cycle=1):
                with tracing.span("epoch/log") as log:
                    pass
            with tracing.span("level/save", level=4) as save:
                pass
        assert log.attrs == {"level": 3, "epoch": 7}
        assert save.attrs == {"level": 4}  # its own wins

    def test_a_span_closed_by_an_exception_is_recorded_with_the_error(self):
        class WindowClosed(Exception):
            pass

        with pytest.raises(WindowClosed):
            with tracing.span("level", level=1) as level:
                with tracing.span("level/save") as save:
                    raise WindowClosed()
        assert save.attrs["error"] == level.attrs["error"] == "WindowClosed"
        assert tracing.recorded("level")[-1] is level and level.end >= save.end > 0
        with tracing.span("after") as after:
            pass
        assert after.parent is None and "error" not in after.attrs  # the stack unwound

    def test_breakdown_of_hand_made_spans_sums_to_the_root(self):
        level = _made("level", 0.0, 10.0, level=2)
        load = _made("level/load", 0.0, 1.0, level)
        read = _made("ckpt/read", 0.1, 0.9, load)
        train = _made("level/train", 1.5, 9.0, level)
        epochs = [_made("epoch", 2.0 + 3 * i, 4.5 + 3 * i, train) for i in range(2)]
        inner = [_made("epoch/train", e.start, e.start + 2.0, e) for e in epochs]
        # A level's save as it runs since PR 30: it waits for the write of the
        # level before (and holds that save's barrier), fetches, and returns.
        # That write ended behind ``epoch/train``, on the writer's thread.
        save = _made("level/save", 9.0, 10.0, level)
        wait = _made("ckpt/wait", 9.0, 9.125, save)
        barrier = _made("ckpt/barrier", 9.125, 9.25, save)
        fetch = _made("ckpt/fetch", 9.25, 9.75, save)
        behind = _made("ckpt/write", -0.25, 2.5, level=1)
        behind.thread += 1
        inner[1].compiles, inner[1].compile_s = 2, 0.5
        inner[0].trace_s, inner[1].trace_s, epochs[0].lower_s = 0.25, 0.5, 0.125
        spans = [read, load, behind, *inner, *epochs, train, wait, barrier, fetch, save, level]
        b = tracing.breakdown([level], spans)
        assert b["terms"] == {"level/load": 1.0, "epoch/train": 4.0, "level/save": 1.0}
        assert b["inside"] == {
            "level/load": {"ckpt/read": 0.8},
            "level/save": {"ckpt/wait": 0.125, "ckpt/barrier": 0.125, "ckpt/fetch": 0.5},
        }
        assert b["behind"] == [(1, 2.75)]
        # level 0.5 of its own, level/train 7.5 - 5.0, each epoch 0.5
        assert b["other_s"] == pytest.approx(0.5 + 2.5 + 1.0)
        assert sum(b["terms"].values()) + b["other_s"] == pytest.approx(b["total_s"]) == 10.0
        assert (b["compiles"], b["compile_s"]) == (2, 0.5)
        assert (b["trace_s"], b["lower_s"]) == (0.75, 0.125)  # summed over terms and containers
        assert tracing.line("level 2", b) == (
            "[time] level 2: 10.00 s = load 1.00 (read 0.80) + train 4.00 + "
            "save 1.00 (wait 0.12, barrier 0.12, fetch 0.50) + other 4.00; "
            "wrote level 1 behind, 2.75 s; traced 0.8 s, lowered 0.1 s, compiled 2 modules "
            "in 0.5 s (0 read from the cache in 0.0 s; 0 missed)"
        )
        row = tracing.timing_row(level, b)
        assert list(row) == tracing.TIMING_COLUMNS
        assert (row["level"], row["train_s"], row["prune_s"]) == (2, 4.0, 0.0)
        # The stall on the loop, and the work off it that ended in this level.
        assert (row["ckpt_wait_s"], row["ckpt_fetch_s"], row["ckpt_write_s"]) == (0.125, 0.5, 2.75)

    @pytest.mark.parametrize(
        "case, start, end, other_thread, said",
        [
            ("ended in the level", -1.0, 1.0, True, [(1, 2.0)]),
            ("ended before it opened", -2.0, -0.5, True, []),  # the level before names it
            ("still running at its end", 9.0, 11.0, True, []),  # the next level's, or the exit's
            ("in line, on the level's thread", 1.0, 2.0, False, []),  # a child, named inside its term
        ],
    )
    def test_a_level_names_the_write_that_ended_behind_it(self, case, start, end, other_thread, said):
        level = _made("level", 0.0, 10.0, level=2)
        ckpt = _made("epoch/ckpt", 1.0, 2.0, level)
        write = _made("ckpt/write", start, end, None if other_thread else ckpt, level=1)
        write.thread += other_thread
        b = tracing.breakdown([level], [write, ckpt, level])
        assert b["behind"] == said, case
        assert ("wrote level 1 behind, 2.00 s" in tracing.line("level 2", b)) == bool(said)
        in_line = 0.0 if other_thread else 1.0
        assert tracing.timing_row(level, b)["ckpt_write_s"] == in_line + sum(s for _, s in said)

    def test_the_recorder_is_bounded_and_keeps_the_newest(self, monkeypatch):
        assert tracing._spans.maxlen == tracing.MAX_SPANS
        monkeypatch.setattr(tracing, "_spans", deque(maxlen=8))
        for i in range(20):
            with tracing.span("t/bounded", i=i):
                pass
        assert [s.attrs["i"] for s in tracing.recorded("t/bounded")] == list(range(12, 20))

    def test_a_compile_is_charged_to_the_innermost_open_span_only(self):
        f = jax.jit(lambda x: x * 3 + 1)
        x = jnp.ones(3)  # an eager op is a compilation of its own
        with tracing.span("t/root") as root:
            with tracing.span("t/before") as before:
                pass
            with tracing.span("t/compiles") as here:
                f(x).block_until_ready()
            with tracing.span("t/cached") as cached:
                f(x).block_until_ready()
        assert here.compiles == 1 and here.compile_s > 0
        assert (before.compiles, cached.compiles, root.compiles) == (0, 0, 0)
        assert tracing.breakdown([root])["compiles"] == 1

    def test_the_tail_names_the_cache_and_the_longest_misses_and_the_row_has_them(self, monkeypatch):
        level = _made("level", 0.0, 100.0, level=0)
        train = _made("epoch/train", 1.0, 90.0, level)
        other = _made("epoch/train", 0.0, 1.0)  # not under the level
        train.trace_s, train.lower_s, train.compiles, train.compile_s = 12.25, 3.5, 7, 60.0
        train.cache_hits, train.cache_read_s, train.cache_misses, train.miss_compile_s = 2, 1.25, 4, 57.5
        level.compiles, level.compile_s = 1, 0.25  # a container's own count too
        missed = [("jit(scan_chunk)", 34.5), ("jit(a)", 0.5), ("jit(top_k)", 20.0), ("jit(b)", 2.5)]
        record = [tracing.Module(n, 5.0, train.id, train.name, 0.1, sec, "miss") for n, sec in missed]
        record.append(tracing.Module("jit(hit)", 5.0, train.id, train.name, 0.1, 0.75, "hit"))
        record.append(tracing.Module("jit(elsewhere)", 0.5, other.id, other.name, 0.1, 99.0, "miss"))
        monkeypatch.setattr(tracing, "_modules", deque(record, maxlen=tracing.MAX_MODULES))
        b = tracing.breakdown([level], [train, level, other])
        assert b["missed"] == [("jit(scan_chunk)", 34.5), ("jit(top_k)", 20.0), ("jit(b)", 2.5), ("jit(a)", 0.5)]
        assert tracing.line("level 0", b).split("; ", 1)[1] == (
            "traced 12.2 s, lowered 3.5 s, compiled 8 modules in 60.2 s (2 read from the cache "
            "in 1.2 s; 4 missed: jit(scan_chunk) 34.5, jit(top_k) 20.0, jit(b) 2.5)"
        )
        row = tracing.timing_row(level, b)
        assert list(row) == tracing.TIMING_COLUMNS
        assert tracing.TIMING_COLUMNS[-8:] == [
            "compiles", "compile_s", "trace_s", "lower_s",
            "cache_hits", "cache_misses", "cache_read_s", "miss_compile_s",
        ]  # fmt: skip
        assert [row[c] for c in tracing.TIMING_COLUMNS[-8:]] == [8, 60.25, 12.25, 3.5, 2, 4, 1.25, 57.5]

    @pytest.mark.parametrize("nested", [False, True], ids=["one jit", "a jit inside a jit"])
    def test_a_first_call_is_charged_its_trace_lowering_and_compile_once(self, nested):
        inner = jax.jit(lambda x: jnp.tanh(x) * 5 + 2)
        f = jax.jit(lambda x: inner(inner(x)) - 1) if nested else inner
        x = jnp.ones(5)
        with tracing.span("t/first") as first:
            f(x).block_until_ready()
        with tracing.span("t/second") as second:
            f(x).block_until_ready()
        assert first.trace_s > 0 and first.lower_s > 0 and first.compiles == 1 and first.compile_s > 0
        # A jit traced inside a jit, and a trace inside a lowering, are
        # charged their own seconds once: the three never exceed the wall.
        assert first.trace_s + first.lower_s + first.compile_s <= first.seconds
        charged = [getattr(second, k) for k in tracing.CHARGED]
        assert charged == [0] * len(tracing.CHARGED)
        (module,) = tracing.modules(first.start, first.end)
        assert (module.span, module.span_name, module.cache) == (first.id, "t/first", "none")
        assert module.name.startswith("jit(") and module.compile_s == first.compile_s
        assert 0 < module.lower_s <= first.lower_s
        assert not tracing.modules(second.start, second.end)

    def test_the_persistent_cache_answers_miss_then_hit_by_module_and_span(self, tmp_path):
        from jax.experimental.compilation_cache import compilation_cache as cc

        keys = {
            "jax_enable_compilation_cache": True,
            "jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0,
            "jax_persistent_cache_min_entry_size_bytes": -1,
        }
        before = {k: getattr(jax.config, k) for k in keys}
        f = jax.jit(lambda x: jnp.cos(x) * 7 - 3)
        x = jnp.ones(7)
        try:
            for k, v in keys.items():
                jax.config.update(k, v)
            cc.reset_cache()
            with tracing.span("t/cold") as cold:
                f(x).block_until_ready()
            jax.clear_caches()  # what a new process starts with: the directory alone
            with tracing.span("t/warm") as warm:
                f(x).block_until_ready()
        finally:
            for k, v in before.items():
                jax.config.update(k, v)
            cc.reset_cache()
        assert (cold.compiles, cold.cache_misses, cold.cache_hits) == (1, 1, 0)
        assert cold.miss_compile_s == cold.compile_s > 0 and cold.cache_read_s == 0
        assert (warm.compiles, warm.cache_misses, warm.cache_hits) == (1, 0, 1)
        assert 0 < warm.cache_read_s <= warm.compile_s and warm.miss_compile_s == 0
        (missed,), (hit,) = tracing.modules(cold.start, cold.end), tracing.modules(warm.start, warm.end)
        assert (missed.cache, missed.span, missed.span_name) == ("miss", cold.id, "t/cold")
        assert (hit.cache, hit.span, hit.span_name) == ("hit", warm.id, "t/warm")
        assert missed.name == hit.name and cold.start <= missed.when <= cold.end
        b = tracing.breakdown([cold])
        assert b["missed"] == [(missed.name, cold.compile_s)]
        assert f"1 missed: {missed.name} " in tracing.line("cold", b)

    def test_a_module_compiled_on_another_thread_is_charged_to_that_threads_span(self):
        f = jax.jit(lambda x: jnp.sinh(x) * 11)
        x = jnp.ones(11)
        seen = {}

        def worker():
            with tracing.span("t/worker") as s:
                f(x).block_until_ready()
            seen["span"] = s

        with tracing.span("t/main") as main:
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=60)
            assert not t.is_alive()
        theirs = seen["span"]
        assert theirs.compiles == 1 and theirs.trace_s > 0 and theirs.thread != main.thread
        assert [getattr(main, k) for k in tracing.CHARGED] == [0] * len(tracing.CHARGED)
        (module,) = tracing.modules(main.start, main.end)
        assert (module.span, module.span_name) == (theirs.id, "t/worker")

    def test_the_module_record_is_bounded_and_a_module_outside_every_span_is_kept(self, monkeypatch):
        assert tracing._modules.maxlen == tracing.MAX_MODULES == 4096
        monkeypatch.setattr(tracing, "_modules", deque(maxlen=2))
        for i in range(3):
            jax.jit(lambda x, i=i: x * (13 + i))(jnp.ones(13)).block_until_ready()
        kept = tracing.modules()
        assert len(kept) == 2 and all(m.span is None and m.span_name is None for m in kept)

    def test_gauges_hold_the_last_value_set(self):
        tracing.gauge("t_gauge", 3)
        tracing.gauge("t_gauge", 5)
        assert tracing.gauges()["t_gauge"] == 5

    def test_the_gauges_set_while_a_program_is_traced_are_told_apart(self):
        """What the first-epoch line prints: no module's names listed anywhere."""

        def built(x):
            tracing.gauge("t_traced_gauge", x.shape[0])
            tracing.count("t_traced_count")
            return x + 1

        tracing.count("t_host_count")
        jax.jit(built)(jnp.zeros(7))
        got = tracing.trace_gauges()
        assert got["t_traced_gauge"] == 7 and got["t_traced_count"] == 1
        assert "t_host_count" not in got and "t_gauge" not in got
        assert tracing.gauges()["t_host_count"] == 1


def test_the_lowered_train_step_names_its_layers():
    from turboprune_tpu.models import create_model
    from turboprune_tpu.train import create_optimizer, create_train_state, make_eval_step, make_train_step

    model = create_model("resnet18", num_classes=10, dataset_name="CIFAR10")
    tx = create_optimizer("SGD", lambda step: 0.1, momentum=0.9, weight_decay=5e-4)
    # Shapes are enough to lower: no weights are made, nothing compiles.
    state = jax.eval_shape(
        lambda: create_train_state(model, tx, jax.random.PRNGKey(0), (1, 32, 32, 3))
    )
    batch = (jnp.zeros((4, 32, 32, 3)), jnp.zeros((4,), jnp.int32))
    text = jax.jit(make_train_step(model, tx)).lower(state, batch).as_text(debug_info=True)
    for scope in ("forward", "loss", "optimizer", "mask_apply", "transpose(jvp(forward))"):
        assert scope in text, scope
    text = jax.jit(make_eval_step(model)).lower(state, batch).as_text(debug_info=True)
    assert "eval_forward" in text and "mask_apply" in text


# What a two-level IMP ladder records (ISSUE 24's table), and whether a level
# without a prune (level 0) records it too. RESUMED: nowhere in a continuous
# run, which hands its state on in memory; only in level 1 of a process that
# starts from level 0's checkpoint. A level's write runs behind the next
# level (``ckpt/write``, on the writer's thread, with the level that asked for
# it); ``ckpt/wait`` is recorded where a write was in flight to wait for:
# in level 1's save and, outside every level, at the run's end.
RESUMED = "resumed"
LADDER_SPANS = {
    "setup/imports": None, "setup/config": None, "setup/distributed": None, "setup/backend": None,
    "harness/init": None, "init/mesh_model": None, "init/loaders": None,
    "init/state": None, "init/steps": None,
    "level": True, "level/load": RESUMED, "level/prune": False, "level/rewind": False,
    "level/train": True, "level/setup": True, "epoch": True, "epoch/feed": True,
    "epoch/train": True, "epoch/eval": True, "epoch/log": True, "level/finish": True,
    "level/save": True, "ckpt/fetch": True, "ckpt/write": True, "ckpt/barrier": True,
    "ckpt/wait": False, "ckpt/read": RESUMED,
}  # fmt: skip


@pytest.fixture(scope="module")
def ladder(tmp_path_factory):
    import run_experiment
    from turboprune_tpu import driver

    tmp = tmp_path_factory.mktemp("traced")
    common = [
        "--config-name=cifar10_imp",
        f"experiment_params.base_dir={tmp / 'experiments'}",
        "experiment_params.num_devices=1",
        "experiment_params.epochs_per_level=2",
        "dataset_params.dataloader_type=synthetic",
        # One scanned step of batch 8 an epoch: the CPU runs ResNet18's
        # scanned epoch at seconds a step.
        "dataset_params.total_batch_size=8",
        "dataset_params.synthetic_num_train=8",
        "dataset_params.synthetic_num_test=8",
        # Two levels; keeping a tenth is a top_k the CPU makes in 5 s,
        # not 13.
        "pruning_params.prune_rate=0.9",
        "pruning_params.target_sparsity=0.9",
    ]
    ran = []
    real_run = driver.run

    def main(*more):
        """``run_experiment.main`` as the CLI calls it; (what it printed, the
        spans it recorded, what ``driver.run`` returned to it)."""
        out = io.StringIO()
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(driver, "run", lambda cfg: ran.append(real_run(cfg)) or ran[-1])
            with tracing.span("t/ladder") as whole, contextlib.redirect_stdout(out):
                assert run_experiment.main([*common, *more]) == 0
        return out.getvalue(), tracing.recorded(t0=whole.start, t1=whole.end), ran[-1]

    out, spans, (expt_dir, summaries) = main(f"experiment_params.profile_dir={tmp / 'profile'}")
    assert len(summaries) == 2
    timing = pd.read_csv(Path(expt_dir) / "metrics" / "level_timing.csv")

    # The same directory taken up at level 1 by a process that holds nothing,
    # under a session of the test's own: ``profile_dir`` starts none past
    # level 0, and the harness stops this one where level 1's set-up ends.
    tracing.start_profile(tmp / "profile_resumed" / "level1_resumed")
    try:
        resumed_out, resumed_spans, (_, summaries) = main(
            "experiment_params.resume_experiment=true",
            f"experiment_params.resume_experiment_stuff.resume_expt_name={Path(expt_dir).name}",
            "experiment_params.resume_experiment_stuff.resume_level=1",
        )
    finally:
        tracing.stop_profile()
    assert [s["level"] for s in summaries] == [1]
    return {
        "spans": spans, "out": out, "expt_dir": Path(expt_dir), "profile": tmp / "profile",
        "timing": timing, "resumed_spans": resumed_spans,
        "resumed_out": resumed_out, "profile_resumed": tmp / "profile_resumed",
    }  # fmt: skip


@pytest.mark.parametrize("name", sorted(LADDER_SPANS))
def test_the_ladder_records_every_span_of_the_table(ladder, name):
    in_level_zero = LADDER_SPANS[name]
    found = [s for s in ladder["spans"] if s.name == name]
    if in_level_zero == RESUMED:
        assert not found, name  # the continuous run read nothing back
        found = [s for s in ladder["resumed_spans"] if s.name == name]
    assert found, name
    if name in ("ckpt/wait", "ckpt/barrier"):
        at_exit = [s for s in found if "level" not in s.attrs]  # driver.run's last wait()
        assert len(at_exit) == 1
        found.remove(at_exit[0])
    if in_level_zero is None:
        assert all("level" not in s.attrs for s in found)
    else:
        want = {0, 1} if in_level_zero is True else {1}
        assert {s.attrs["level"] for s in found} == want
    if name.startswith("epoch"):
        assert {s.attrs["epoch"] for s in found} == {0, 1}
    assert not any("error" in s.attrs for s in found)


def test_every_recorded_name_is_one_the_time_line_knows(ladder):
    known = set(LADDER_SPANS) | {"t/ladder"}
    assert {s.name for s in ladder["spans"] + ladder["resumed_spans"]} <= known
    terms_or_inside = set(tracing.TERMS) | {n for n in known if n.startswith(("ckpt/", "init/"))}
    containers = {"level", "level/train", "epoch", "t/ladder"}
    assert known - terms_or_inside == containers
    assert {n for n in known if n.startswith("setup/")} == {n for n in tracing.TERMS if n.startswith("setup/")}


def test_level_self_times_sum_to_the_level_and_land_in_the_csv(ladder):
    levels = [s for s in ladder["spans"] if s.name == "level"]
    assert [s.attrs["level"] for s in levels] == [0, 1]
    rows = ladder["timing"]
    assert list(rows.columns) == tracing.TIMING_COLUMNS and list(rows["level"]) == [0, 1]
    for level, (_, row) in zip(levels, rows.iterrows()):
        b = tracing.breakdown([level], ladder["spans"])
        assert sum(b["terms"].values()) + b["other_s"] == pytest.approx(level.seconds)
        assert 0 <= b["other_s"] < level.seconds
        assert row["total_s"] == pytest.approx(level.seconds)
        assert row["train_s"] == pytest.approx(b["terms"]["epoch/train"])
        parts = [c for c in rows.columns if c.endswith("_s") and not c.startswith(("total", "ckpt_", "compile", "trace", "lower", "cache_", "miss_"))]
        assert row[parts].sum() + row["ckpt_s"] == pytest.approx(row["total_s"])
    assert rows["rewind_s"][0] == 0 and rows["rewind_s"][1] > 0
    # A level of a continuous run reads nothing: the columns stay and say 0.
    assert (rows["load_s"] == 0).all() and (rows["ckpt_read_s"] == 0).all()
    assert rows["ckpt_write_s"].min() > 0
    # The stall on the loop beside the work off it: level 0 had no write to
    # wait for; level 1's row holds its wait and level 0's write, whole.
    (waited,) = [s for s in ladder["spans"] if s.name == "ckpt/wait" and "level" in s.attrs]
    (wrote,) = [s for s in ladder["spans"] if s.name == "ckpt/write" and s.attrs["level"] == 0 and s.thread != waited.thread]
    assert rows["ckpt_wait_s"][0] == 0 and rows["ckpt_wait_s"][1] == pytest.approx(waited.seconds)
    assert rows["ckpt_write_s"][1] == pytest.approx(wrote.seconds)
    # Level 0 traces, lowers and compiles the epoch; level 1 reuses it and
    # compiles its prune. No persistent cache in the tests: nothing is read
    # from one and nothing missed.
    assert rows["compiles"][0] > 0 and rows["trace_s"][0] > 0 and rows["lower_s"][0] > 0
    assert (rows["trace_s"] + rows["lower_s"] + rows["compile_s"] <= rows["total_s"]).all()
    assert not rows[["cache_hits", "cache_misses", "cache_read_s", "miss_compile_s"]].any().any()


def test_a_resumed_level_says_what_it_read_in_its_row_and_its_spans(ladder):
    rows = pd.read_csv(ladder["expt_dir"] / "metrics" / "level_timing.csv")
    assert list(rows["level"]) == [0, 1, 1]  # the resumed level's row follows
    row = rows.iloc[2]
    assert row["load_s"] > 0 and row["rewind_s"] > 0
    (load,) = [s for s in ladder["resumed_spans"] if s.name == "level/load"]
    (rewind,) = [s for s in ladder["resumed_spans"] if s.name == "level/rewind"]
    reads = [s for s in ladder["resumed_spans"] if s.name == "ckpt/read"]
    assert sorted(s.parent for s in reads) == sorted([load.id, rewind.id])
    assert row["ckpt_read_s"] == pytest.approx(sum(s.seconds for s in reads))
    assert row["ckpt_read_s"] < row["load_s"] + row["rewind_s"]
    # Where the rewind target came from: the writer's memory, or the disk.
    (continuous,) = [s for s in ladder["spans"] if s.name == "level/rewind"]
    assert (continuous.attrs["source"], rewind.attrs["source"]) == ("resident", "disk")


@pytest.mark.parametrize(
    "out, titles, load",
    [
        ("out", ["[time] set-up", "[time] start to first epoch", "[time] level 0", "[time] level 1"], False),
        ("resumed_out", ["[time] set-up", "[time] start to first epoch", "[time] level 1"], True),
    ],
)
def test_the_operator_gets_a_time_line_per_level_and_one_for_setup(ladder, out, titles, load):
    lines = [ln for ln in ladder[out].splitlines() if ln.startswith("[time] ")]
    assert [ln.split(":")[0] for ln in lines] == titles
    assert "init " in lines[0] and "loaders " in lines[0] and "state " in lines[0]
    for want in ("imports ", "config ", "distributed ", "backend "):
        assert want in lines[0], want
    for want in ("prune ", "rewind ", "setup ", "train ", "eval ", "log ", "save ", "other "):
        assert want in lines[-1], want
    tail = r"; traced \d+\.\d s, lowered \d+\.\d s, compiled \d+ modules in \d+\.\d s \(0 read from the cache in 0\.0 s; 0 missed\)"
    # The first epoch's line goes on with what the process's programs set while they were
    # traced (tracing.trace_gauges(): this process's, so other tests' too), "; name value" each.
    assert all(re.search(tail + (r"(; \S+ \S+)*$" if i == 1 else "$"), ln) for i, ln in enumerate(lines)), lines
    # Only a level that restored says so: "load 0.20 (read 0.19)", and its
    # rewind "(read ...)" too.
    assert ("load " in lines[-1]) == load and (lines[-1].count("(read ") == 2) == load
    assert (lines[-1].count("(read ") == 0) != load
    level0 = lines[2]
    if not load:
        assert "prune " not in level0
    # A level's save waits for the write of the level before and says so, and
    # the level says which write ended behind it. The first level a process
    # saves has neither: level 0, and a resumed process's level 1.
    behind = not load
    assert ("save " in lines[-1] and "(wait " in lines[-1]) == behind
    assert ("; wrote level 0 behind, " in lines[-1]) == behind
    assert "wait " not in level0 and "behind" not in level0


def _terms(line: str) -> tuple[float, dict]:
    """(total, {term: seconds}) of a ``[time]`` line, parentheses left out."""
    total, terms = re.match(r"\[time\] [^:]+: ([\d.]+) s = ([^;]+)", line).groups()
    named = [part.split() for part in re.sub(r" \([^)]*\)", "", terms).split(" + ")]
    return float(total), {name: float(sec) for name, sec in named}


@pytest.mark.parametrize("out, level", [("out", 0), ("resumed_out", 1)])
def test_the_first_epoch_of_a_process_gets_its_line_once(ladder, out, level):
    lines = ladder[out].splitlines()
    said = [i for i, ln in enumerate(lines) if ln.startswith("[time] start to first epoch:")]
    assert len(said) == 1  # four epochs ran, and two in the resumed process
    # After the first epoch's own console line and before the second's.
    epochs = [i for i, ln in enumerate(lines) if ln.startswith(f"[L {level} E ")]
    assert epochs[0] < said[0] < epochs[1]
    total, terms = _terms(lines[said[0]])
    want = ["imports", "config", "distributed", "backend", "init", "setup", "feed", "train", "eval", "log", "other"]
    assert list(terms) == want
    assert sum(terms.values()) == pytest.approx(total, abs=0.005 * (len(terms) + 1))
    # Its roots: this process's set-up, and the first level/setup and epoch
    # it ran, at whatever level; the line's total is theirs.
    spans = ladder["spans" if level == 0 else "resumed_spans"]
    first = {n: next(s for s in spans if s.name == n) for n in ("harness/init", "level/setup", "epoch")}
    assert first["level/setup"].attrs["level"] == first["epoch"].attrs["level"] == level
    roots = [s for s in spans if s.name.startswith("setup/")] + list(first.values())
    assert sum(s.seconds for s in roots) == pytest.approx(total, abs=0.006)
    assert terms["train"] == pytest.approx(next(s for s in spans if s.name == "epoch/train").seconds, abs=0.006)
    # A fresh level 0 writes model_init and optimizer_init in its set-up.
    assert ("write " in lines[said[0]]) == (level == 0)


class _Dies(Exception):
    pass


class _DyingHarness:
    """What ``driver.run`` needs of a harness, with a level 0 that sets up,
    trains part of an epoch and raises."""

    data_gauges: dict = {}

    def __init__(self, cfg, expt_dir):
        with tracing.span("harness/init"):
            self.rows = []
            self.metrics = SimpleNamespace(log_level_timing=self.rows.append)
            self.ckpts = SimpleNamespace(wait=lambda: None)
        type(self).last = self

    def train_one_level(self, epochs, level):
        with tracing.span("level/setup"):
            x = jnp.ones(17)
        with tracing.span("epoch", epoch=0):
            with tracing.span("epoch/train"):
                jax.jit(lambda x: x * 17 + level)(x).block_until_ready()
                raise _Dies()


def test_a_level_that_never_ends_says_where_it_got_to_and_writes_no_row(tmp_path, capsys):
    from turboprune_tpu.config.compose import compose
    from turboprune_tpu.driver import run

    cfg = compose("cifar10_imp", overrides=[f"experiment_params.base_dir={tmp_path}"])
    with pytest.raises(_Dies):
        run(cfg, harness_cls=_DyingHarness)
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("[time] ")]
    assert [ln.split(":")[0] for ln in lines] == ["[time] set-up", "[time] level 0 (unfinished)"]
    total, terms = _terms(lines[1])
    assert list(terms) == ["setup", "train", "other"] and terms["train"] > 0
    assert sum(terms.values()) == pytest.approx(total, abs=0.02)
    assert "compiled 2 modules" in lines[1]  # the set-up's array, the epoch's program
    assert _DyingHarness.last.rows == []
    level = tracing.recorded("level")[-1]
    assert level.attrs["error"] == "_Dies" and total == pytest.approx(level.seconds, abs=0.006)


def _host_span_names(session: Path) -> set[str]:
    from jax.profiler import ProfileData

    (xplane,) = session.glob("plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(str(xplane))
    return {
        ev.name
        for plane in data.planes
        if plane.name.startswith("/host:")
        for ln in plane.lines
        for ev in ln.events
        if ev.name.startswith(tracing.PREFIX)
    }


@pytest.mark.parametrize(
    "where, session, holds, lacks",
    [
        ("profile", "level0_epoch1", {"tp/epoch", "tp/epoch/train", "tp/epoch/eval", "tp/epoch/log"}, {"tp/level/rewind"}),
        ("profile", "level0_to_1", {"tp/level/save", "tp/level/prune", "tp/level/rewind", "tp/level/setup"}, {"tp/epoch/train", "tp/level/load", "tp/ckpt/read"}),
        # The boundary as a resumed process crosses it: the session is the test's.
        ("profile_resumed", "level1_resumed", {"tp/level/load", "tp/level/prune", "tp/level/rewind", "tp/level/setup", "tp/ckpt/read"}, {"tp/epoch/train", "tp/level/save"}),
    ],
)  # fmt: skip
def test_profile_dir_leaves_two_sessions_that_hold_the_spans(ladder, where, session, holds, lacks):
    assert sorted(p.name for p in ladder["profile"].iterdir()) == ["level0_epoch1", "level0_to_1"]
    names = _host_span_names(ladder[where] / session)
    assert holds <= names and not (lacks & names)
