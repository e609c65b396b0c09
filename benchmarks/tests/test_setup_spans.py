"""The six readers under ``setup_s`` (PR 36) on a hand-made recorder: what
each takes of a run's set-up, that the four stretches and the named spans add
up to ``setup_s``, that another run's spans in the same process are left
alone, and what a program without the fields, the record or the recorder
gives. CPU, no device, no number of a chip."""

import sys
from collections import deque
from types import SimpleNamespace

import pytest

from benchmarks import registry

NAMES = (
    "setup_trace_s", "setup_cache_miss_s", "init_ckpt_s",
    "first_epoch_s", "setup_warm_units_s", "setup_unnamed_s",
)  # fmt: skip
MAIN, WRITER = 1, 2
# The run under test: its process started at 100, its window opened at 200.
OBS = {"window": (200.0, 230.0), "setup_s": 100.0}


def _span(tracing, name, start, end, parent=None, thread=MAIN, **charged):
    s = tracing.Span(name, {})
    s.start, s.end, s.thread, s.parent = start, end, thread, parent.id if parent else None
    for key, value in charged.items():
        setattr(s, key, value)
    return s


@pytest.fixture
def recorder(monkeypatch):
    """A dense-level run's set-up as the program records it, after an earlier
    run of the same process (spans and modules before 100)."""
    from turboprune_tpu.utils import tracing

    mk = lambda *a, **kw: _span(tracing, *a, **kw)  # noqa: E731
    earlier = [mk("harness/init", 10.0, 20.0), mk("epoch", 30.0, 40.0, trace_s=9.0)]
    imports = mk("setup/imports", 108.0, 111.0)
    backend = mk("setup/backend", 111.0, 111.5)
    init = mk("harness/init", 112.0, 118.0, trace_s=0.5)
    level = mk("level", 119.0, 260.0)  # closed by the window's end
    setup = mk("level/setup", 119.0, 140.0, level)
    fetch = mk("ckpt/fetch", 120.0, 123.0, setup)
    write = mk("ckpt/write", 123.0, 138.0, setup)
    barrier = mk("ckpt/barrier", 138.0, 138.5, setup)
    behind = mk("ckpt/write", 150.0, 155.0, thread=WRITER)  # no child of the set-up
    train = mk("level/train", 140.0, 260.0, level)
    first = mk("epoch", 140.0, 170.0, train)
    first_train = mk("epoch/train", 141.0, 168.0, first, trace_s=12.0)
    second = mk("epoch", 170.0, 185.0, train, trace_s=0.25)
    warm = mk("level/save", 185.0, 201.0, train, trace_s=1.0)  # open when the window opens
    inside = mk("epoch", 205.0, 210.0, train, trace_s=64.0)  # began inside the window
    spans = [
        *earlier, imports, backend, init, fetch, write, barrier, setup, behind,
        first_train, first, second, warm, inside, train, level,
    ]  # fmt: skip
    monkeypatch.setattr(tracing, "_spans", deque(spans))
    module = lambda name, when, sec, cache: tracing.Module(name, when, first_train.id, "epoch/train", 0.5, sec, cache)  # noqa: E731
    record = [
        module("jit(earlier)", 35.0, 50.0, "miss"),
        module("jit(scan_chunk)", 160.0, 34.5, "miss"),
        module("jit(scan_eval)", 165.0, 3.0, "miss"),
        module("jit(read)", 166.0, 2.0, "hit"),
        module("jit(small)", 167.0, 0.25, "none"),
        module("jit(in_window)", 215.0, 7.0, "miss"),
    ]
    monkeypatch.setattr(tracing, "_modules", deque(record))
    return tracing


def _read(name, obs=OBS):
    return registry.load_metric(name).read(obs)


@pytest.mark.parametrize(
    "name, want",
    [
        ("setup_trace_s", 0.5 + 12.0 + 0.25 + 1.0),  # not the earlier run's 9, not the window's 64
        ("setup_cache_miss_s", 34.5 + 3.0),  # not the hit, the small one, the earlier run's or the window's
        ("init_ckpt_s", 3.0 + 15.0 + 0.5),  # not the write behind a later level
        ("first_epoch_s", 30.0),
        ("setup_warm_units_s", 30.0),  # 170 -> 200: the second epoch and the job's warm-up
        ("setup_unnamed_s", 8.0 + 0.5 + 1.0),  # before the imports, and between two spans twice
    ],
)
def test_a_reader_takes_its_part_of_this_runs_setup(recorder, name, want):
    assert _read(name) == pytest.approx(want)


def test_the_stretches_and_the_named_spans_add_up_to_setup_s(recorder):
    named = sum(s.seconds for s in recorder.recorded(t0=100.0) if s.name.startswith("setup/"))
    harness_init = _read("harness_init_s")
    (level_setup,) = recorder.recorded("level/setup", 100.0)
    total = (
        _read("setup_unnamed_s") + named + harness_init + level_setup.seconds
        + _read("first_epoch_s") + _read("setup_warm_units_s")
    )  # fmt: skip
    assert (named, harness_init, level_setup.seconds) == (3.5, 6.0, 21.0)
    assert total == pytest.approx(OBS["setup_s"])
    assert _read("init_ckpt_s") <= level_setup.seconds


def test_every_new_metric_is_declared_under_setup_s_for_every_cell():
    from benchmarks.tests import tiny

    declared = {m["name"]: m for m in tiny.REAL["per_layer"]}
    assert [m["name"] for m in tiny.REAL["per_layer"]][-6:] == list(NAMES)
    for name in NAMES:
        m = declared[name]
        assert (m["moves"], m["layer"], m["unit"], m["better"]) == ("setup_s", "entry and compile cache", "s", "lower")
        assert "workloads" not in m
        assert m["source"] == ("program_counter" if name in NAMES[:2] else "program_span")


def test_a_resumed_runs_first_setup_wrote_nothing(recorder, monkeypatch):
    kept = [s for s in recorder._spans if not (s.name.startswith("ckpt/") and s.thread == MAIN)]
    monkeypatch.setattr(recorder, "_spans", deque(kept))
    assert _read("init_ckpt_s") == 0.0


def test_a_run_that_never_trained_before_its_window_has_no_first_epoch(recorder, monkeypatch):
    kept = [s for s in recorder._spans if s.name not in ("epoch", "epoch/train", "level/setup")]
    monkeypatch.setattr(recorder, "_spans", deque(kept))
    got = {name: _read(name) for name in NAMES}
    assert got["first_epoch_s"] is got["setup_warm_units_s"] is got["init_ckpt_s"] is None
    assert got["setup_unnamed_s"] == pytest.approx(9.5)


def test_a_program_whose_spans_carry_no_counts_gives_the_counters_nothing(recorder, monkeypatch):
    """The parent of PR 36: the spans are there, ``trace_s`` and the module
    record are not. The span readers still read."""
    bare = [
        SimpleNamespace(id=s.id, name=s.name, start=s.start, end=s.end, seconds=s.seconds,
                        parent=s.parent, thread=s.thread, attrs=s.attrs)
        for s in recorder._spans
    ]  # fmt: skip
    monkeypatch.setattr(recorder, "_spans", deque(bare))
    monkeypatch.delattr(recorder, "modules")
    assert _read("setup_trace_s") is None and _read("setup_cache_miss_s") is None
    assert _read("first_epoch_s") == 30.0 and _read("init_ckpt_s") == pytest.approx(18.5)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_recorder_gives_nothing_to_read(name, monkeypatch):
    monkeypatch.setitem(sys.modules, "turboprune_tpu.utils.tracing", None)
    assert _read(name) is None
