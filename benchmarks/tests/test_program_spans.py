"""The six readers of the program's own spans (PR 24), on whole runs at tiny
size on the CPU: a ladder run, then a dense-shaped run in the same process.
None of the numbers is a device number."""

import sys
from statistics import median

import pytest

from benchmarks import observe, registry, run
from benchmarks.tests import tiny

SPAN_METRICS = {
    "rewind_s", "level_setup_ms", "ckpt_read_s", "ckpt_write_s", "epoch_log_ms", "harness_init_s",
}  # fmt: skip
LEVEL_ONLY = SPAN_METRICS - {"epoch_log_ms", "harness_init_s"}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """{cell: (result line, the benchmark's outside spans, window)}; the
    ladder first, so that its spans are in the recorder when the dense run's
    readers look."""
    kept = []

    class KeptSpans(observe.Spans):
        def __init__(self):
            super().__init__()
            kept.append(self)

    out = {}
    mp = pytest.MonkeyPatch()
    mp.setattr(observe, "Spans", KeptSpans)
    try:
        for cell in ("tiny-ladder", "tiny-dense"):
            root, bench = tiny.make_bench(tmp_path_factory.mktemp(cell))
            seen = {}
            line = run.run_cell(
                tiny.args(cell, seed=2**31 + 24, trace=1),
                platform="cpu",
                repo_root=root,
                bench_dir=bench,
                after=lambda result: seen.update(window=result["obs"]["window"]),
            )
            out[cell] = (line, kept[-1], seen["window"])
    finally:
        mp.undo()
    return out


def test_the_ladder_reports_all_six(runs):
    line, _, _ = runs["tiny-ladder"]
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items() if k in SPAN_METRICS}
    assert set(got) == SPAN_METRICS and all(v > 0 for v in got.values())
    assert line["metrics"]["level_setup_ms"]["unit"] == "ms"
    # The outside metrics they split are still there, read as before.
    assert {"prune_s", "ckpt_s", "epoch_gap_ms", "compile_s", "window_compiles"} <= set(line["metrics"])


def test_the_dense_shaped_run_reports_the_two_it_has(runs):
    line, _, _ = runs["tiny-dense"]
    assert line["correct"] is True
    assert SPAN_METRICS & set(line["metrics"]) == {"epoch_log_ms", "harness_init_s"}


def test_the_inside_spans_lie_within_the_outside_ones(runs):
    from turboprune_tpu.utils import tracing

    line, outside, (t0, t1) = runs["tiny-ladder"]
    rewinds = tracing.recorded("level/rewind", t0, t1)
    prunes = outside.named("prune_level", t0, t1)
    assert rewinds and len(rewinds) == len(prunes)
    for r in rewinds:
        assert any(p.start <= r.start and r.end <= p.end and p.meta["level"] == r.attrs["level"] for p in prunes)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["rewind_s"] < m["prune_s"]
    assert m["ckpt_write_s"] <= median(s.seconds for s in outside.named("save_level", t0, t1))
    assert m["ckpt_read_s"] + m["ckpt_write_s"] <= m["ckpt_s"] + m["prune_s"]


def test_a_run_reads_only_its_own_spans(runs):
    from turboprune_tpu.utils import tracing

    _, ladder_outside, ladder_window = runs["tiny-ladder"]
    line, outside, window = runs["tiny-dense"]
    assert ladder_window[1] < window[0]
    # The ladder's rewinds are still in the process-wide recorder ...
    assert tracing.recorded("level/rewind", *ladder_window)
    # ... and the dense run, whose window holds none, reported none of them.
    assert not LEVEL_ONLY & set(line["metrics"])
    # Of the two harness/init spans that closed before the dense window, the
    # reader took the dense run's own: the one inside its outside span.
    (mine,) = outside.named("harness_init")
    (theirs,) = ladder_outside.named("harness_init")
    inits = tracing.recorded("harness/init", t1=window[0])
    assert len(inits) >= 2
    value = line["metrics"]["harness_init_s"]["value"]
    assert value == inits[-1].seconds and mine.start <= inits[-1].start and inits[-1].end <= mine.end
    assert not (theirs.start <= inits[-1].start <= theirs.end)


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_a_program_without_the_recorder_gives_nothing_to_read(name, monkeypatch):
    """The parent commit has no utils/tracing.py: the readers return None and
    do not raise (the driver runs them over the parent's checkout too)."""
    monkeypatch.setitem(sys.modules, "turboprune_tpu.utils.tracing", None)
    obs = {"unit": "level", "window": (float("-inf"), float("inf")), "boundaries": []}
    assert registry.load_metric(name).read(obs) is None
