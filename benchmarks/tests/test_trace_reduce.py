"""The interval arithmetic of trace_reduce.py on hand-made intervals."""

import pytest

from benchmarks import trace_reduce as tr


def test_union_merges_overlap_touch_and_nesting():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5), (6, 6), (1.2, 1.3)]) == [
        (0, 2.5),
        (3, 4),
    ]
    assert tr.total(tr.union([(0, 10), (2, 3), (9, 12)])) == 12


def test_gaps_are_what_busy_leaves_of_the_window():
    busy = tr.union([(1, 2), (4, 5), (9, 12)])
    assert tr.gaps(busy, 0, 10) == [(0, 1), (2, 4), (5, 9)]
    assert tr.gaps(busy, 1.5, 4.5) == [(2, 4)]
    assert tr.gaps([], 0, 3) == [(0, 3)]
    assert tr.total(tr.gaps(busy, 0, 10)) + tr.total(tr.clip(busy, 0, 10)) == 10


def test_a_gap_is_labelled_by_the_innermost_span_that_covers_most_of_it():
    spans = [
        ("train_one_level", 0.0, 10.0),
        ("evaluate", 2.0, 4.0),
        ("save_level", 10.5, 11.0),
    ]
    assert tr.label_gap((2.5, 3.5), spans) == "evaluate"  # both cover it: the shorter
    assert tr.label_gap((3.5, 6.0), spans) == "train_one_level"  # covers more of it
    assert tr.label_gap((10.6, 10.9), spans) == "save_level"
    assert tr.label_gap((20, 21), spans) == tr.UNLABELLED


def test_idle_by_label_adds_up_to_the_idle_time():
    busy = tr.union([(0, 2), (4, 10)])
    spans = [("evaluate", 2.0, 3.5), ("log", 3.5, 4.0), ("prune_level", 10.0, 12.0)]
    out = tr.idle_by_label(busy, spans, 0, 13)
    # A gap is labelled once, whole: (2, 4) by evaluate, (10, 13) by prune_level.
    assert out == [("prune_level", 3.0), ("evaluate", 2.0)]
    assert tr.idle_by_label(busy, [], 0, 13) == [(tr.UNLABELLED, 5.0)]


def test_self_seconds_charges_a_while_only_what_its_body_leaves():
    events = [
        ("while", 0.0, 10.0),
        ("fusion.1", 1.0, 4.0),
        ("fusion.2", 4.0, 9.0),
        ("copy", 10.0, 11.0),
        ("fusion.1", 12.0, 13.0),
    ]
    own = tr.self_seconds(events)
    assert own == pytest.approx({"while": 2.0, "fusion.1": 4.0, "fusion.2": 5.0, "copy": 1.0})
    assert sum(own.values()) == pytest.approx(tr.total(tr.union((s, e) for _, s, e in events)))


def test_op_kind_is_the_operations_name_without_its_number():
    hlo = "%convert_reduce_fusion.304 = (f32[256]{0:T(256)S(1)}) fusion(f32[256] %copy-done.429), kind=kOutput"
    assert tr.op_kind(hlo) == "convert_reduce_fusion"
    assert tr.op_kind("%while = (s32[]) while(...)") == "while"
    assert tr.op_kind("%dynamic-update-slice.3 = f32[2048,224,224,3] dynamic-update-slice(...)") == "dynamic-update-slice"
    assert tr.op_kind("fusion.12") == "fusion" and tr.op_kind("copy") == "copy"
