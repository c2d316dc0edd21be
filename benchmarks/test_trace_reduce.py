"""Run by hand: `python -m pytest benchmarks/test_trace_reduce.py -q`
(outside tests/, so tier-1 does not collect it). The trace reduction and
iter_mfu's tree walk on inputs small enough to check by eye."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import trace_reduce as tr  # noqa: E402
import work  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_and_busy():
    iv = [(0, 10), (5, 20), (30, 40), (40, 45), (100, 100)]
    assert tr.union_intervals(iv) == [(0, 20), (30, 45)]
    assert tr.busy_seconds(iv) == pytest.approx(35e-9)
    # clipped to a window that cuts both runs
    assert tr.busy_seconds(iv, 10, 35) == pytest.approx(15e-9)


def test_self_times_charge_children_once():
    # a while [0, 100) holds two fusions and a gap; one op stands alone
    events = [("while", 0, 100), ("fusion.1", 10, 40), ("fusion.2", 40, 70),
              ("copy", 120, 130), ("fusion.1", 130, 150)]
    got = tr.self_times(events)
    assert got["while"] == 40          # 100 - 30 - 30
    assert got["fusion.1"] == 30 + 20
    assert got["fusion.2"] == 30
    assert got["copy"] == 10
    assert sum(got.values()) == 100 + 10 + 20   # equals the union


def test_idle_gaps_are_charged_to_the_innermost_span():
    busy = [(10, 20), (50, 60)]
    spans = [("bench/update", 0, 45), ("bench/drain", 45, 100),
             ("bench/inner", 25, 35)]
    gaps = tr.idle_gaps(busy, 0, 100, spans)
    # [0,10) mid 5 -> update; [20,50) mid 35 -> update (inner ends at 35);
    # [60,100) mid 80 -> drain
    assert gaps == {"bench/update": 10 + 30, "bench/drain": 40}
    assert sum(gaps.values()) == 100 - 20
    assert tr.idle_gaps(busy, 0, 100, []) == {"(no harness span)": 80}


def five_leaf_tree():
    #            n0 (100)
    #        n1 (60)      leaf1 (40)
    #   leaf0 (10)   n2 (50)
    #            n3 (30)   leaf3 (20)
    #        leaf2 (18) leaf4 (12)
    left = [1, -1, 3, -3]
    right = [-2, 2, -4, -5]
    internal = [100, 60, 50, 30]
    leaves = [10, 40, 18, 20, 12]
    return left, right, internal, leaves


def test_rows_to_histogram_five_leaves():
    left, right, internal, leaves = five_leaf_tree()
    # root 100 + min(60,40) + min(10,50) + min(30,20) + min(18,12)
    assert work.rows_to_histogram(left, right, internal, leaves) == \
        100 + 40 + 10 + 20 + 12
    assert work.rows_to_histogram([], [], [], [77]) == 77


def test_least_seconds_takes_the_larger_bound():
    peaks = {"bytes_per_s": 100.0, "flops_per_s": 1000.0}
    got = work.least_seconds(10, 4, peaks)
    assert got["bytes"] == 10 * (4 + 8) and got["ops"] == 10 * 4 * 3
    assert got["seconds"] == pytest.approx(1.2) and got["bound"] == "bytes"
    got = work.least_seconds(10, 4, {"bytes_per_s": 1e6, "flops_per_s": 10.0})
    assert got["seconds"] == pytest.approx(12.0) and got["bound"] == "ops"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.load_peaks("TPU v9 imaginary")
    assert work.load_peaks("TPU v5 lite")["bytes_per_s"] == 819e9


def test_iter_mfu_is_taken_over_the_traced_span():
    import datagen
    reader = datagen.load_file_module(
        os.path.join(HERE, "layer_metrics", "iter_mfu.py"), "iter_mfu_test")
    left, right, internal, leaves = five_leaf_tree()
    tree = {"left_child": left, "right_child": right,
            "internal_count": internal, "leaf_count": leaves}
    peaks = {"bytes_per_s": 100.0, "flops_per_s": 1e9}
    ctx = {"trees_window": [tree] * 4, "traced_trees": [1, 3], "features": 4,
           "traced_host_s": 50.0, "window_s": 1e9, "peaks": peaks}
    rows = work.rows_to_histogram(left, right, internal, leaves)
    # two traced trees, 12 bytes a row, over the traced 50 s (not window_s)
    assert reader.read(ctx) == pytest.approx(
        100.0 * 2 * rows * 12 / 100.0 / 50.0)
    assert reader.read(dict(ctx, traced_trees=None)) is None
