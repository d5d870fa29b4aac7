"""The reduction from a trace to metrics, on hand-built traces with known
answers and on a small trace recorded on the chip (``testdata/``)."""

import glob
import os

import pytest

import trace_reduce as TR

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# two overlapping ops, a gap, one op; host: a dispatch annotation holding
# the first gap, a wait annotation holding the second
HAND = {
    "devices": {"/device:TPU:0": [
        ["%fusion.1 = f32[8]{0} fusion()", 100, 300],
        ["custom-call.7", 200, 400], ["fusion.2", 600, 700]]},
    "host": [["bench.train.dispatch", 0, 500],
             ["bench.train.wait", 500, 1000],
             ["bench.train.batch", 420, 480]],
    "window": [0, 1000],
}


def test_busy_is_a_union_not_a_sum():
    ev = HAND["devices"]["/device:TPU:0"]
    assert TR.union(ev) == [(100, 400), (600, 700)]
    assert TR.busy_ns(ev, HAND["window"]) == 400
    assert sum(e - s for _, s, e in ev) == 500


def test_clipped_to_the_window():
    ev = HAND["devices"]["/device:TPU:0"]
    assert TR.busy_ns(ev, (250, 650)) == 150 + 50


def test_kernel_time_grouped_by_trace_names():
    ev = HAND["devices"]["/device:TPU:0"]
    assert TR.by_name(ev, HAND["window"]) == {
        "fusion": [300, 2], "custom-call": [200, 1]}
    assert TR.matching(ev, HAND["window"], r"^custom-call") == (200, 1)


def test_container_ops_leave_the_grouping_not_the_union():
    ev = HAND["devices"]["/device:TPU:0"] + [
        ["%while.3 = (s32[]) while()", 90, 710]]
    assert "while" not in TR.by_name(ev, HAND["window"])
    assert TR.busy_ns(ev, HAND["window"]) == 620


def test_gaps_labelled_by_the_enclosing_annotation():
    ev = HAND["devices"]["/device:TPU:0"]
    assert TR.gaps(ev, HAND["window"]) == [(0, 100), (400, 600),
                                           (700, 1000)]
    got = TR.labelled_gaps(ev, HAND["host"], HAND["window"])
    # the gap 400..600 has its midpoint (500) in the innermost annotation
    # that starts latest: the wait annotation starts at 500
    assert got == [("bench.train.wait", 300), ("bench.train.wait", 200),
                   ("bench.train.dispatch", 100)]
    assert TR.label_at(HAND["host"], 450) == "bench.train.batch"


def test_summary_of_the_hand_trace():
    s = TR.summary(HAND)
    assert s["busy_s"] == 400e-9 and s["window_s"] == 1000e-9
    assert s["breakdown"]["device_ops"][0] == ["fusion", 300e-9]


RECORDED = sorted(glob.glob(os.path.join(CHIP, "testdata", "trace_*.json.gz")))


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_trace(path):
    flat = TR.load(path)
    window = flat["window"]
    for ev in flat["devices"].values():
        clipped = TR.clip(ev, window)
        busy = TR.busy_ns(ev, window)
        total = sum(e - s for _, s, e in clipped)
        assert 0 < busy <= min(total, window[1] - window[0])
        groups = TR.by_name(ev, window)
        leaves = sum(e - s for n, s, e in clipped
                     if TR.base_name(n) not in TR.CONTAINERS)
        assert sum(v[0] for v in groups.values()) == leaves < total
        assert "while" not in groups and "_qmatmul_fused" in groups
        idle = sum(ns for _, ns in TR.labelled_gaps(ev, flat["host"],
                                                    window))
        assert idle == window[1] - window[0] - busy
        names = {n for n, _, _ in flat["host"]}
        for label, _ in TR.labelled_gaps(ev, flat["host"], window):
            assert label in names or label.startswith("host:")
