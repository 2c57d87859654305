"""The trace reduction: busy time as the union of operation intervals,
idle gaps labelled by the host span open over them, the busiest
operations."""
import json
from pathlib import Path

import pytest

import trace_reduce

# 0.4 s of a traced window of 8 tenants' Q1/Q6 queries on a resident
# service on a TPU v5 lite: the device's
# XLA Ops events, the bench:align mark, the host spans, and the busy time
# and top operations worked out when it was recorded by another method
# (a running maximum over the sorted intervals)
RECORDED = Path(__file__).parent / "data" / "trace_slice.json"


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        [0, 3], [5, 8]]


def test_busy_idle_and_labels_on_a_hand_made_trace():
    # profiler clock = monotonic clock + 1000
    events = {"marks": {"bench:align": [(1000 + 50, 1)]},
              "device": {"/device:TPU:0": [
                  ("fusion.1", 1100, 100),    # 100-200 (monotonic)
                  ("fusion.1", 1150, 100),    # overlaps: 150-250
                  ("scatter.2", 1600, 200),   # 600-800
                  ("scatter.2", 1950, 100)]}}  # 950-1050, clipped at 1000
    spans = [(0, 1000, "query", 0), (300, 500, "op3:AGG", 2),
             (250, 700, "execute", 1)]
    red = trace_reduce.reduce(events, align_mono=50, window=(0, 1000),
                              spans=spans)
    assert red["window_ns"] == 1000
    assert red["busy_ns"] == 150 + 200 + 50
    assert red["device_ops"] == [["scatter.2", 250 / 1e9],
                                 ["fusion.1", 200 / 1e9]]
    gaps = dict(red["idle_gaps"])
    # 0-100 and 250-600 and 800-950 idle; midpoints 50, 425, 875
    assert gaps == {"query": (100 + 150) / 1e9, "op3:AGG": 350 / 1e9}
    assert red["busy"] == {"/device:TPU:0": [[100, 250], [600, 800],
                                             [950, 1000]]}
    # device time inside the AGG span (300-500): none; inside execute
    # (250-700): 600-700
    assert trace_reduce.overlap(red["busy"]["/device:TPU:0"],
                                [[300, 500]]) == 0
    assert trace_reduce.overlap(red["busy"]["/device:TPU:0"],
                                [[250, 700]]) == 100


def test_overlap_of_interval_lists():
    a = [[0, 10], [20, 30], [40, 50]]
    assert trace_reduce.overlap(a, [[5, 25], [29, 45]]) == 5 + 5 + 1 + 5
    assert trace_reduce.overlap(a, []) == 0
    assert trace_reduce.overlap(a, [[-5, 100]]) == 30


def test_no_device_events_reads_nothing():
    events = {"marks": {"bench:align": [(0, 1)]}, "device": {}}
    assert trace_reduce.reduce(events, 0, (0, 10), []) is None


def test_recorded_chip_trace():
    rec = json.loads(RECORDED.read_text())
    red = trace_reduce.reduce(rec["events"], rec["align_mono"],
                              tuple(rec["window"]),
                              [tuple(s) for s in rec["spans"]])
    assert 0 < red["busy_ns"] <= red["window_ns"]
    assert red["busy_ns"] == pytest.approx(rec["expect"]["busy_ns"])
    assert [n for n, _ in red["device_ops"]] == rec["expect"]["top_ops"]
    labels = dict(red["idle_gaps"])
    assert set(labels) <= {s[2] for s in rec["spans"]} | {"client"}
    idle = sum(labels.values())
    assert idle == pytest.approx((red["window_ns"] - red["busy_ns"]) / 1e9)
