"""The trace reduction's arithmetic on a hand-made event list.

    python -m pytest benchmarks/tests -q        (CPU, seconds)
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib.trace_reduce import Trace, merge, subtract, total  # noqa: E402

# one device, window 0..10 s.  Operations (start, end, name):
OPS = [
    (1.0, 2.0, "fusion.1"),
    (1.5, 2.5, "fusion.2"),        # overlaps fusion.1: union 1.0..2.5
    (4.0, 6.0, "while.3"),         # a loop ...
    (4.5, 5.0, "custom-call.4"),   # ... and its body, inside it
    (7.0, 7.5, "all-reduce.5"),    # a collective alone on the device
    (8.0, 9.0, "all-reduce.6"),    # a collective half hidden ...
    (8.5, 9.5, "fusion.7"),        # ... by this
]
SPANS = [
    (0.5, 3.0, "table_convert"),
    (3.0, 6.5, "lloyd_loop"),
    (6.5, 6.8, "covariance"),
    (6.9, 9.25, "eigh"),
]


@pytest.fixture
def tr():
    return Trace({"/device:TPU:0": OPS}, SPANS, window=(0.0, 10.0))


def test_merge_subtract_total():
    assert merge([(3, 4), (1, 2), (1.5, 2.5), (5, 5)]) == [(1, 2.5), (3, 4)]
    assert subtract([(0, 10)], [(1, 2.5), (4, 6)]) == [(0, 1), (2.5, 4), (6, 10)]
    assert total([(0, 1), (2.5, 4)]) == pytest.approx(2.5)


def test_busy_and_idle(tr):
    dev = tr.busiest()
    # 1.0-2.5, 4-6, 7-7.5, 8-9.5
    assert tr.busy_s(dev) == pytest.approx(1.5 + 2.0 + 0.5 + 1.5)
    assert tr.mean_busy_s() == pytest.approx(5.5)
    assert tr.window_s == pytest.approx(10.0)
    assert tr.busy_s(dev, 2.0, 5.0) == pytest.approx(0.5 + 1.0)


def test_busy_inside_annotation(tr):
    busy, spanned = tr.busy_inside(tr.busiest(), ["lloyd_loop"])
    assert (busy, spanned) == (pytest.approx(2.0), pytest.approx(3.5))
    busy, spanned = tr.busy_inside(tr.busiest(), ["no_such_phase"])
    assert (busy, spanned) == (0, 0)


def test_busy_between_two_annotations(tr):
    # covariance starts 6.5, the eigh that follows ends 9.25:
    # busy 7-7.5 and 8-9.25
    busy, spanned = tr.busy_between(tr.busiest(), "covariance", "eigh")
    assert busy == pytest.approx(0.5 + 1.25)
    assert spanned == pytest.approx(2.75)


def test_exposed_collective_time(tr):
    exposed = tr.exposed_s(tr.busiest(), lambda n: n.startswith("all-reduce"))
    # all-reduce.5 wholly (0.5), all-reduce.6 until fusion.7 starts (0.5)
    assert exposed == pytest.approx(1.0)


def test_idle_gaps_by_phase(tr):
    gaps = dict(tr.idle_gaps(tr.busiest(), ["table_convert", "lloyd_loop",
                                            "covariance", "eigh"]))
    # idle: 0-1 (0.5 outside, 0.5 table_convert), 2.5-4 (0.5 table_convert,
    # 1.0 lloyd_loop), 6-7 (0.5 lloyd_loop, 0.3 covariance, 0.1 outside,
    # 0.1 eigh), 7.5-8 eigh, 9.5-10 outside
    assert gaps["table_convert"] == pytest.approx(1.0)
    assert gaps["lloyd_loop"] == pytest.approx(1.5)
    assert gaps["covariance"] == pytest.approx(0.3)
    assert gaps["eigh"] == pytest.approx(0.6)
    assert gaps["outside_fit"] == pytest.approx(0.5 + 0.1 + 0.5)
    assert sum(gaps.values()) == pytest.approx(10.0 - 5.5)


def test_top_ops(tr):
    top = tr.top_ops(2)
    assert top[0] == ["while.3", pytest.approx(2.0)]
    assert len(top) == 2


def test_window_clips(tr):
    short = Trace({"/device:TPU:0": OPS}, SPANS, window=(1.5, 4.5))
    assert short.busy_s(short.busiest()) == pytest.approx(1.0 + 0.5)
    busy, spanned = short.busy_inside(short.busiest(), ["lloyd_loop"])
    assert (busy, spanned) == (pytest.approx(0.5), pytest.approx(1.5))
