"""The trace reduction: interval arithmetic, and the whole reduction on
a small trace recorded on a TPU v5e chip (``data/``)."""
import glob
import os

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_gaps():
    busy = trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 12)])
    assert busy == [(0, 3), (5, 8), (10, 12)]
    assert trace.gaps(busy, 1, 11) == [(3, 5), (8, 10)]
    assert trace.gaps([], 0, 4) == [(0, 4)]


def test_label_takes_the_span_covering_most_of_a_gap():
    spans = [("plan", 0, 4), ("launch", 3, 20)]
    assert trace._label((2, 10), spans) == "launch"
    assert trace._label((30, 40), spans) == "unattributed"


@pytest.mark.skipif(not glob.glob(os.path.join(DATA, "*.xplane.pb")),
                    reason="no recorded trace")
def test_reduction_of_a_recorded_chip_trace():
    s = trace.reduce(DATA, "window")
    assert 0 < s.busy_s < s.window_s
    assert s.ops and all(t > 0 for _, t in s.ops)
    assert sum(t for _, t in s.ops) >= s.busy_s * 0.99
    assert s.idle and s.idle[0][1] > 0
    labels = {name for name, _ in s.idle}
    assert "gap" in labels
    assert s.module_times("jit_")
