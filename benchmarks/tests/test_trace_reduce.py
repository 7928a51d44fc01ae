"""trace_reduce on a small trace whose numbers were worked by hand.

Two traced fits, [1000, 10000) and [11000, 15000): a window of 14000 ns (the
third fit lies outside the two units asked for). Device operations: a `while`
over [2000, 5000) that contains two kernel calls of 1000 ns (so 1000 ns of its
own), a fusion over [7000, 7500), a kernel call over [11500, 13500), a fusion
over [14200, 14500). Busy union: 3000 + 500 + 2000 + 300 = 5800 ns. Idle gaps:
[1000, 2000) and [5000, 7000) before the first fit's update ended at 6000
(midpoints 1500 and 6000: the second lies at the mark, so after it);
[7500, 11500) spans the end of fit 0 (midpoint 9500, after its update);
[13500, 14200) in fit 1 before its mark at 14000; [14500, 15000) after it.
"""

import json
import os

import pytest

from benchmarks import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(HERE, "small_trace.json")) as f:
        planes = json.load(f)
    return trace_reduce.reduce(planes, n_units=2)


def test_window_and_busy_union(reduced):
    assert reduced["devices"] == 1 and reduced["units"] == 2
    assert reduced["window_s"] == pytest.approx(14000e-9)
    assert reduced["busy_s"] == pytest.approx(5800e-9)
    assert 100 * (1 - reduced["busy_s"] / reduced["window_s"]) == pytest.approx(100 * 8200 / 14000)


def test_per_operation_self_time_and_counts(reduced):
    assert reduced["op_self_s"]["value_gradient_sums.2"] == pytest.approx(4000e-9)
    assert reduced["op_count"]["value_gradient_sums.2"] == 3
    assert reduced["op_self_s"]["while.1"] == pytest.approx(1000e-9)
    assert reduced["op_self_s"]["fusion"] == pytest.approx(800e-9)
    assert sum(reduced["op_self_s"].values()) == pytest.approx(reduced["busy_s"])


def test_idle_gaps_are_labelled_by_what_the_host_did(reduced):
    idle = reduced["idle_by_label_s"]
    assert idle["fit, up to end of update global"] == pytest.approx((1000 + 700) * 1e-9)
    assert idle["fit, after the last update (validation, evaluation)"] == pytest.approx(
        (2000 + 4000 + 500) * 1e-9
    )
    assert sum(idle.values()) == pytest.approx(reduced["window_s"] - reduced["busy_s"])
    assert reduced["longest_gap_s"] == pytest.approx(4000e-9)


def test_breakdown_lists_at_most_ten(reduced):
    out = trace_reduce.breakdown(reduced)
    assert out["device_ops"][0] == [
        "value_gradient_sums.2 = (f32[1,2]) custom-call(...)", pytest.approx(4000e-9)
    ]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_union_and_self_times():
    assert trace_reduce.union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    nested = [("outer", 0.0, 10.0), ("inner", 2.0, 3.0), ("inner", 6.0, 2.0), ("next", 10.0, 1.0)]
    assert sorted(trace_reduce.self_times(nested)) == [("inner", 2.0), ("inner", 3.0), ("next", 1.0), ("outer", 5.0)]
