"""The reduction from a profiler trace to metrics, on a small trace
recorded on a TPU v5e (``fixtures/small.xplane.pb``, made by
``fixtures/record_trace.py``) and on made-up events."""

import os

import numpy as np
import pytest

import benchkit
from benchlib import trace as T

FIXTURE = os.path.join(benchkit.FIXTURES, "small.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return T.load(FIXTURE)


def test_fixture_has_one_tpu_and_the_benchmark_spans(tr):
    assert sorted(tr.devices) == [0]
    dev = tr.devices[0]
    assert len(dev.modules) == 4 and len(dev.ops) == 30
    names = [s[2] for s in tr.spans]
    assert names == ["bench.conv", "bench.host_wait", "bench.matmul"] * 2
    lo, hi = tr.window
    assert 0 < lo < tr.spans[0][0] and tr.spans[-1][1] < hi


def test_busy_is_the_union_of_op_intervals(tr):
    dev = tr.devices[0]
    lo, hi = tr.window
    grid = np.zeros(int(hi - lo) + 1, bool)
    for o in dev.ops:
        a, b = max(o.start, lo), min(o.end, hi)
        if b > a:
            grid[int(a - lo):int(b - lo)] = True
    assert T.busy_ns(dev, tr.window) == pytest.approx(grid.sum(), abs=64)


def test_op_self_times_add_up_to_the_ops(tr):
    ops = T.in_window(tr.devices[0].ops, tr.window)
    by_name = T.op_time_by_name(ops)
    assert sum(by_name.values()) == pytest.approx(sum(T.self_times(ops)))
    assert max(by_name, key=by_name.get) == "%convolution_tanh_fusion"


def test_idle_gaps_are_named_by_the_host_span(tr):
    idle = T.idle_by_span(tr.devices[0], tr.spans, tr.window)
    lo, hi = tr.window
    assert sum(idle.values()) == pytest.approx(
        (hi - lo) - T.busy_ns(tr.devices[0], tr.window))
    # the two 5 ms sleeps dominate the idle time
    assert idle["bench.host_wait"] > 0.6 * sum(idle.values())


def test_nested_ops_count_once():
    ops = [T.Op(0, 100, "%while", ""), T.Op(10, 30, "%a", ""),
           T.Op(40, 90, "%b", ""), T.Op(120, 130, "%c", "")]
    assert T.self_times(ops) == [30, 20, 50, 10]
    dev = T.Device(ops, [])
    assert T.busy_ns(dev, (0, 200)) == 110


def test_gaps_and_subtract():
    assert T.union([(0, 5), (3, 8), (10, 12)], 0, 11) == [(0, 8), (10, 11)]
    assert T.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert T.top({"a": 2e9, "b": 3e9}, 1) == [["b", 3.0]]
