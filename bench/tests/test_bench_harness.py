"""What every run shares: seeds, peaks, which metrics a cell reports, the
traced sub-window's place and the checks that decide ``correct``."""

import math

import pytest

import benchkit  # noqa: F401
from benchlib import harness

BM = {"end_to_end": [{"name": "images_per_s", "workloads": ["a"]},
                     {"name": "setup_s"}],
      "per_layer": [{"name": "mfu.train", "moves": "images_per_s",
                     "workloads": ["a"]},
                    {"name": "setup_share", "moves": "setup_s"}]}


def test_large_seeds_give_distinct_keys():
    import jax
    keys = [harness.seed_key(s) for s in (5, 2 ** 32 + 5, 2 ** 33 + 5)]
    data = [tuple(jax.random.key_data(k).tolist()) for k in keys]
    assert len(set(data)) == 3
    assert data[0] == tuple(jax.random.key_data(harness.seed_key(5)).tolist())


def test_peaks_are_known_by_device_kind():
    assert harness.peaks_for("TPU v5 lite")["bf16_flop_per_s"] == 197e12
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("cell,e2e,per", [
    ("a", {"images_per_s", "setup_s"}, {"mfu.train", "setup_share"}),
    ("b", {"setup_s"}, {"setup_share"}),
])
def test_a_cell_reports_its_own_metrics(cell, e2e, per):
    c = {"name": cell}
    assert {m["name"] for m in harness.cell_metrics(BM, c, "end_to_end")} \
        == e2e
    assert {m["name"] for m in harness.cell_metrics(BM, c, "per_layer")} \
        == per


def test_the_traced_sub_window_sits_in_the_middle():
    t = harness.Tracer(False, 50.0)
    t.open_window(100.0)
    assert (t.start_at, t.stop_at) == (120.0, 130.0)
    assert not t.poll(125.0)               # the profiler stays off
    short = harness.Tracer(False, 4.0)
    short.open_window(0.0)
    assert (short.start_at, short.stop_at) == (0.0, 4.0)


@pytest.mark.parametrize("value,ok", [(0.0, True), (0.05, True),
                                      (0.0501, False), (math.nan, False),
                                      (math.inf, False)])
def test_a_check_holds_up_to_its_limit(value, ok):
    checks = harness.fmt_checks({"grad_gap": value, "unlimited": 9.0},
                                {"grad_gap": 0.05})
    assert set(checks) == {"grad_gap"}
    assert harness.all_within(checks) is ok
