"""BENCHMARK.json against the rules its checker applies, and the result
line's shape."""

import io
import json
import os
import re
from contextlib import redirect_stderr, redirect_stdout

import benchkit
from benchlib import harness

BM = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_names_units_and_sizes():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for c in BM["workloads"]:
        assert c["chips"] in (1, 4) and len(c["why"]) <= 200
    assert 1 <= BM["run_seconds"] <= 51
    assert len(json.dumps(BM)) < 64 * 1024


def test_every_entry_has_its_files():
    root = os.path.dirname(benchkit.BENCH)
    for c in BM["configs"]:
        assert os.path.exists(os.path.join(root, c["file"]))
        assert os.path.exists(os.path.join(
            root, os.path.splitext(c["file"])[0] + ".py"))
        assert any(w["config"] == c["name"] for w in BM["workloads"])
    for m in BM["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)
        assert m["moves"] in {e["name"] for e in BM["end_to_end"]}


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for cell in BM["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(BM, cell,
                                                       "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        per = harness.cell_metrics(BM, cell, "per_layer")
        assert per and all(m["moves"] in e2e for m in per)
        assert any("mfu" in m["name"] for m in per)


def test_result_line_puts_the_checks_last():
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        harness.emit({"correct": True, "attempted": 3, "failed": 0,
                      "metrics": {}, "device": {}},
                     {"loss_gap": (0.001, 0.01)})
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["checks"] == {"loss_gap": {"value": 0.001, "limit": 0.01}}
    assert err.getvalue().strip().splitlines()[-1] == \
        "check loss_gap: 0.001 (limit 0.01)"
