"""repro.obs: fleet merge, trend series + gate, bottleneck advisor.

Everything here runs on synthetic stored records — no jax lowering, no
measurement; the observability layer reads only persisted state, so the
tests write that state directly (the merge-conflict cases are the ISSUE
acceptance list: same run_id twice, differing schema versions, corrupt
remote lines — skip-and-report, never corrupt the local store).
"""

from __future__ import annotations

import json
import os

import pytest

from repro.obs.merge import (MergeReport, merge_bench, merge_jsonl,
                             merge_tune, merge_workspace, render_merge)
from repro.obs.trend import (DEFAULT_TOLERANCE, TrendPoint, TrendSeries,
                             bench_series, gate_series, render_trend,
                             sparkline, trace_series)
from repro.session.workspace import Workspace
from repro.trace.store import SCHEMA_VERSION, TraceRecord, TraceStore
from repro.tune.store import TuneStore

MACHINE = "cpu-host"

# cpu-host datasheet numbers (core.machine): the level_pinned rule needs
# byte counts sized against these bandwidths
HBM_BPS = 20e9
VMEM_BPS = 200e9


def _phase(wall=2e-3, *, bound_overlap=1e-3, bound_serial=None,
           launches=100, zero_ai=0, scatter=0, flops=1e9,
           hbm_bytes=1e6, vmem_bytes=1e6, dominant="compute"):
    return {
        "launches": launches, "zero_ai_launches": zero_ai,
        "scatter_launches": scatter,
        "wall_s": wall, "flops": flops,
        "hbm_bytes": hbm_bytes, "vmem_bytes": vmem_bytes,
        "compute_s": bound_overlap, "memory_s": bound_overlap / 2,
        "collective_s": 0.0,
        "bound_overlap_s": bound_overlap,
        "bound_serial_s": (bound_serial if bound_serial is not None
                           else bound_overlap * 1.5),
        "dominant": dominant,
    }


def _record(run_id, *, config="minitron-4b", ts=1000.0, wall=2e-3,
            host="hostA", fusion="off", phases=None, meta=None):
    return TraceRecord(
        schema_version=SCHEMA_VERSION, run_id=run_id, timestamp=ts,
        git_sha="deadbeef", config=config, machine=MACHINE, mesh={},
        host={"host": host, "backend": "cpu"},
        phases=phases if phases is not None else {"fwd": _phase(wall)},
        meta={"fusion": fusion, **(meta or {})})


def _write_store(path, records):
    store = TraceStore(path)
    for rec in records:
        store.append(rec)
    return store


# --------------------------------------------------------------------------
# merge: JSONL stores
# --------------------------------------------------------------------------

class TestMergeJsonl:
    def test_union_by_run_id(self, tmp_path):
        local, remote = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
        _write_store(local, [_record("r1")])
        _write_store(remote, [_record("r1"), _record("r2", ts=2000.0)])
        rep = merge_jsonl(local, remote)
        assert (rep.n_added, rep.n_dup, rep.n_conflict) == (1, 1, 0)
        assert {r.run_id for r in TraceStore(local).records()} == \
            {"r1", "r2"}

    def test_same_run_id_identical_is_duplicate(self, tmp_path):
        local, remote = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
        _write_store(local, [_record("r1")])
        _write_store(remote, [_record("r1")])
        rep = merge_jsonl(local, remote)
        assert (rep.n_added, rep.n_dup) == (0, 1)
        assert not rep.merged_any

    def test_same_run_id_different_content_keeps_local(self, tmp_path):
        local, remote = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
        _write_store(local, [_record("r1", wall=2e-3)])
        _write_store(remote, [_record("r1", wall=9e-3)])
        rep = merge_jsonl(local, remote)
        assert rep.n_conflict == 1 and rep.n_added == 0
        assert any("local kept" in n for n in rep.notes)
        [rec] = TraceStore(local).records()
        assert rec.phases["fwd"]["wall_s"] == pytest.approx(2e-3)

    def test_newer_schema_remote_skipped(self, tmp_path):
        local, remote = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
        _write_store(local, [_record("r1")])
        d = json.loads(_record("r9").to_json())
        d["schema_version"] = SCHEMA_VERSION + 7
        with open(remote, "w") as f:
            f.write(json.dumps(d) + "\n")
        rep = merge_jsonl(local, remote)
        assert rep.n_skipped == 1 and rep.n_added == 0
        assert any("newer writer" in n for n in rep.notes)

    def test_corrupt_remote_lines_never_corrupt_local(self, tmp_path):
        local, remote = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
        _write_store(local, [_record("r1")])
        with open(remote, "w") as f:
            f.write("{not json!\n")
            f.write('"a bare string"\n')
            f.write(_record("r2").to_json() + "\n")
        rep = merge_jsonl(local, remote)
        assert rep.n_skipped == 2 and rep.n_added == 1
        # the local store still parses completely: every line is a record
        recs = TraceStore(local).records()
        assert {r.run_id for r in recs} == {"r1", "r2"}
        with open(local) as f:
            for line in f:
                assert isinstance(json.loads(line), dict)

    def test_missing_remote_is_noop(self, tmp_path):
        rep = merge_jsonl(str(tmp_path / "a.jsonl"),
                          str(tmp_path / "nope.jsonl"))
        assert rep.n_added == 0 and rep.notes

    def test_idempotent(self, tmp_path):
        local, remote = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
        _write_store(remote, [_record("r1"), _record("r2")])
        assert merge_jsonl(local, remote).n_added == 2
        again = merge_jsonl(local, remote)
        assert again.n_added == 0 and again.n_dup == 2
        assert len(TraceStore(local).records()) == 2

    def test_unstamped_records_dedupe_by_content(self, tmp_path):
        local, remote = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
        d = json.loads(_record("x").to_json())
        d["run_id"] = ""                   # pre-run_id era record
        for p in (local, remote):
            with open(p, "w") as f:
                f.write(json.dumps(d) + "\n")
        rep = merge_jsonl(local, remote)
        assert rep.n_dup == 1 and rep.n_added == 0


# --------------------------------------------------------------------------
# merge: tune store + bench harvests
# --------------------------------------------------------------------------

def _tune_doc(key="triad|pallas|[1048576]|float32|cpu-host", ts=100.0,
              wall=1e-3, schema=None):
    from repro.tune.store import SCHEMA_VERSION as TUNE_SCHEMA
    return {"schema_version": TUNE_SCHEMA, "records": {key: {
        "schema_version": schema if schema is not None else TUNE_SCHEMA,
        "key": key, "kernel": key.split("|")[0], "backend": "pallas",
        "shape": [1048576], "dtype": "float32", "machine": MACHINE,
        "params": {"block": 256}, "wall_s": wall, "metric": 1.0 / wall,
        "metric_name": "bytes_per_s", "default_wall_s": 2 * wall,
        "default_metric": 0.5 / wall, "n_candidates": 4,
        "timestamp": ts, "git_sha": "cafe", "host": {"host": "hostB"}}}}


class TestMergeTune:
    def test_absent_key_added(self, tmp_path):
        local, remote = str(tmp_path / "l.json"), str(tmp_path / "r.json")
        json.dump(_tune_doc(), open(remote, "w"))
        rep = merge_tune(local, remote)
        assert rep.n_added == 1
        assert len(list(TuneStore(local).records())) == 1

    def test_conflict_newer_timestamp_wins(self, tmp_path):
        local, remote = str(tmp_path / "l.json"), str(tmp_path / "r.json")
        json.dump(_tune_doc(ts=100.0, wall=2e-3), open(local, "w"))
        json.dump(_tune_doc(ts=200.0, wall=1e-3), open(remote, "w"))
        rep = merge_tune(local, remote)
        assert rep.n_conflict == 1 and rep.n_added == 1
        [rec] = TuneStore(local).records()
        assert rec.timestamp == 200.0 and rec.wall_s == pytest.approx(1e-3)

    def test_conflict_older_remote_kept_out(self, tmp_path):
        local, remote = str(tmp_path / "l.json"), str(tmp_path / "r.json")
        json.dump(_tune_doc(ts=300.0, wall=2e-3), open(local, "w"))
        json.dump(_tune_doc(ts=200.0, wall=1e-3), open(remote, "w"))
        rep = merge_tune(local, remote)
        assert rep.n_conflict == 1 and rep.n_added == 0
        [rec] = TuneStore(local).records()
        assert rec.timestamp == 300.0

    def test_corrupt_remote_store_skipped(self, tmp_path):
        local, remote = str(tmp_path / "l.json"), str(tmp_path / "r.json")
        json.dump(_tune_doc(), open(local, "w"))
        with open(remote, "w") as f:
            f.write("{broken")
        rep = merge_tune(local, remote)
        assert rep.n_skipped == 1 and rep.n_added == 0
        assert len(list(TuneStore(local).records())) == 1  # untouched

    def test_newer_schema_record_skipped(self, tmp_path):
        from repro.tune.store import SCHEMA_VERSION as TUNE_SCHEMA
        local, remote = str(tmp_path / "l.json"), str(tmp_path / "r.json")
        json.dump(_tune_doc(schema=TUNE_SCHEMA + 5), open(remote, "w"))
        rep = merge_tune(local, remote)
        assert rep.n_skipped == 1 and rep.n_added == 0


class TestMergeBench:
    def _harvest(self, d, name, ok=True):
        path = os.path.join(d, name)
        json.dump({"schema_version": 1, "timestamp": 1.0,
                   "suites": {"s": {"ok": ok, "wall_s": 1.0, "rows": []}}},
                  open(path, "w"))
        return path

    def test_copies_absent_files_only(self, tmp_path):
        ldir, rdir = str(tmp_path / "l"), str(tmp_path / "r")
        os.makedirs(ldir), os.makedirs(rdir)
        self._harvest(ldir, "BENCH_1.json")
        self._harvest(rdir, "BENCH_1.json")
        self._harvest(rdir, "BENCH_2.json")
        rep = merge_bench(ldir, rdir)
        assert (rep.n_added, rep.n_dup) == (1, 1)
        assert sorted(os.listdir(ldir)) == ["BENCH_1.json", "BENCH_2.json"]

    def test_corrupt_harvest_skipped(self, tmp_path):
        ldir, rdir = str(tmp_path / "l"), str(tmp_path / "r")
        os.makedirs(ldir), os.makedirs(rdir)
        with open(os.path.join(rdir, "BENCH_bad.json"), "w") as f:
            f.write("nope")
        rep = merge_bench(ldir, rdir)
        assert rep.n_skipped == 1 and os.listdir(ldir) == []


# --------------------------------------------------------------------------
# merge: whole workspaces (idempotency is the acceptance criterion)
# --------------------------------------------------------------------------

class TestMergeWorkspace:
    def _ws(self, root, records):
        ws = Workspace(str(root))
        for rec in records:
            ws.trace_store.append(rec)
        ws.write_header(MACHINE)
        return ws

    def test_merge_and_provenance(self, tmp_path):
        a = self._ws(tmp_path / "a", [_record("r1")])
        b = self._ws(tmp_path / "b", [_record("r2", host="hostB")])
        reports = merge_workspace(a, str(tmp_path / "b"))
        assert sum(r.n_added for r in reports) == 1
        [entry] = a.read_header()["merges"]
        assert entry["remote_root"] == str(tmp_path / "b")
        assert entry["added"]["trace"] == 1
        text = render_merge(reports, a.root, str(tmp_path / "b"))
        assert "+1 added" in text

    def test_remerge_is_idempotent_no_new_provenance(self, tmp_path):
        a = self._ws(tmp_path / "a", [_record("r1")])
        self._ws(tmp_path / "b", [_record("r2")])
        merge_workspace(a, str(tmp_path / "b"))
        before = open(a.trace_path).read()
        reports = merge_workspace(a, str(tmp_path / "b"))
        assert sum(r.n_added for r in reports) == 0
        assert open(a.trace_path).read() == before
        assert len(a.read_header()["merges"]) == 1
        assert "(no-op)" in render_merge(reports, a.root, "b")

    def test_missing_remote_raises(self, tmp_path):
        a = self._ws(tmp_path / "a", [])
        with pytest.raises(FileNotFoundError):
            merge_workspace(a, str(tmp_path / "nope"))

    def test_write_header_preserves_merge_provenance(self, tmp_path):
        a = self._ws(tmp_path / "a", [_record("r1")])
        self._ws(tmp_path / "b", [_record("r2")])
        merge_workspace(a, str(tmp_path / "b"))
        a.write_header(MACHINE)        # e.g. a later record() refresh
        assert len(a.read_header()["merges"]) == 1


# --------------------------------------------------------------------------
# trend: series, sparkline, gate
# --------------------------------------------------------------------------

def _series(values, *, lower=True, metric="wall_s", key="k"):
    return TrendSeries(
        key=key, source="trace", metric=metric, lower_is_better=lower,
        points=[TrendPoint(float(i), v, f"run r{i}")
                for i, v in enumerate(values)])


class TestSparkline:
    def test_empty(self):
        assert sparkline([]) == ""

    def test_constant_is_flat(self):
        out = sparkline([2.0, 2.0, 2.0])
        assert len(out) == 3 and len(set(out)) == 1

    def test_monotone_ramps(self):
        out = sparkline([0.0, 1.0, 2.0, 3.0])
        assert out[0] == "▁" and out[-1] == "█"


class TestTrendSeries:
    def test_trace_series_groups_by_fleet_key(self):
        recs = [_record("r1", ts=1.0, host="hostA"),
                _record("r2", ts=2.0, host="hostA"),
                _record("r3", ts=1.5, host="hostB")]
        wall = [s for s in trace_series(recs) if s.metric == "wall_s"]
        keys = {s.key for s in wall}
        assert keys == {f"minitron-4b|{MACHINE}|hostA|off",
                        f"minitron-4b|{MACHINE}|hostB|off"}
        a = next(s for s in wall if "hostA" in s.key)
        assert [p.ref for p in a.points] == ["run r1", "run r2"]

    def test_analytical_records_excluded(self):
        rec = _record("r1", phases={"fwd": _phase(wall=0.0)})
        assert trace_series([rec]) == []

    def test_baseline_is_median_of_prior(self):
        s = _series([1.0, 3.0, 2.0, 9.0])
        assert s.baseline() == 2.0     # median of [1, 3, 2]
        assert _series([5.0]).baseline() is None

    def test_gate_flags_regression(self):
        s = _series([1.0, 1.0, 1.0, 2.0])
        [reg] = gate_series([s])
        assert reg.rel == pytest.approx(1.0)
        assert "baseline 1" in reg.describe()

    def test_gate_respects_tolerance_and_direction(self):
        slow = _series([1.0, 1.2])
        assert gate_series([slow], tolerance=0.25) == []
        assert len(gate_series([slow], tolerance=0.1)) == 1
        # higher-is-better metrics never gate
        up = _series([1.0, 9.0], lower=False, metric="gflops")
        assert gate_series([up]) == []
        # a single point has no baseline
        assert gate_series([_series([9.0])]) == []

    def test_default_tolerance_sane(self):
        assert 0.0 < DEFAULT_TOLERANCE < 1.0

    def test_bench_series_from_harvests(self, tmp_path):
        for i, us in enumerate((10.0, 30.0)):
            json.dump(
                {"schema_version": 1, "timestamp": float(i),
                 "host": {"host": "hostZ"},
                 "suites": {
                     "good": {"ok": True, "wall_s": 1.0 + i, "rows": [
                         {"name": "op", "us_per_call": us, "derived": ""},
                         {"name": "derived_only", "us_per_call": 0.0,
                          "derived": "x"}]},
                     "broken": {"ok": False, "wall_s": 9.0, "rows": []}}},
                open(tmp_path / f"BENCH_{i}.json", "w"))
        series = {(s.key, s.metric): s
                  for s in bench_series([str(tmp_path)])}
        assert ("good|hostZ", "wall_s") in series
        row = series[("good/op|hostZ", "us_per_call")]
        assert row.values == [10.0, 30.0]
        # not-ok suites and derived-only rows contribute nothing
        assert not any("broken" in k for k, _ in series)
        assert not any("derived_only" in k for k, _ in series)

    def test_render_trend(self):
        s = _series([1.0, 1.0, 2.0])
        out = render_trend([s], gate_series([s]))
        assert "regression(s)" in out and "!" in out
        assert "gate: OK" in render_trend([s], [])
        assert "no history" in render_trend([], None)


# --------------------------------------------------------------------------
# advisor rules
# --------------------------------------------------------------------------

class TestAdvisor:
    def _ws(self, tmp_path, records):
        ws = Workspace(str(tmp_path / "ws"))
        for rec in records:
            ws.trace_store.append(rec)
        return ws

    def test_launch_overhead_fires_past_serial_bound(self, tmp_path):
        from repro.obs.advisor import advise
        # wall 3x past the serial bound, 40% zero-AI launches, fusion=off
        rec = _record("r1", phases={"fwd": _phase(
            wall=3e-3, bound_overlap=0.8e-3, bound_serial=1e-3,
            launches=100, zero_ai=40)})
        findings = advise(self._ws(tmp_path, [rec]))
        hit = [f for f in findings if f.rule == "launch_overhead"]
        assert len(hit) == 1
        assert "40/100 launches" in hit[0].evidence[1]
        assert "fusion" in hit[0].remediation
        assert "run r1" in hit[0].evidence[0]

    def test_launch_overhead_quiet_when_fused_or_clean(self, tmp_path):
        from repro.obs.advisor import advise
        bad = dict(wall=3e-3, bound_overlap=0.8e-3, bound_serial=1e-3,
                   launches=100, zero_ai=40)
        fused = _record("r1", fusion="auto", phases={"fwd": _phase(**bad)})
        in_envelope = _record("r2", phases={"fwd": _phase(
            wall=0.9e-3, bound_overlap=0.8e-3, bound_serial=1e-3,
            launches=100, zero_ai=40)})
        for rec in (fused, in_envelope):
            findings = advise(self._ws(tmp_path / rec.run_id, [rec]))
            assert not [f for f in findings
                        if f.rule == "launch_overhead"]

    def test_scatter_heavy_backward_only(self, tmp_path):
        from repro.obs.advisor import advise
        rec = _record("r1", phases={
            "fwd": _phase(scatter=5),       # forward scatter: not flagged
            "bwd": _phase(scatter=8)})
        hit = [f for f in advise(self._ws(tmp_path, [rec]))
               if f.rule == "scatter_heavy"]
        assert [f.subject for f in hit] == ["minitron-4b/bwd"]
        assert "8 scatter launch(es)" in hit[0].evidence[0]

    def test_untuned_fires_once_on_default_stamp(self, tmp_path):
        from repro.obs.advisor import advise
        kcfg = {"flash_attention": {"source": "default"},
                "fused_norm": {"source": "default"}}
        recs = [_record("r1", meta={"kernel_configs": kcfg}),
                _record("r2", ts=2000.0, meta={"kernel_configs": kcfg})]
        hit = [f for f in advise(self._ws(tmp_path, recs))
               if f.rule == "untuned"]
        assert len(hit) == 1               # one finding, not one per record
        assert "tune search" in hit[0].remediation

    def test_tune_mismatch_stale_default(self, tmp_path):
        from repro.obs.advisor import advise
        ws = self._ws(tmp_path, [_record("r1", meta={"kernel_configs": {
            "triad": {"source": "default"}}})])
        json.dump(_tune_doc(), open(ws.tune_path, "w"))
        hit = [f for f in advise(ws) if f.rule == "tune_mismatch"]
        assert len(hit) == 1
        assert "tuned winner" in hit[0].evidence[0]
        # ... and the untuned rule stays quiet once winners exist
        assert not [f for f in advise(ws) if f.rule == "untuned"]

    def test_level_pinned_on_dominant_bandwidth(self, tmp_path):
        from repro.obs.advisor import advise
        # hbm streaming time = 80% of a 10ms wall on the cpu-host model
        rec = _record("r1", phases={"fwd": _phase(
            wall=10e-3, bound_overlap=9e-3, bound_serial=20e-3,
            hbm_bytes=0.8 * 10e-3 * HBM_BPS, vmem_bytes=1.0,
            dominant="memory")})
        hit = [f for f in advise(self._ws(tmp_path, [rec]))
               if f.rule == "level_pinned"]
        assert len(hit) == 1
        assert "hbm" in hit[0].evidence[0]
        assert hit[0].severity == pytest.approx(0.8)

    def test_findings_ranked_by_severity(self, tmp_path):
        from repro.obs.advisor import advise, render_findings
        rec = _record("r1", phases={
            "fwd": _phase(wall=3e-3, bound_overlap=0.8e-3,
                          bound_serial=1e-3, launches=100, zero_ai=40),
            "bwd": _phase(scatter=1)})
        findings = advise(self._ws(tmp_path, [rec]))
        assert len(findings) >= 2
        sevs = [f.severity for f in findings]
        assert sevs == sorted(sevs, reverse=True)
        out = render_findings(findings, top=1)
        assert "1. [" in out and "more (raise --top)" in out

    def test_no_findings_message(self):
        from repro.obs.advisor import render_findings
        assert "no known bottleneck" in render_findings([])


# --------------------------------------------------------------------------
# CLI: python -m repro trend / advise / merge
# --------------------------------------------------------------------------

class TestObsCli:
    def _seed(self, root, records):
        ws = Workspace(str(root))
        for rec in records:
            ws.trace_store.append(rec)
        ws.write_header(MACHINE)
        return ws

    def test_trend_gate_exit_codes(self, tmp_path, capsys):
        from repro.cli import main
        ws = str(tmp_path / "ws")
        self._seed(ws, [_record(f"r{i}", ts=float(i), wall=1e-3)
                        for i in range(3)])
        assert main(["--workspace", ws, "trend", "--gate"]) == 0
        assert "gate: OK" in capsys.readouterr().out
        # a 3x slowdown lands as the newest point and trips the gate
        Workspace(ws).trace_store.append(_record("r9", ts=9.0, wall=3e-3))
        assert main(["--workspace", ws, "trend", "--gate"]) == 1
        out = capsys.readouterr().out
        assert "run r9" in out and "wall_s" in out
        # ... but a generous tolerance waves it through
        assert main(["--workspace", ws, "trend", "--gate",
                     "--tolerance", "5.0"]) == 0

    def test_advise_cli(self, tmp_path, capsys):
        from repro.cli import main
        ws = str(tmp_path / "ws")
        self._seed(ws, [_record("r1", phases={"fwd": _phase(
            wall=3e-3, bound_overlap=0.8e-3, bound_serial=1e-3,
            launches=100, zero_ai=40)})])
        assert main(["--workspace", ws, "advise"]) == 0
        out = capsys.readouterr().out
        assert "[launch_overhead]" in out and "evidence:" in out

    def test_read_only_commands_leave_the_device_alone(self, tmp_path,
                                                       monkeypatch):
        """trend / advise / merge only read the stores: they neither ask
        JAX for a device nor turn the compile cache on, either of which
        would claim an accelerator from the process measuring on it."""
        from repro import cli
        from repro.core import machine

        def claim(*a, **k):
            raise AssertionError("a read-only command asked for the device")

        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        self._seed(a, [_record("r1")])
        self._seed(b, [_record("r2", host="hostB")])
        monkeypatch.setattr(machine, "machine_for_device", claim)
        monkeypatch.setattr(cli, "enable_compile_cache", claim)
        assert cli.main(["--workspace", a, "trend"]) == 0
        assert cli.main(["--workspace", a, "advise"]) == 0
        assert cli.main(["--workspace", a, "merge", b]) == 0

    def test_merge_cli_and_idempotency(self, tmp_path, capsys):
        from repro.cli import main
        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        self._seed(a, [_record("r1")])
        self._seed(b, [_record("r2", host="hostB")])
        assert main(["--workspace", a, "merge", b]) == 0
        assert "+1 added" in capsys.readouterr().out
        assert main(["--workspace", a, "merge", b]) == 0
        assert "(no-op)" in capsys.readouterr().out
        assert len(Workspace(a).read_header()["merges"]) == 1

    def test_merge_cli_missing_remote_exit_2(self, tmp_path, capsys):
        from repro.cli import main
        a = str(tmp_path / "a")
        self._seed(a, [])
        assert main(["--workspace", a, "merge",
                     str(tmp_path / "nope")]) == 2
        assert "merge:" in capsys.readouterr().err

    def test_session_trend_data_shape(self, tmp_path):
        """Session.trend exposes (series, regressions) for callers."""
        from repro.session import Session
        ws = Workspace(str(tmp_path / "ws"))
        for i in range(3):
            ws.trace_store.append(_record(f"r{i}", ts=float(i)))
        res = Session(machine=MACHINE, workspace=ws).trend(gate=True)
        series, regressions = res.data
        assert series and regressions == [] and res.exit_code == 0


class TestMergeReport:
    def test_describe_counts(self):
        rep = MergeReport(store="trace", n_added=2, n_dup=1)
        rep.note("detail line")
        text = rep.describe()
        assert "+2 added" in text and "detail line" in text
        assert rep.merged_any
        assert not MergeReport(store="tune").merged_any
