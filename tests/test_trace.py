"""repro.trace subsystem tests: attribution math, timeline overlap model,
store round-trip + schema behavior, regression flagging, and the CLI
record→compare loop end to end on a smoke config — all CPU-only."""

import dataclasses
import json
import os

import pytest

from repro.core import get_machine
from repro.core.hlo_analysis import KernelRecord, ModuleAnalysis
from repro.core.roofline import roofline_terms
from repro.trace import (SCHEMA_VERSION, TraceRecord, TraceStore,
                         attribute_time, build_timeline, compare_last,
                         compare_records, has_regressions,
                         record_from_phases, regressions)
from repro.trace.collector import PhaseMeasurement, kernel_bound_s
from repro.trace.store import PHASE_METRICS
from repro.trace.timeline import ascii_timeline, timeline_from_record

MACHINE = get_machine("tpu-v5e")


def _rec(name, flops_bf16=0.0, hbm=1, count=1, category="matmul"):
    return KernelRecord(
        name=name, opcode="fusion", op_name="", exec_count=count,
        flops_by_class={"bf16": flops_bf16} if flops_bf16 else {},
        hbm_bytes=hbm, vmem_bytes=hbm, category=category)


def _analysis():
    return ModuleAnalysis(kernels=[
        _rec("mm", flops_bf16=4e9, hbm=16e6),
        _rec("copy", hbm=16e6, category="zero-ai"),
    ], collectives=[])


def _measurement(name="fwd", wall_s=2e-3, analysis=None):
    analysis = analysis or _analysis()
    return PhaseMeasurement(
        name=name, wall_s=wall_s, iters=3, machine=MACHINE.name,
        terms=roofline_terms(analysis, MACHINE),
        kernels=attribute_time(analysis, MACHINE, wall_s),
        flops=analysis.total_flops, hbm_bytes=analysis.total_hbm_bytes)


class TestAttribution:
    def test_attributed_time_sums_to_wall(self):
        wall = 3e-3
        ks = attribute_time(_analysis(), MACHINE, wall)
        assert sum(k.attributed_s for k in ks) == pytest.approx(wall)

    def test_weights_proportional_to_bounds(self):
        an = _analysis()
        ks = {k.name: k for k in attribute_time(an, MACHINE, 1e-3)}
        bounds = {r.name: kernel_bound_s(r, MACHINE) for r in an.kernels}
        ratio = bounds["mm"] / bounds["copy"]
        assert (ks["mm"].attributed_s / ks["copy"].attributed_s
                == pytest.approx(ratio))

    def test_achieved_and_pct(self):
        ks = {k.name: k for k in attribute_time(_analysis(), MACHINE, 1e-3)}
        mm = ks["mm"]
        assert mm.achieved_flops_per_s == pytest.approx(
            mm.flops / mm.attributed_s)
        assert mm.pct_of_roofline == pytest.approx(
            mm.bound_s / mm.attributed_s)
        # zero-FLOP kernel: no achieved FLOP/s but still owns time
        assert ks["copy"].achieved_flops_per_s == 0.0
        assert ks["copy"].attributed_s > 0

    def test_all_zero_bounds_split_evenly(self):
        an = ModuleAnalysis(kernels=[
            _rec("a", hbm=0, category="zero-ai"),
            _rec("b", hbm=0, category="zero-ai")], collectives=[])
        ks = attribute_time(an, MACHINE, 2e-3)
        assert [k.attributed_s for k in ks] == pytest.approx([1e-3, 1e-3])

    def test_empty_analysis(self):
        assert attribute_time(ModuleAnalysis([], []), MACHINE, 1e-3) == []

    def test_phase_measurement_properties(self):
        m = _measurement(wall_s=2e-3)
        assert m.achieved_flops_per_s == pytest.approx(m.flops / 2e-3)
        assert m.pct_of_roofline == pytest.approx(
            m.terms.bound_overlap_s / 2e-3)
        assert "GFLOP/s" in m.summary()


class TestTimeline:
    def test_sequential_layout_and_totals(self):
        ms = {"fwd": _measurement("fwd", 1e-3),
              "bwd": _measurement("bwd", 2e-3)}
        tl = build_timeline(ms)
        assert [s.name for s in tl.spans] == ["fwd", "bwd"]
        assert tl.spans[1].start_s == pytest.approx(1e-3)
        assert tl.total_measured_s == pytest.approx(3e-3)

    def test_overlap_classification(self):
        def span(measured, lo=1.0, hi=2.0):
            from repro.trace.timeline import PhaseSpan
            return PhaseSpan("p", 0.0, measured, lo, hi, "compute")
        assert span(0.5).verdict == "sub-bound"
        assert span(0.5).overlap_efficiency == 1.0
        assert span(1.5).verdict == "overlapped"
        assert span(1.5).overlap_efficiency == pytest.approx(0.5)
        assert span(3.0).verdict == "serial"
        assert span(3.0).overlap_efficiency == 0.0
        assert span(10.0).verdict == "overhead"

    def test_ascii_timeline_renders(self):
        tl = build_timeline({"fwd": _measurement("fwd", 1e-3)})
        out = ascii_timeline(tl)
        assert "fwd" in out and "verdict" in out and "#" in out

    def test_timeline_from_record_payloads(self):
        rec = record_from_phases("c", {"fwd": _measurement("fwd", 1e-3),
                                       "bwd": _measurement("bwd", 2e-3)},
                                 machine=MACHINE.name)
        tl = timeline_from_record(rec)
        assert [s.name for s in tl.spans] == ["fwd", "bwd"]
        assert tl.total_measured_s == pytest.approx(3e-3)


class TestTimelineEdgeCases:
    """Degenerate inputs the renderer must survive: zero-duration spans,
    a single phase, an empty timeline, a collapsed envelope."""

    @staticmethod
    def _span(measured, lo=1.0, hi=2.0, name="p", start=0.0):
        from repro.trace.timeline import PhaseSpan
        return PhaseSpan(name, start, measured, lo, hi, "compute")

    def test_zero_duration_span(self):
        from repro.trace.timeline import Timeline
        s = self._span(0.0, lo=0.0, hi=0.0)
        # a 0-wall phase sits AT the (empty) envelope: perfect, sub-bound
        # never fires (strict <), and efficiency clamps to 1.0
        assert s.overlap_efficiency == 1.0
        assert s.verdict == "overlapped"
        assert s.end_s == s.start_s
        out = ascii_timeline(Timeline([s]))
        assert "p" in out and "0.000ms" in out
        # every span still draws at least one bar cell
        assert "#" in out

    def test_zero_duration_span_among_real_ones(self):
        from repro.trace.timeline import Timeline
        tl = Timeline([self._span(1e-3, name="fwd"),
                       self._span(0.0, lo=0.0, hi=0.0, name="opt",
                                  start=1e-3)])
        assert tl.total_measured_s == pytest.approx(1e-3)
        out = ascii_timeline(tl)
        assert "opt" in out and "fwd" in out

    def test_collapsed_envelope_measured_above(self):
        # hi == lo (single-term phase): any overage is fully serialized
        s = self._span(1.5, lo=1.0, hi=1.0)
        assert s.overlap_efficiency == 0.0
        assert s.verdict == "serial"       # within 1x..2x of serial bound

    def test_single_phase_timeline(self):
        tl = build_timeline({"fwd": _measurement("fwd", 1e-3)})
        assert len(tl.spans) == 1
        assert tl.spans[0].start_s == 0.0
        assert tl.pct_of_roofline == pytest.approx(
            tl.total_bound_overlap_s / 1e-3)
        out = ascii_timeline(tl)
        assert out.count("fwd") == 2       # table row + gantt bar row

    def test_empty_timeline(self):
        from repro.trace.timeline import Timeline
        tl = Timeline([])
        assert tl.total_measured_s == 0.0
        assert tl.pct_of_roofline == 0.0   # no division by zero
        out = ascii_timeline(tl)
        assert "verdict" in out and "0.000 ms" in out

    def test_bound_marks_land_on_or_past_bar(self):
        from repro.trace.timeline import Timeline
        # serial bound far past the measured bar: marks must not crash
        # the renderer even when they fall outside the drawn line
        out = ascii_timeline(Timeline([self._span(1.0, lo=0.5, hi=50.0)]))
        assert "|" in out.splitlines()[-4]  # overlap mark inside the bar


class TestStore:
    def test_round_trip(self, tmp_path):
        store = TraceStore(str(tmp_path / "t.jsonl"))
        rec = record_from_phases(
            "minitron-4b", {"fwd": _measurement()}, machine="cpu-host",
            mesh={"data": 2, "model": 4}, meta={"note": "x"})
        store.append(rec)
        got = store.records("minitron-4b")
        assert len(got) == 1
        r = got[0]
        assert r.schema_version == SCHEMA_VERSION
        assert r.run_id == rec.run_id
        assert r.git_sha and r.git_sha != ""
        assert r.mesh == {"data": 2, "model": 4}
        assert r.machine == "cpu-host"
        assert r.meta["note"] == "x"
        # acceptance metrics all present per phase
        for key in PHASE_METRICS:
            assert key in r.phases["fwd"], key
        assert r.phases["fwd"]["kernels"], "top kernels persisted"

    def test_host_fingerprint_names_the_device(self):
        import jax
        from repro.trace.store import host_fingerprint
        fp = host_fingerprint()
        assert fp["device_kind"] == jax.devices()[0].device_kind
        assert fp["device_count"] == str(jax.device_count())

    @pytest.mark.parametrize("layout", ["loose", "packed", "detached",
                                        "worktree"])
    def test_git_sha_read_from_dot_git(self, tmp_path, layout):
        from repro.trace.store import git_sha
        sha = "0123456789abcdef0123456789abcdef01234567"
        git = tmp_path / "main" / ".git"
        (git / "refs" / "heads").mkdir(parents=True)
        if layout == "detached":
            (git / "HEAD").write_text(sha + "\n")
        else:
            (git / "HEAD").write_text("ref: refs/heads/main\n")
        if layout in ("loose", "worktree"):
            (git / "refs" / "heads" / "main").write_text(sha + "\n")
        elif layout == "packed":
            (git / "packed-refs").write_text(
                f"# pack-refs with: peeled\n{sha} refs/heads/main\n")
        checkout = tmp_path / "main"
        if layout == "worktree":
            # the worktree's own HEAD names a branch of the main repo
            (git / "HEAD").write_text("ref: refs/heads/other\n")
            wt_git = git / "worktrees" / "wt"
            wt_git.mkdir(parents=True)
            (wt_git / "HEAD").write_text("ref: refs/heads/main\n")
            (wt_git / "commondir").write_text("../..\n")
            checkout = tmp_path / "wt"
            checkout.mkdir()
            (checkout / ".git").write_text(f"gitdir: {wt_git}\n")
        assert git_sha(str(checkout)) == sha

    def test_git_sha_never_looks_above_the_checkout(self, tmp_path):
        from repro.trace.store import git_sha
        assert git_sha(str(tmp_path)) == "unknown"
        git = tmp_path / ".git"
        git.mkdir()
        (git / "HEAD").write_text("0123456789abcdef\n")
        # an exported tree inside a repository has no history of its own
        export = tmp_path / "archive" / "repo"
        export.mkdir(parents=True)
        assert git_sha(str(export)) == "unknown"

    def test_append_only_and_filtering(self, tmp_path):
        store = TraceStore(str(tmp_path / "t.jsonl"))
        for cfg in ("a", "b", "a"):
            store.append(record_from_phases(
                cfg, {"fwd": _measurement()}, machine="cpu-host"))
        assert len(store.records()) == 3
        assert len(store.records("a")) == 2
        assert store.configs() == ["a", "b"]
        last = store.last("a", n=1)
        assert len(last) == 1

    def test_corrupt_line_skipped(self, tmp_path):
        path = tmp_path / "t.jsonl"
        store = TraceStore(str(path))
        store.append(record_from_phases("a", {"fwd": _measurement()},
                                        machine="cpu-host"))
        with open(path, "a") as f:
            f.write("{not json\n")
        store.append(record_from_phases("a", {"fwd": _measurement()},
                                        machine="cpu-host"))
        with pytest.warns(UserWarning, match="corrupt"):
            assert len(store.records("a")) == 2

    def test_newer_schema_skipped(self, tmp_path):
        path = tmp_path / "t.jsonl"
        store = TraceStore(str(path))
        rec = record_from_phases("a", {"fwd": _measurement()},
                                 machine="cpu-host")
        d = json.loads(rec.to_json())
        d["schema_version"] = SCHEMA_VERSION + 1
        with open(path, "a") as f:
            f.write(json.dumps(d) + "\n")
        with pytest.warns(UserWarning, match="newer"):
            assert store.records("a") == []

    def test_unknown_keys_tolerated(self, tmp_path):
        path = tmp_path / "t.jsonl"
        rec = record_from_phases("a", {"fwd": _measurement()},
                                 machine="cpu-host")
        d = json.loads(rec.to_json())
        d["some_future_field"] = {"x": 1}
        with open(path, "a") as f:
            f.write(json.dumps(d) + "\n")
        got = TraceStore(str(path)).records("a")
        assert len(got) == 1

    def test_run_lookup_by_prefix(self, tmp_path):
        store = TraceStore(str(tmp_path / "t.jsonl"))
        rec = store.append(record_from_phases(
            "a", {"fwd": _measurement()}, machine="cpu-host"))
        assert store.run(rec.run_id[:6]).run_id == rec.run_id
        assert store.run("nope") is None

    def test_missing_file_is_empty(self, tmp_path):
        assert TraceStore(str(tmp_path / "absent.jsonl")).records() == []


def _slowed(rec: TraceRecord, factor: float, phase="fwd") -> TraceRecord:
    phases = {k: dict(v) for k, v in rec.phases.items()}
    p = phases[phase]
    p["wall_s"] *= factor
    p["achieved_flops_per_s"] /= factor
    p["pct_of_roofline"] /= factor
    return dataclasses.replace(rec, phases=phases, run_id=rec.run_id + "x")


class TestCompare:
    def _base(self):
        return record_from_phases(
            "minitron-4b", {"fwd": _measurement("fwd", 2e-3),
                            "bwd": _measurement("bwd", 4e-3)},
            machine="cpu-host")

    def test_identical_runs_flag_nothing(self):
        base = self._base()
        deltas = compare_records(base, base, threshold=0.10)
        assert deltas and not has_regressions(deltas)

    def test_injected_regression_flagged(self):
        base = self._base()
        new = _slowed(base, 1.5, "fwd")
        deltas = compare_records(base, new, threshold=0.10)
        flagged = regressions(deltas)
        assert flagged
        assert {(d.phase, d.metric) for d in flagged} == {
            ("fwd", "wall_s"), ("fwd", "achieved_flops_per_s"),
            ("fwd", "pct_of_roofline")}
        wall = next(d for d in flagged if d.metric == "wall_s")
        assert wall.rel_delta == pytest.approx(0.5)

    def test_improvement_not_a_regression(self):
        base = self._base()
        faster = _slowed(base, 0.5, "bwd")
        deltas = compare_records(base, faster, threshold=0.10)
        assert not has_regressions(deltas)
        assert any(d.improvement for d in deltas)

    def test_below_threshold_not_flagged(self):
        base = self._base()
        new = _slowed(base, 1.05, "fwd")
        assert not has_regressions(compare_records(base, new, threshold=0.10))

    def test_vanished_phase_is_a_regression(self):
        base = self._base()
        new = dataclasses.replace(
            base, phases={"fwd": base.phases["fwd"]}, run_id="y")
        deltas = compare_records(base, new)
        cell = next(d for d in deltas if d.phase == "bwd")
        assert cell.new == 0.0
        # a silently dropped phase must FAIL the gate, not read as a speedup
        assert cell.regression and not cell.improvement
        assert has_regressions(deltas)

    def test_new_phase_is_a_regression_cell(self):
        base = self._base()
        grown = dataclasses.replace(
            base, phases={**base.phases, "extra": dict(base.phases["fwd"])},
            run_id="z")
        deltas = compare_records(base, grown)
        cell = next(d for d in deltas if d.phase == "extra")
        assert cell.base == 0.0 and cell.regression

    def test_compare_last_over_store(self, tmp_path):
        store = TraceStore(str(tmp_path / "t.jsonl"))
        base = self._base()
        store.append(base)
        store.append(_slowed(base, 2.0, "fwd"))
        deltas = compare_last(store, "minitron-4b", threshold=0.10)
        assert has_regressions(deltas)
        # single run per config → nothing to compare
        store2 = TraceStore(str(tmp_path / "u.jsonl"))
        store2.append(base)
        assert compare_last(store2, "minitron-4b") == []


class TestCliEndToEnd:
    """The acceptance loop: record twice (second run with an injected
    slowdown), then compare flags it — smoke config, CPU only."""

    @pytest.fixture(scope="class")
    def store_path(self, tmp_path_factory):
        from repro.trace.cli import main
        path = str(tmp_path_factory.mktemp("trace") / "trace.jsonl")
        rc = main(["record", "--config", "minitron-4b", "--store", path,
                   "--iters", "2", "--warmup", "1"])
        assert rc == 0
        rc = main(["record", "--config", "minitron-4b", "--store", path,
                   "--iters", "2", "--warmup", "1", "--scale-wall", "3.0"])
        assert rc == 0
        return path

    def test_record_writes_schema_versioned_metrics(self, store_path):
        recs = TraceStore(store_path).records("minitron-4b")
        assert len(recs) == 2
        for rec in recs:
            assert rec.schema_version == SCHEMA_VERSION
            assert set(rec.phases) == {"fwd", "bwd", "opt"}
            for p in rec.phases.values():
                assert p["wall_s"] > 0
                assert p["achieved_flops_per_s"] > 0
                assert p["pct_of_roofline"] > 0
                assert p["iters"] == 2

    def test_compare_flags_injected_regression(self, store_path, capsys):
        from repro.trace.cli import main
        rc = main(["compare", "--config", "minitron-4b", "--store",
                   store_path])
        out = capsys.readouterr().out
        assert rc == 1, out          # regression → non-zero exit
        assert "!" in out and "wall_s" in out
        assert "regression" in out

    def test_report_renders_stored_run(self, store_path, capsys):
        from repro.trace.cli import main
        rc = main(["report", "--store", store_path])
        out = capsys.readouterr().out
        assert rc == 0
        assert "minitron-4b" in out
        assert "%roof" in out and "verdict" in out

    def test_compare_explicit_run_ids(self, store_path, capsys):
        from repro.trace.cli import main
        recs = TraceStore(store_path).records("minitron-4b")
        rc = main(["compare", "--store", store_path,
                   "--base", recs[0].run_id, "--new", recs[1].run_id])
        assert rc == 1
        assert "!" in capsys.readouterr().out


class TestCliErrorPaths:
    """The non-happy branches of every ``repro.trace`` subcommand exit
    non-zero with a message instead of silently doing nothing."""

    def test_record_without_config_errors(self, tmp_path, capsys):
        from repro.trace.cli import main
        rc = main(["record", "--store", str(tmp_path / "t.jsonl")])
        assert rc == 2
        assert "--config" in capsys.readouterr().err

    def test_record_failure_exits_nonzero(self, tmp_path, capsys,
                                          monkeypatch):
        import repro.trace.cli as cli
        monkeypatch.setattr(cli, "build_measured_phases",
                            lambda *a, **k: 1 / 0)
        rc = cli.main(["record", "--config", "minitron-4b", "--store",
                       str(tmp_path / "t.jsonl")])
        assert rc == 1
        assert "[FAIL] minitron-4b" in capsys.readouterr().err

    def test_compare_base_without_new_errors(self, tmp_path, capsys):
        from repro.trace.cli import main
        rc = main(["compare", "--store", str(tmp_path / "t.jsonl"),
                   "--base", "abc"])
        assert rc == 2
        assert "go together" in capsys.readouterr().err

    def test_compare_unknown_run_id_errors(self, tmp_path, capsys):
        from repro.trace.cli import main
        store = TraceStore(str(tmp_path / "t.jsonl"))
        store.append(record_from_phases("a", {"fwd": _measurement()},
                                        machine="cpu-host"))
        rc = main(["compare", "--store", store.path,
                   "--base", "nope", "--new", "alsonope"])
        assert rc == 2
        assert "not found" in capsys.readouterr().err

    def test_compare_single_run_is_clean_exit(self, tmp_path, capsys):
        from repro.trace.cli import main
        store = TraceStore(str(tmp_path / "t.jsonl"))
        store.append(record_from_phases("a", {"fwd": _measurement()},
                                        machine="cpu-host"))
        rc = main(["compare", "--store", store.path])
        assert rc == 0                      # nothing comparable != regression
        assert "no cells" in capsys.readouterr().out

    def test_report_empty_store_errors(self, tmp_path, capsys):
        from repro.trace.cli import main
        rc = main(["report", "--store", str(tmp_path / "absent.jsonl")])
        assert rc == 2
        assert "no records" in capsys.readouterr().err

    def test_report_unknown_config_errors(self, tmp_path, capsys):
        from repro.trace.cli import main
        store = TraceStore(str(tmp_path / "t.jsonl"))
        store.append(record_from_phases("a", {"fwd": _measurement()},
                                        machine="cpu-host"))
        rc = main(["report", "--store", store.path, "--config", "missing"])
        assert rc == 2
        assert "no records" in capsys.readouterr().err


class TestMeasuredProfile:
    """profile_fn(measure=True) drives the same compiled object."""

    def test_wall_time_recorded(self):
        import jax
        import jax.numpy as jnp
        from repro.core import profile_fn
        from repro.trace import measurement_from_profile

        def f(a, b):
            return (a @ b).sum()

        spec = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        res = profile_fn(f, args=(spec, spec), machine="cpu-host",
                         measure=True, measure_iters=2, measure_warmup=1)
        assert res.wall_s is not None and res.wall_s > 0
        assert res.measure_iters == 2
        m = measurement_from_profile(res, "cpu-host")
        assert m.kernels
        assert sum(k.attributed_s for k in m.kernels) == pytest.approx(
            res.wall_s)

    def test_unmeasured_profile_rejected(self):
        import jax
        import jax.numpy as jnp
        from repro.core import profile_fn
        from repro.trace import measurement_from_profile

        spec = jax.ShapeDtypeStruct((8, 8), jnp.float32)
        res = profile_fn(lambda a: a + 1, args=(spec,), machine="cpu-host")
        with pytest.raises(ValueError, match="wall_s"):
            measurement_from_profile(res, "cpu-host")
