"""repro.resilience tests: fault plans, torn-tail repair, the campaign
journal, the supervised worker pool, checkpoint integrity and the
trainer/serve retry paths (docs/DESIGN.md §17).
"""

import json
import os
import tempfile
import time

import pytest

from repro.resilience import faults
from repro.resilience.journal import CampaignJournal, journal_path_for
from repro.resilience.jsonl import fsync_append, repair_jsonl_tail
from repro.resilience.watchdog import SupervisedPool


@pytest.fixture
def fault_env(monkeypatch):
    """Set REPRO_FAULTS for one test with fresh fire counters."""
    def set_plan(value: str) -> None:
        monkeypatch.setenv(faults.FAULT_ENV, value)
        faults._active = None           # fresh counters per test
    yield set_plan
    faults._active = None


# ---------------------------------------------------------------------------
# fault plan grammar + firing semantics
# ---------------------------------------------------------------------------

class TestFaultPlan:
    def test_parse_roundtrip(self):
        text = ("crash_point:3;hang_point:1:30x2;torn_tail:sweep;"
                "step_fault:7x-1")
        plan = faults.parse_plan(text)
        kinds = [s.kind for s in plan.specs]
        assert kinds == ["crash_point", "hang_point", "torn_tail",
                         "step_fault"]
        assert plan.specs[0].index == 3
        assert plan.specs[1].arg == 30.0 and plan.specs[1].times == 2
        assert plan.specs[2].target == "sweep"
        assert plan.specs[3].times == -1
        assert faults.parse_plan(plan.render()).render() == plan.render()

    def test_empty_plan_is_falsy(self):
        assert not faults.parse_plan(None)
        assert not faults.parse_plan("")
        assert not faults.parse_plan(" ; ;")
        assert faults.parse_plan("torn_tail")

    @pytest.mark.parametrize("bad", [
        "explode:1",                 # unknown kind
        "crash_point",               # missing target index
        "crash_point:x",             # non-integer target
        "hang_point:1",              # missing seconds
        "step_fault:1x0",            # zero firings
        "step_fault:1x-2",           # invalid negative
    ])
    def test_parse_rejects(self, bad):
        with pytest.raises(ValueError):
            faults.parse_plan(bad)

    def test_counter_bounds_firings(self):
        plan = faults.parse_plan("step_fault:5x2")
        assert plan.fires("step_fault", target=5) is not None
        assert plan.fires("step_fault", target=5) is not None
        assert plan.fires("step_fault", target=5) is None    # exhausted
        # a different target never matches and never burns the counter
        assert plan.fires("step_fault", target=6) is None

    def test_always_spec_never_exhausts(self):
        plan = faults.parse_plan("crash_point:2x-1")
        for attempt in range(5):
            assert plan.fires("crash_point", target=2,
                              attempt=attempt) is not None

    def test_explicit_attempt_overrides_counter(self):
        plan = faults.parse_plan("crash_point:0")    # times=1
        assert plan.fires("crash_point", target=0, attempt=0) is not None
        assert plan.fires("crash_point", target=0, attempt=1) is None
        # explicit attempts never advanced the internal counter
        assert plan.fires("crash_point", target=0, attempt=0) is not None

    def test_untargeted_spec_matches_any_target(self):
        plan = faults.parse_plan("torn_tailx-1")
        assert plan.fires("torn_tail", target="trace") is not None
        assert plan.fires("torn_tail", target="sweep") is not None
        targeted = faults.parse_plan("torn_tail:trace")
        assert targeted.fires("torn_tail", target="sweep") is None
        assert targeted.fires("torn_tail", target=None) is None

    def test_maybe_raise(self):
        plan = faults.parse_plan("serve_fault:4")
        with pytest.raises(faults.TransientFault, match="serve_fault:4"):
            plan.maybe_raise("serve_fault", target=4)
        plan.maybe_raise("serve_fault", target=4)      # exhausted: no-op
        plan2 = faults.parse_plan("ckpt_fail:1")
        with pytest.raises(faults.InjectedFault):
            plan2.maybe_raise("ckpt_fail", target=1,
                              exc=faults.InjectedFault)

    def test_active_plan_tracks_env(self, fault_env):
        fault_env("step_fault:1")
        assert faults.active_plan().specs[0].kind == "step_fault"
        fault_env("")
        assert not faults.active_plan()
        fault_env("not-a-kind:1")
        with pytest.raises(ValueError):
            faults.active_plan()


# ---------------------------------------------------------------------------
# torn-tail repair
# ---------------------------------------------------------------------------

class TestRepairJsonlTail:
    def test_missing_and_empty(self, tmp_path):
        assert repair_jsonl_tail(str(tmp_path / "nope.jsonl")) == 0
        p = tmp_path / "empty.jsonl"
        p.write_text("")
        assert repair_jsonl_tail(str(p)) == 0

    def test_clean_file_untouched(self, tmp_path):
        p = tmp_path / "a.jsonl"
        p.write_text('{"x": 1}\n{"x": 2}\n')
        assert repair_jsonl_tail(str(p)) == 0
        assert p.read_text() == '{"x": 1}\n{"x": 2}\n'

    def test_torn_fragment_truncated(self, tmp_path):
        p = tmp_path / "a.jsonl"
        p.write_text('{"x": 1}\n{"x": 2, "name"')
        torn = repair_jsonl_tail(str(p))
        assert torn == len('{"x": 2, "name"')
        assert p.read_text() == '{"x": 1}\n'

    def test_valid_json_fragment_completed(self, tmp_path):
        # crash fell between the payload write and the newline: the
        # record is intact and must be kept, not truncated
        p = tmp_path / "a.jsonl"
        p.write_text('{"x": 1}\n{"x": 2}')
        assert repair_jsonl_tail(str(p)) == 0
        assert p.read_text() == '{"x": 1}\n{"x": 2}\n'

    def test_whole_file_torn(self, tmp_path):
        p = tmp_path / "a.jsonl"
        p.write_text('{"x": 1, "na')
        assert repair_jsonl_tail(str(p)) > 0
        assert p.read_text() == ""

    def test_fsync_append_repairs_first(self, tmp_path):
        p = str(tmp_path / "deep" / "a.jsonl")
        fsync_append(p, '{"x": 1}')
        with open(p, "a") as f:
            f.write('{"torn')
        fsync_append(p, '{"x": 2}')
        with open(p) as f:
            assert [json.loads(ln) for ln in f] == [{"x": 1}, {"x": 2}]


# ---------------------------------------------------------------------------
# store-level torn tails (satellite: trace.jsonl AND sweep.jsonl)
# ---------------------------------------------------------------------------

def _trace_record(run_id: str):
    from repro.trace.store import SCHEMA_VERSION, TraceRecord
    return TraceRecord(
        schema_version=SCHEMA_VERSION, run_id=run_id, timestamp=0.0,
        git_sha="t", config="c", machine="m", mesh={}, host={},
        phases={"fwd": {"wall_s": 0.125}}, meta={})


class TestStoreTornTail:
    @pytest.mark.parametrize("filename", ["trace.jsonl", "sweep.jsonl"])
    def test_torn_final_line_recovery(self, tmp_path, filename):
        """Truncate the final line mid-record: the store opens, drops
        exactly the torn record, and subsequent appends round-trip."""
        from repro.trace.store import TraceStore
        store = TraceStore(str(tmp_path / filename))
        store.append(_trace_record("r1"))
        torn = _trace_record("r2").to_json()
        with open(store.path, "a") as f:
            f.write(torn[:len(torn) // 2])       # mid-record, no newline
        assert [r.run_id for r in store.records()] == ["r1"]
        store.append(_trace_record("r3"))
        assert [r.run_id for r in store.records()] == ["r1", "r3"]

    def test_injected_torn_tail(self, tmp_path, fault_env):
        from repro.trace.store import TraceStore
        store = TraceStore(str(tmp_path / "trace.jsonl"))
        store.append(_trace_record("a"))
        fault_env("torn_tail:trace")             # next append crashes torn
        with pytest.raises(faults.InjectedFault):
            store.append(_trace_record("b"))
        raw = open(store.path).read()
        assert not raw.endswith("\n")            # torn bytes really landed
        store.append(_trace_record("c"))         # spec exhausted: repairs
        assert [r.run_id for r in store.records()] == ["a", "c"]

    def test_injection_respects_store_kind(self, tmp_path, fault_env):
        from repro.trace.store import TraceStore
        fault_env("torn_tail:sweep")
        trace = TraceStore(str(tmp_path / "trace.jsonl"))
        trace.append(_trace_record("a"))         # wrong store: no fault
        sweep = TraceStore(str(tmp_path / "sweep.jsonl"))
        with pytest.raises(faults.InjectedFault):
            sweep.append(_trace_record("b"))


# ---------------------------------------------------------------------------
# campaign journal
# ---------------------------------------------------------------------------

class TestCampaignJournal:
    def test_replay_folds_lifecycle(self, tmp_path):
        j = CampaignJournal(str(tmp_path / "sweep_journal.jsonl"))
        j.log("attempt", sweep="s", point="p1", attempt=0)
        j.log("fail", sweep="s", point="p1", attempt=0, reason="boom")
        j.log("attempt", sweep="s", point="p1", attempt=1)
        j.log("done", sweep="s", point="p1", attempt=1, run_id="r-9")
        j.log("attempt", sweep="s", point="p2", attempt=0)
        j.log("quarantine", sweep="s", point="p2", attempt=0,
              reason="poison")
        state = j.replay("s")
        assert state.done == {"p1": "r-9"}
        assert state.attempts == {"p1": 2, "p2": 1}
        assert state.quarantined == {"p2": "poison"}
        assert "p1" not in state.failures        # done clears the failure

    def test_done_rehabilitates_quarantine(self, tmp_path):
        j = CampaignJournal(str(tmp_path / "j.jsonl"))
        j.log("quarantine", sweep="s", point="p", reason="x")
        j.log("done", sweep="s", point="p", run_id="r")
        state = j.replay("s")
        assert state.done == {"p": "r"} and not state.quarantined

    def test_summary_shape(self, tmp_path):
        j = CampaignJournal(str(tmp_path / "j.jsonl"))
        j.log("attempt", sweep="s", point="p", attempt=0)
        j.log("quarantine", sweep="s", point="p", reason="dead")
        s = j.summary("s")
        assert s["sweep"] == "s" and s["done"] == 0
        assert s["quarantined"] == [{"point": "p", "reason": "dead",
                                     "attempts": 1}]
        assert s["failed"] == []

    def test_sweeps_are_isolated(self, tmp_path):
        j = CampaignJournal(str(tmp_path / "j.jsonl"))
        j.log("done", sweep="a", point="p", run_id="r")
        assert j.replay("b").n_done == 0

    def test_unknown_event_rejected(self, tmp_path):
        j = CampaignJournal(str(tmp_path / "j.jsonl"))
        with pytest.raises(ValueError):
            j.log("explode", sweep="s", point="p")

    def test_torn_journal_tail_skipped(self, tmp_path):
        j = CampaignJournal(str(tmp_path / "j.jsonl"))
        j.log("done", sweep="s", point="p", run_id="r")
        with open(j.path, "a") as f:
            f.write('{"event": "done", "sweep": "s", "point": "q"')
        assert j.replay("s").done == {"p": "r"}
        j.log("done", sweep="s", point="p3", run_id="r3")
        assert set(j.replay("s").done) == {"p", "p3"}

    def test_journal_path_beside_store(self, tmp_path):
        store = str(tmp_path / "ws" / "sweep.jsonl")
        assert journal_path_for(store) == str(
            tmp_path / "ws" / "sweep_journal.jsonl")


# ---------------------------------------------------------------------------
# supervised pool (module-level worker fns: spawn pickles by reference)
# ---------------------------------------------------------------------------

def _wd_double(x):
    return x * 2


def _wd_boom(x):
    raise RuntimeError(f"boom {x}")


def _wd_exit(x):
    os._exit(faults.CRASH_EXIT_CODE)


def _wd_sleep(secs):
    time.sleep(secs)
    return "woke"


class TestSupervisedPool:
    def test_rejects_zero_workers(self):
        with pytest.raises(ValueError):
            SupervisedPool(_wd_double, 0)

    def test_ok_and_error_outcomes(self):
        with SupervisedPool(_wd_double, 1) as pool:
            out = pool.run([("a", (3,))])
            assert out["a"].ok and out["a"].value == 6
            spawned = pool._spawns
            out = pool.run([("b", (4,))])        # warm worker reused
            assert out["b"].value == 8 and pool._spawns == spawned
        with SupervisedPool(_wd_boom, 1) as pool:
            out = pool.run([("a", (1,))])
            assert out["a"].kind == "ok" and "boom 1" in out["a"].error

    def test_crash_detected_and_replaced(self):
        """An os._exit worker (even one dying within the poll quantum)
        must settle its task as a crash, not hang the pool."""
        with SupervisedPool(_wd_exit, 1) as pool:
            out = pool.run([("a", (0,)), ("b", (1,))])
            for key in ("a", "b"):
                assert out[key].kind == "crash"
                assert f"exit code {faults.CRASH_EXIT_CODE}" in \
                    out[key].error
            assert pool.replacements >= 2
            # the pool still serves work after the crashes
            pool.worker_fn = _wd_double
        with SupervisedPool(_wd_sleep, 1) as pool:
            assert pool.run([("z", (0.0,))])["z"].value == "woke"

    def test_refuses_when_this_process_holds_the_chip(self, monkeypatch):
        from repro.resilience import watchdog
        assert not watchdog.holds_accelerator()      # the CPU test backend
        monkeypatch.setattr(watchdog, "holds_accelerator", lambda: True)
        with pytest.raises(RuntimeError, match="holds the accelerator"):
            SupervisedPool(_wd_double, 1)

    def test_slow_start_does_not_count_against_the_deadline(self):
        """A worker's start (a fresh interpreter, its imports, the
        initializer) may outlast a short task deadline.  Reaped at the
        deadline, it would be respawned into the same slow start and the
        task would end as a crash without ever running."""
        with SupervisedPool(_wd_double, 1, deadline_s=0.5, init=_wd_sleep,
                            initargs=(2.0,)) as pool:
            out = pool.run([("a", (3,))])
        assert out["a"].kind == "ok" and out["a"].value == 6
        assert pool.replacements == 0

    def test_deadline_kills_hung_worker(self):
        t0 = time.monotonic()
        with SupervisedPool(_wd_sleep, 1, deadline_s=0.5) as pool:
            out = pool.run([("hung", (60.0,))])
        assert out["hung"].kind == "timeout"
        assert "deadline" in out["hung"].error
        assert time.monotonic() - t0 < 30        # not 60: it was killed


# ---------------------------------------------------------------------------
# checkpoint integrity + GC
# ---------------------------------------------------------------------------

def _tiny_tree():
    import numpy as np
    return {"w": np.arange(6, dtype="float32").reshape(2, 3),
            "b": np.ones(3, dtype="float32")}


class TestCheckpointIntegrity:
    def test_digest_roundtrip(self, tmp_path):
        from repro.checkpoint import checkpointer as ckpt
        import numpy as np
        d = str(tmp_path)
        ckpt.save(d, 1, _tiny_tree())
        with open(os.path.join(d, "step_00000001", "manifest.json")) as f:
            assert len(json.load(f)["digest"]) == 64
        tree, meta = ckpt.restore(d, _tiny_tree())
        np.testing.assert_array_equal(tree["w"], _tiny_tree()["w"])

    def test_digest_mismatch_rejected(self, tmp_path):
        from repro.checkpoint import checkpointer as ckpt
        d = str(tmp_path)
        ckpt.save(d, 1, _tiny_tree())
        mpath = os.path.join(d, "step_00000001", "manifest.json")
        with open(mpath) as f:
            manifest = json.load(f)
        manifest["digest"] = "0" * 64
        with open(mpath, "w") as f:
            json.dump(manifest, f)
        with pytest.raises(ckpt.CheckpointCorrupt):
            ckpt.restore(d, _tiny_tree())
        tree, _ = ckpt.restore(d, _tiny_tree(), verify=False)
        assert tree is not None                  # explicit opt-out works

    def test_digestless_manifest_still_loads(self, tmp_path):
        from repro.checkpoint import checkpointer as ckpt
        d = str(tmp_path)
        ckpt.save(d, 1, _tiny_tree())
        mpath = os.path.join(d, "step_00000001", "manifest.json")
        with open(mpath) as f:
            manifest = json.load(f)
        del manifest["digest"]                   # pre-§17 checkpoint
        with open(mpath, "w") as f:
            json.dump(manifest, f)
        tree, _ = ckpt.restore(d, _tiny_tree())
        assert tree is not None

    def test_gc_keep_parameter(self, tmp_path):
        from repro.checkpoint import checkpointer as ckpt
        d = str(tmp_path)
        for step in range(1, 6):
            ckpt.save(d, step, _tiny_tree(), keep=2)
        assert ckpt.available_steps(d) == [4, 5]
        d2 = str(tmp_path / "nogc")
        for step in range(1, 4):
            ckpt.save(d2, step, _tiny_tree(), keep=0)
        assert ckpt.available_steps(d2) == [1, 2, 3]

    def test_gc_never_deletes_latest_target(self, tmp_path):
        from repro.checkpoint import checkpointer as ckpt
        d = str(tmp_path)
        for step in range(1, 5):
            ckpt.save(d, step, _tiny_tree(), keep=0)
        # a concurrent restore just resolved `latest` to the oldest step
        with open(os.path.join(d, "latest"), "w") as f:
            f.write("step_00000001")
        ckpt._gc(d, keep=1)
        assert ckpt.available_steps(d) == [1, 4]  # pointed + newest

    def test_async_healthy_surfaces_write_error(self, tmp_path, fault_env):
        from repro.checkpoint import checkpointer as ckpt
        fault_env("ckpt_fail:7")
        a = ckpt.AsyncCheckpointer()
        assert a.healthy()
        a.save(str(tmp_path), 7, _tiny_tree())
        deadline = time.monotonic() + 10
        while a.healthy() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not a.healthy()
        with pytest.raises(faults.InjectedFault):
            a.wait()
        assert a.healthy()                       # error surfaced once


# ---------------------------------------------------------------------------
# trainer resilience (granite-8b smoke — same fixture family as test_train)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def train_setup():
    import jax
    from repro.configs.base import RunConfig, ShapeSpec
    from repro.configs.registry import get_smoke
    from repro.models import build
    cfg = get_smoke("granite-8b")
    model = build(cfg)
    shape = ShapeSpec("t", 32, 8, "train")
    return cfg, model, shape


class TestTrainerResilience:
    def test_transient_retry_is_bit_identical(self, train_setup, fault_env):
        from repro.configs.base import RunConfig
        from repro.data.pipeline import TokenStream
        from repro.train.trainer import Trainer
        cfg, model, shape = train_setup
        stream = TokenStream(cfg, shape, batch=8)
        run = RunConfig(amp="O1")
        clean = Trainer(model, run, stream, lr=1e-3).fit(
            5, log_every=0, log=lambda *_: None)
        fault_env("step_fault:2x2")
        faulted = Trainer(model, run, stream, lr=1e-3,
                          retry_backoff_s=0.0).fit(
            5, log_every=0, log=lambda *_: None)
        assert faulted.retries == 2
        assert [x.hex() for x in faulted.losses] == \
               [x.hex() for x in clean.losses]

    def test_exhausted_retries_raise(self, train_setup, fault_env):
        from repro.configs.base import RunConfig
        from repro.data.pipeline import TokenStream
        from repro.train.trainer import Trainer
        cfg, model, shape = train_setup
        fault_env("step_fault:1x-1")             # never stops firing
        t = Trainer(model, RunConfig(amp="O1"), TokenStream(cfg, shape, 8),
                    lr=1e-3, step_retries=1, retry_backoff_s=0.0)
        with pytest.raises(faults.TransientFault):
            t.fit(3, log_every=0, log=lambda *_: None)
        assert t.report.steps == 1               # step 0 landed, 1 did not

    def test_corrupt_newest_ckpt_falls_back(self, train_setup):
        from repro.configs.base import RunConfig
        from repro.data.pipeline import TokenStream
        from repro.train.trainer import Trainer
        cfg, model, shape = train_setup
        stream = TokenStream(cfg, shape, batch=8)
        run = RunConfig(amp="O1")
        with tempfile.TemporaryDirectory() as d:
            Trainer(model, run, stream, ckpt_dir=d, ckpt_every=4,
                    lr=1e-3).fit(8, log_every=0, log=lambda *_: None)
            mpath = os.path.join(d, "step_00000008", "manifest.json")
            with open(mpath) as f:
                manifest = json.load(f)
            manifest["digest"] = "f" * 64        # bit-rot the newest
            with open(mpath, "w") as f:
                json.dump(manifest, f)
            t2 = Trainer(model, run, stream, ckpt_dir=d, ckpt_every=4,
                         lr=1e-3)
            assert t2.report.resumed_from == 4   # older verified ckpt
            assert [s for s, _ in t2.report.skipped_ckpts] == [8]

    def test_dead_ckpt_writer_fails_promptly(self, train_setup, fault_env):
        from repro.configs.base import RunConfig
        from repro.data.pipeline import TokenStream
        from repro.train.trainer import Trainer
        cfg, model, shape = train_setup
        fault_env("ckpt_fail:2")
        with tempfile.TemporaryDirectory() as d:
            t = Trainer(model, RunConfig(amp="O1"),
                        TokenStream(cfg, shape, 8), ckpt_dir=d,
                        ckpt_every=2, lr=1e-3)
            with pytest.raises(faults.InjectedFault):
                t.fit(12, log_every=1, log=lambda *_: None)
            assert t.report.steps < 12           # not at the very end


# ---------------------------------------------------------------------------
# sweep engine: resume + failure summary
# ---------------------------------------------------------------------------

class TestSweepResume:
    @pytest.fixture(scope="class")
    def campaign(self, tmp_path_factory):
        from repro.sweep.engine import run_sweep
        from repro.sweep.spec import SweepSpec
        root = tmp_path_factory.mktemp("resume-ws")
        spec = SweepSpec(name="resume-test", configs=("minitron-4b",),
                         seqs=(16,), batches=(2,), amps=("O1",),
                         meshes=((1, 1),), machine="cpu-host",
                         measure=False, smoke=True)
        store = str(root / "sweep.jsonl")
        first = run_sweep(spec, store_path=store, workers=0,
                          cache_dir=str(root / "cache"))
        return spec, store, root, first

    def test_first_run_journals_done(self, campaign):
        spec, store, root, first = campaign
        assert first.n_ok == 1 and first.n_resumed == 0
        journal = CampaignJournal(journal_path_for(store))
        assert journal.replay(spec.name).n_done == 1

    def test_resume_skips_completed_points(self, campaign):
        from repro.sweep.engine import run_sweep
        from repro.trace.store import TraceStore
        spec, store, root, first = campaign
        again = run_sweep(spec, store_path=store, workers=0,
                          cache_dir=str(root / "cache"), resume=True)
        assert again.n_ok == 1 and again.n_resumed == 1
        assert again.results[0].run_id == first.results[0].run_id
        # zero duplicate records landed
        assert len(TraceStore(store).records()) == 1

    def test_store_scan_covers_missing_journal(self, campaign):
        from repro.sweep.engine import run_sweep
        from repro.trace.store import TraceStore
        spec, store, root, first = campaign
        lost = str(root / "lost_journal.jsonl")  # journal never existed
        again = run_sweep(spec, store_path=store, workers=0,
                          cache_dir=str(root / "cache"), resume=True,
                          journal_path=lost)
        assert again.n_resumed == 1              # store scan alone suffices
        assert len(TraceStore(store).records()) == 1


def test_sweep_runs_inline_in_the_process_holding_the_chip(monkeypatch,
                                                          tmp_path):
    """Asked for workers by a process on an accelerator, a sweep runs every
    point in that process: a worker could never reach the chip."""
    from repro.sweep import engine
    from repro.sweep.spec import SweepSpec

    def no_pool(*a, **k):
        raise AssertionError("a worker pool was started")

    monkeypatch.setattr(engine, "holds_accelerator", lambda: True)
    monkeypatch.setattr(engine, "SupervisedPool", no_pool)
    spec = SweepSpec(name="chip-inline", configs=("minitron-4b",),
                     seqs=(16,), batches=(2,), amps=("O1",),
                     meshes=((1, 1),), machine="cpu-host", measure=False,
                     smoke=True)
    res = engine.run_sweep(spec, store_path=str(tmp_path / "sweep.jsonl"),
                           workers=2, cache_dir=str(tmp_path / "cache"))
    assert res.n_ok == 1


class TestFailureSummary:
    def test_one_line_per_failed_point(self):
        from repro.sweep.engine import PointResult, SweepResult
        from repro.sweep.spec import SweepPoint
        p_ok = SweepPoint(config="a", seq=16, batch=2, amp="O1",
                          mesh=(1, 1), machine="cpu-host",
                          measured=False, smoke=True)
        p_bad = SweepPoint(config="b", seq=16, batch=2, amp="O1",
                           mesh=(1, 1), machine="cpu-host",
                           measured=False, smoke=True)
        res = SweepResult([
            PointResult(p_ok, run_id="r"),
            PointResult(p_bad, error="Traceback...\nValueError: nope\n",
                        attempts=2, quarantined=True),
        ], skipped=[])
        lines = res.failure_summary()
        assert len(lines) == 1
        assert "quarantined after 2 attempt(s)" in lines[0]
        assert lines[0].endswith("ValueError: nope")
        assert res.n_quarantined == 1 and res.n_failed == 1


class TestSweepCli:
    def test_bad_fault_plan_exits_2(self, capsys):
        from repro.sweep import cli
        rc = cli.main(["run", "--configs", "minitron-4b",
                       "--faults", "explode:1"])
        assert rc == 2
        assert "unknown fault kind" in capsys.readouterr().err

    def test_resilience_flags_parse(self):
        # the real parser accepts the new flags (the full chaos loop runs
        # in benchmarks/chaos_smoke.py, not under pytest)
        from repro.sweep import cli
        with pytest.raises(SystemExit) as e:
            cli.main(["run", "--resume", "--deadline", "45",
                      "--retries", "2", "--backoff", "0.5",
                      "--journal", "/tmp/j.jsonl", "--help"])
        assert e.value.code == 0


# ---------------------------------------------------------------------------
# serve engine tick retry
# ---------------------------------------------------------------------------

class TestServeTickRetry:
    def test_transient_tick_fault_is_retried(self, fault_env):
        import jax
        import numpy as np
        from repro.configs.base import RunConfig
        from repro.configs.registry import get_smoke
        from repro.models import build
        from repro.models.params import init
        from repro.serve.engine import Engine, Request
        cfg = get_smoke("minitron-4b")
        model = build(cfg)
        params = init(jax.random.PRNGKey(0), model.spec)
        run = RunConfig(amp="O1")
        prompt = np.array([5, 7, 9], np.int32)

        clean_req = Request(0, prompt, max_new=3)
        clean = Engine(cfg, run, params, n_slots=1, max_len=16)
        clean.run_trace([clean_req])

        fault_env("serve_fault:1x2")
        req = Request(0, prompt, max_new=3)
        eng = Engine(cfg, run, params, n_slots=1, max_len=16)
        eng.run_trace([req])
        assert eng.retried_ticks == 2
        # the retried tick replayed cleanly: identical generation
        assert req.out == clean_req.out

    def test_exhausted_tick_retries_raise(self, fault_env):
        import jax
        import numpy as np
        from repro.configs.base import RunConfig
        from repro.configs.registry import get_smoke
        from repro.models import build
        from repro.models.params import init
        from repro.serve.engine import Engine, Request
        cfg = get_smoke("minitron-4b")
        model = build(cfg)
        params = init(jax.random.PRNGKey(0), model.spec)
        eng = Engine(cfg, RunConfig(amp="O1"), params, n_slots=1,
                     max_len=16, tick_retries=1)
        fault_env("serve_fault:0x-1")
        with pytest.raises(faults.TransientFault):
            eng.run_trace([Request(0, np.array([5], np.int32), max_new=2)])
