"""chaos_smoke: the resilience layer end to end, under injected faults.

Two legs, both asserting that a faulted run converges to the *same bytes*
as a fault-free one (docs/DESIGN.md §17):

* **sweep** — a 3-point analytical campaign runs once fault-free
  (baseline), then again under an injected plan: point 0's worker hard-
  crashes on its first attempt, point 1 hangs past the per-point deadline
  (killed and replaced by the watchdog), point 2 crashes on *every*
  attempt and is quarantined.  A final ``resume`` pass with the plan
  cleared skips the two completed points via the campaign journal and
  rehabilitates the quarantined one.  Asserts: exactly one record per
  point key (zero duplicates across three invocations), phase payloads
  byte-identical to the baseline, quarantine visible in the journal
  summary.
* **train** — a 12-step smoke train loop runs uninterrupted in a child
  process (reference loss), then a sibling child is hard-crashed at step
  5 by ``crash_step`` and a third child auto-resumes from the last
  verified checkpoint: its final loss must be bit-identical (float hex)
  to the reference.  In-process legs cover transient step faults retried
  with backoff (losses again bitwise equal), a checkpoint-write fault
  surfaced promptly through ``AsyncCheckpointer.healthy()``, and a
  torn-tail store append repaired on the next write.

The journal summaries land in ``chaos_report.json`` (workspace root when
``REPRO_WORKSPACE`` is pinned, else ``benchmarks/results``) — CI uploads
it as the campaign-health artifact.
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import subprocess
import sys
import tempfile
import time

from benchmarks.common import Row

SWEEP_CONFIGS = ("minitron-4b", "mamba2-1.3b", "glm4-9b")
TRAIN_STEPS = 12
CRASH_AT = 5
#: sweep chaos plan: point 0 crashes once, point 1 hangs (far past the
#: deadline) once, point 2 crashes on every attempt → quarantine
SWEEP_PLAN = "crash_point:0;hang_point:1:600x1;crash_point:2x-1"
SWEEP_DEADLINE_S = 30.0

_FINAL_RE = re.compile(
    r"CHAOS_FINAL steps=(\d+) loss=(\S+) resumed_from=(\S+)")


@contextlib.contextmanager
def _fault_env(value: str | None):
    """Temporarily set/clear REPRO_FAULTS (benchmarks must not leak a
    fault plan into later suites)."""
    from repro.resilience.faults import FAULT_ENV
    prev = os.environ.get(FAULT_ENV)
    try:
        if value is None:
            os.environ.pop(FAULT_ENV, None)
        else:
            os.environ[FAULT_ENV] = value
        yield
    finally:
        if prev is None:
            os.environ.pop(FAULT_ENV, None)
        else:
            os.environ[FAULT_ENV] = prev


def _phases_bytes(rec) -> str:
    return json.dumps(rec.phases, sort_keys=True)


def _sweep_spec(name: str):
    from repro.sweep.spec import SweepSpec
    return SweepSpec(name=name, configs=SWEEP_CONFIGS, seqs=(16,),
                     batches=(2,), amps=("O1",), meshes=((1, 1),),
                     machine="cpu-host", measure=False, smoke=True)


def _run_sweep_leg(rows: list[Row], report: dict) -> None:
    from repro.resilience.journal import CampaignJournal, journal_path_for
    from repro.sweep.engine import run_sweep
    from repro.trace.store import TraceStore

    with tempfile.TemporaryDirectory() as d:
        base_store = os.path.join(d, "baseline", "sweep.jsonl")
        chaos_store = os.path.join(d, "chaos", "sweep.jsonl")

        # fault-free baseline, inline: the byte-identity reference
        with _fault_env(None):
            base = run_sweep(_sweep_spec("chaos"), store_path=base_store,
                             workers=0, cache_dir=None)
        assert base.n_ok == 3 and base.n_failed == 0, \
            base.failure_summary()
        base_phases = {r.meta["sweep_point"]: _phases_bytes(r)
                       for r in TraceStore(base_store).records()}

        # chaos pass: crash + hang + poison point, one supervised worker
        t0 = time.time()
        with _fault_env(SWEEP_PLAN):
            chaos = run_sweep(_sweep_spec("chaos"), store_path=chaos_store,
                              workers=1, cache_dir=None,
                              deadline_s=SWEEP_DEADLINE_S, retries=1,
                              backoff_s=0.1)
        t_chaos = time.time() - t0
        assert chaos.n_ok == 2 and chaos.n_quarantined == 1, \
            (chaos.n_ok, chaos.n_quarantined, chaos.failure_summary())
        by_idx = {i: r for i, r in enumerate(chaos.results)}
        assert by_idx[0].ok and by_idx[0].attempts == 2, \
            "point 0 must survive its injected crash on retry"
        assert by_idx[1].ok and by_idx[1].attempts == 2, \
            "point 1 must survive its deadline kill on retry"
        assert by_idx[2].quarantined and by_idx[2].attempts == 2

        journal = CampaignJournal(journal_path_for(chaos_store))
        reasons = [e.get("reason", "") for e in journal.entries("chaos")
                   if e["event"] == "fail"]
        assert any("deadline" in r for r in reasons), reasons
        report["sweep_after_chaos"] = journal.summary("chaos")
        assert len(report["sweep_after_chaos"]["quarantined"]) == 1

        # resume with the plan cleared: skip the done, finish the poisoned
        t0 = time.time()
        with _fault_env(None):
            final = run_sweep(_sweep_spec("chaos"), store_path=chaos_store,
                              workers=1, cache_dir=None, resume=True,
                              deadline_s=SWEEP_DEADLINE_S, retries=1)
        t_resume = time.time() - t0
        assert final.n_ok == 3 and final.n_failed == 0, \
            final.failure_summary()
        assert final.n_resumed == 2, \
            "the two completed points must be skipped, not re-run"
        report["sweep_after_resume"] = journal.summary("chaos")
        assert not report["sweep_after_resume"]["quarantined"]

        # zero duplicates across three invocations; bytes match baseline
        recs = TraceStore(chaos_store).records()
        keys = [r.meta["sweep_point"] for r in recs]
        assert len(keys) == 3 and len(set(keys)) == 3, \
            f"expected exactly one record per point, got {keys}"
        for r in recs:
            assert _phases_bytes(r) == base_phases[r.meta["sweep_point"]], \
                f"{r.meta['label']}: chaos phases differ from baseline"

        rows.append(("chaos_smoke/sweep_chaos", t_chaos * 1e6,
                     "crash+hang+quarantine"))
        rows.append(("chaos_smoke/sweep_resume", t_resume * 1e6,
                     f"{final.n_resumed}resumed"))


def _train_child_cmd(ckpt_dir: str) -> list[str]:
    return [sys.executable, "-m", "benchmarks.chaos_smoke",
            "--train-child", ckpt_dir, "--steps", str(TRAIN_STEPS)]


def _run_child(ckpt_dir: str, fault: str | None):
    from repro.resilience.faults import FAULT_ENV
    env = dict(os.environ)
    env.pop(FAULT_ENV, None)
    if fault:
        env[FAULT_ENV] = fault
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(repo_root, "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(_train_child_cmd(ckpt_dir), cwd=repo_root,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def _parse_final(proc) -> tuple[int, str, str]:
    m = _FINAL_RE.search(proc.stdout)
    assert m, f"no CHAOS_FINAL line in child output:\n{proc.stdout}\n" \
              f"{proc.stderr}"
    return int(m.group(1)), m.group(2), m.group(3)


def _run_train_leg(rows: list[Row], report: dict) -> None:
    from repro.resilience.faults import CRASH_EXIT_CODE

    with tempfile.TemporaryDirectory() as d:
        # reference: uninterrupted child
        t0 = time.time()
        ref = _run_child(os.path.join(d, "ref"), fault=None)
        assert ref.returncode == 0, ref.stderr
        ref_steps, ref_loss, ref_resumed = _parse_final(ref)
        assert ref_steps == TRAIN_STEPS and ref_resumed == "None"

        # crash at step 5, then auto-resume from the last checkpoint
        crash_dir = os.path.join(d, "crash")
        crashed = _run_child(crash_dir, fault=f"crash_step:{CRASH_AT}")
        assert crashed.returncode == CRASH_EXIT_CODE, \
            (crashed.returncode, crashed.stderr)
        resumed = _run_child(crash_dir, fault=None)
        assert resumed.returncode == 0, resumed.stderr
        res_steps, res_loss, res_resumed = _parse_final(resumed)
        assert res_resumed != "None", "second child must resume, not restart"
        assert res_loss == ref_loss, \
            (f"resumed loss {res_loss} != uninterrupted {ref_loss} "
             "(bitwise float hex)")
        t_train = time.time() - t0
        report["train"] = {"steps": TRAIN_STEPS, "crash_at": CRASH_AT,
                           "resumed_from": int(res_resumed),
                           "loss_hex": ref_loss, "bit_identical": True}
        rows.append(("chaos_smoke/train_crash_resume", t_train * 1e6,
                     f"resume@{res_resumed};loss={ref_loss[:10]}"))


def _run_inprocess_legs(rows: list[Row], report: dict) -> None:
    import jax

    from repro.checkpoint.checkpointer import AsyncCheckpointer
    from repro.configs.base import RunConfig, ShapeSpec
    from repro.configs.registry import get_smoke
    from repro.data.pipeline import TokenStream
    from repro.models import build
    from repro.resilience.faults import InjectedFault
    from repro.train.trainer import Trainer

    cfg = get_smoke("granite-8b")
    model = build(cfg)
    shape = ShapeSpec("t", 32, 8, "train")
    run = RunConfig(amp="O1")
    stream = TokenStream(cfg, shape, batch=8)
    quiet = lambda *_: None

    # transient step faults: retried past, losses bitwise unchanged
    with _fault_env(None):
        clean = Trainer(model, run, stream, lr=1e-3).fit(
            6, log_every=0, log=quiet)
    t0 = time.time()
    with _fault_env("step_fault:3x2"):
        faulted = Trainer(model, run, stream, lr=1e-3).fit(
            6, log_every=0, log=quiet)
    t_retry = time.time() - t0
    assert faulted.retries == 2, faulted.retries
    assert [x.hex() for x in faulted.losses] == \
           [x.hex() for x in clean.losses], \
        "retried losses must be bit-identical to the fault-free run"
    rows.append(("chaos_smoke/train_transient_retry", t_retry * 1e6,
                 f"{faulted.retries}retries;bitwise-equal"))

    # checkpoint-write fault: healthy() surfaces it at the log interval
    with tempfile.TemporaryDirectory() as d, _fault_env("ckpt_fail:4"):
        t = Trainer(model, run, stream, ckpt_dir=d, ckpt_every=4, lr=1e-3)
        try:
            t.fit(TRAIN_STEPS, log_every=1, log=quiet)
        except InjectedFault:
            pass
        else:
            raise AssertionError("injected ckpt_fail never surfaced")
        assert t.report.steps < TRAIN_STEPS, \
            "a dead checkpointer must fail the run promptly, not at the end"
    rows.append(("chaos_smoke/ckpt_fail_prompt", 0.0,
                 f"failed@step{t.report.steps}<{TRAIN_STEPS}"))

    # torn-tail append: injected torn write, repaired on the next append
    from repro.trace.store import TraceStore, record_from_payloads
    with tempfile.TemporaryDirectory() as d:
        store = TraceStore(os.path.join(d, "trace.jsonl"))
        mk = lambda name: record_from_payloads(
            name, {"fwd": {"wall_s": 1.0}}, machine="cpu-host")
        with _fault_env(None):
            store.append(mk("a"))
        with _fault_env("torn_tail:trace"):
            try:
                store.append(mk("b"))
            except InjectedFault:
                pass
            else:
                raise AssertionError("torn_tail never fired")
        with _fault_env(None):
            store.append(mk("c"))
            got = [r.config for r in store.records()]
        assert got == ["a", "c"], got
    rows.append(("chaos_smoke/torn_tail_repair", 0.0, "dropped=1;kept=2"))
    report["inprocess"] = {"transient_retries": faulted.retries,
                           "torn_tail": "repaired"}


def _report_path() -> str:
    from repro.session.workspace import env_workspace_root
    root = env_workspace_root() or "benchmarks/results"
    return os.path.join(root, "chaos_report.json")


def main() -> list[Row]:
    from repro.resilience.watchdog import holds_accelerator
    if holds_accelerator():
        # the legs kill and restart worker and trainer processes, which
        # could never reach a chip this process already holds
        raise RuntimeError("chaos_smoke drives child processes and runs "
                           "on the CPU host only (JAX_PLATFORMS=cpu)")
    rows: list[Row] = []
    report: dict = {}
    _run_sweep_leg(rows, report)
    _run_train_leg(rows, report)
    _run_inprocess_legs(rows, report)
    path = _report_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    print(f"# chaos report -> {path}", file=sys.stderr)
    return rows


def _train_child(ckpt_dir: str, steps: int) -> int:
    """Child-process entry: run (or resume) the smoke train loop and
    print the bit-exact final loss.  An injected ``crash_step`` exits
    hard with CRASH_EXIT_CODE before this prints anything."""
    from repro.configs.base import RunConfig, ShapeSpec
    from repro.configs.registry import get_smoke
    from repro.data.pipeline import TokenStream
    from repro.models import build
    from repro.train.trainer import Trainer

    cfg = get_smoke("granite-8b")
    model = build(cfg)
    stream = TokenStream(cfg, ShapeSpec("t", 32, 8, "train"), batch=8)
    t = Trainer(model, RunConfig(amp="O1"), stream, ckpt_dir=ckpt_dir,
                ckpt_every=4, lr=1e-3)
    rep = t.fit(steps, log_every=0, log=lambda *_: None)
    print(f"CHAOS_FINAL steps={int(t.state.step)} "
          f"loss={rep.losses[-1].hex()} resumed_from={rep.resumed_from}")
    return 0


if __name__ == "__main__":
    if "--train-child" in sys.argv:
        import argparse
        ap = argparse.ArgumentParser()
        ap.add_argument("--train-child", required=True, metavar="CKPT_DIR")
        ap.add_argument("--steps", type=int, default=TRAIN_STEPS)
        args = ap.parse_args()
        sys.exit(_train_child(args.train_child, args.steps))
    from benchmarks.common import emit
    emit(main())
