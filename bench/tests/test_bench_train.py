"""A training run at a test's size on the CPU, through everything but the
look for a chip: sound, with the control in the program's place, with a
step that returns its state unchanged, with faults that start after the
set-up steps, and with a trainer that no longer calls the feed."""

import os

import pytest

import benchkit
from benchlib import harness

drv = harness.load_module(os.path.join(benchkit.BENCH, "runners", "train.py"))
SEED = 2 ** 31 + 9


@pytest.fixture(scope="module")
def built():
    c = benchkit.ctx("deepcam-tiny", "tiny-b1", seed=SEED, seconds=0.5)
    return c, drv.Program(c), drv.Reference(c)


def test_sound_run_is_correct(built):
    c, prog, ref = built
    out = drv.run(c, prog, ref)
    assert out.correct, out.checks
    assert out.attempted >= 1 and out.failed == 0
    assert set(out.end_to_end) == {"images_per_s", "setup_s"}
    assert set(out.checks) == {"loss_gap", "grad_gap", "change_gap",
                               "steps_lost", "leaves_unmoved"}
    assert out.checks["steps_lost"] == (0.0, 0.0)
    assert out.checks["leaves_unmoved"] == (0.0, 0.0)


def test_control_fails_a_limit(built):
    c, prog, ref = built
    prog.start(SEED)
    want = ref.readings(SEED, prog.pool)
    low = ref.readings(SEED, prog.pool,
                       quant=harness.reference_of(c.cfg).fp8_conv)
    checks = harness.fmt_checks(drv.compare(low, want), c.limits)
    assert not harness.all_within(checks), checks


def test_unchanged_state_makes_the_run_incorrect(built, monkeypatch):
    c, prog, ref = built
    tr = prog.trainer
    step = tr._step_resilient

    def stuck(i, batch, log, plan):
        return tr.state, step(i, batch, log, plan)[1]

    monkeypatch.setattr(tr, "_step_resilient", stuck)
    out = drv.run(c, prog, ref)
    assert not out.correct
    assert out.checks["change_gap"][0] > 0.99


def _stuck(state):
    return state


def _stale(state):
    return state._replace(step=state.step + 1)


@pytest.mark.parametrize("fault", [_stuck, _stale],
                         ids=["state-unchanged", "parameters-unchanged"])
def test_a_fault_after_the_set_up_steps_makes_the_run_incorrect(
        built, monkeypatch, fault):
    c, prog, ref = built
    tr = prog.trainer
    step = tr._step_resilient
    first = int(c.cfg["setup_steps"])

    def late(i, batch, log, plan):
        new, metrics = step(i, batch, log, plan)
        return (fault(tr.state) if i >= first else new), metrics

    monkeypatch.setattr(tr, "_step_resilient", late)
    out = drv.run(c, prog, ref)
    assert not out.correct
    # the set-up steps were sound: the comparison with the reference holds
    assert all(out.checks[k][0] <= out.checks[k][1]
               for k in ("loss_gap", "grad_gap", "change_gap"))
    lost, unmoved = out.checks["steps_lost"][0], out.checks["leaves_unmoved"][0]
    assert (lost > 0) if fault is _stuck else (lost == 0 and unmoved > 0)


def test_a_trainer_that_bypasses_the_feed_gives_no_result(built,
                                                           monkeypatch):
    c, prog, ref = built
    monkeypatch.setattr(prog.trainer, "_put_batch", prog.put_batch)
    with pytest.raises(harness.BenchError, match="feed"):
        drv.run(c, prog, ref)
