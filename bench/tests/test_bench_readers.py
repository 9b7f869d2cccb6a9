"""The per-layer readers on made-up traces: what each divides by what."""

import pytest

import benchkit  # noqa: F401
from benchlib import harness, readers
from benchlib import trace as T

PEAKS = {"bf16_flop_per_s": 197e12, "hbm_byte_per_s": 819e9}


def _train_trace():
    # three steps of 20 ms each 30 ms, and one other program of 5 ms;
    # the last step runs past the window's end
    modules = [(0, 20e6, "jit_train_step(3)"), (30e6, 50e6, "jit_train_step(3)"),
               (52e6, 57e6, "jit_norms(4)"), (60e6, 80e6, "jit_train_step(3)")]
    ops = [T.Op(s, e, "%fusion", m) for s, e, m in modules]
    return T.Trace({0: T.Device(ops, modules)}, [], (0, 70e6))


def _reading(**kw):
    return harness.Reading(peaks=PEAKS, chips=1, **kw)


def test_training_mfu_is_read_from_the_step_executions():
    # two whole steps in the window, each 197e12 * 0.01 FLOPs in 20 ms
    r = _reading(trace=_train_trace(), program="jit_train_step",
                 flops_per_example=197e12 * 0.005, examples_per_chip=2)
    assert readers.mfu_train(r) == pytest.approx(50.0)


def test_training_mfu_leaves_out_idle_and_other_programs():
    # the same steps with a longer window read the same share
    tr = _train_trace()
    longer = T.Trace(tr.devices, [], (0, 59e6))
    a = _reading(trace=tr, program="jit_train_step",
                 flops_per_example=1e9, examples_per_chip=1)
    b = _reading(trace=longer, program="jit_train_step",
                 flops_per_example=1e9, examples_per_chip=1)
    assert readers.mfu_train(a) == pytest.approx(readers.mfu_train(b))


def test_idle_share_is_what_the_ops_leave():
    r = _reading(trace=_train_trace())
    # 20 + 20 + 5 + 10 of 70 ms busy
    assert readers.device_idle_share(r) == pytest.approx(100 * 15 / 70)


def test_nothing_to_read_gives_nothing():
    r = _reading(trace=_train_trace(), program="jit_other",
                 flops_per_example=1e9, examples_per_chip=1)
    assert readers.mfu_train(r) is None                  # no such program
    assert readers.mfu_train(_reading(trace=_train_trace())) is None
    assert readers.conv_roofline_train(r) is None        # no HLO costs
