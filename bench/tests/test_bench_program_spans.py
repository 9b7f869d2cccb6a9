"""The program's own spans and phase scopes, on the CPU at a test's size:
the compiled DeepCAM step read by ``phases.scopes``, the host spans of
``Trainer.fit`` under the profiler, and ``bench/split.py`` reading both
from a traced run."""

import os
import re
import tempfile

import pytest

import benchkit
from benchlib import harness, hlo, phases

drv = harness.load_module(os.path.join(benchkit.BENCH, "runners", "train.py"))
split = harness.load_module(os.path.join(benchkit.BENCH, "split.py"))
SEED = 2 ** 33 + 17

#: substrings that ``repro.core.kernel_adjust`` reads in an ``op_name``
KERNEL_SCOPES = ("attention", "ssm", "mlp", "moe", "logits")


@pytest.fixture(scope="module")
def built():
    c = benchkit.ctx("deepcam-tiny", "tiny-b1", seed=SEED, seconds=1.0)
    prog = drv.Program(c)
    prog.start(SEED)
    return c, prog, prog.hlo_text()


def test_every_convolution_of_the_step_is_forward_or_backward(built):
    _, _, text = built
    sc = phases.scopes(text)
    convs = [k for k, c in hlo.costs(text).items()
             if "convolution" in c.opcodes]
    assert convs
    assert {sc[k] for k in convs} == {"forward", "backward"}
    assert "optimizer" in sc.values()


def test_no_scope_name_is_one_the_kernel_adjustment_reads(built):
    _, _, text = built
    names = re.findall(r'op_name="((?:[^"\\]|\\.)*)"', text)
    scoped = {part for n in names for part in n.split("/")[:-1]}
    assert any("train.grad" in p for p in scoped)
    assert any("deepcam.stage4" in p for p in scoped)
    assert not [p for p in scoped if any(w in p for w in KERNEL_SCOPES)]


def test_fit_records_each_step_and_its_parts_in_order(built):
    import jax
    _, prog, _ = built
    tr = prog.trainer
    k = int(tr.state.step)
    d = tempfile.mkdtemp(prefix="spans-")
    with jax.profiler.trace(d):
        tr.fit(k + 3, log_every=0)
    prog.check_feed()                 # the wrapped calls: once per step
    path = next(os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs
                if f.endswith(".xplane.pb"))
    spans = phases.load_spans(path)
    steps = [s for s in spans if s[2] == phases.STEP]
    assert len(steps) == 3
    order = [phases.BATCH, phases.PUT, phases.DISPATCH, phases.WAIT,
             phases.READBACK]
    for s, e, _ in steps:
        inside = [c for c in spans if c[2] != phases.STEP
                  and c[0] >= s and c[1] <= e]
        assert [c[2] for c in inside] == order
        for a, b in zip(inside, inside[1:]):
            assert a[1] <= b[0]                       # one after the other
    assert len(spans) == 3 * (1 + len(order))


def test_split_reads_a_traced_run_by_the_program_spans(built, monkeypatch):
    c, prog, text = built
    monkeypatch.setattr(prog, "hlo_text", lambda: text)
    c.trace, c.tracer = True, harness.Tracer(True, c.seconds)
    out = split.measure(harness.load_benchmark(), c, prog)
    assert out["correct"]
    host = out["host_ms"]
    assert set(host) == {*phases.STEP_PARTS, "self", phases.STEP}
    assert host[phases.DISPATCH] > 0 and host[phases.WAIT] > 0
    assert out["metrics"]["host_input_ms"] > 0
    assert out["metrics"]["host_dispatch_ms"] > 0
    # no chip: the device's readings are absent, not zero
    assert out["metrics"]["forward_ms"] is None
    assert out["device_ms"]["by_phase"] is None
