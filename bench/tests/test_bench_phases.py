"""The reading of the program's own spans and phase scopes
(``benchlib/phases.py``) on a hand-written HLO module and made-up
traces: what each number divides by what, what the window's edges leave
out, and nothing where the spans or scopes are missing."""

import os

import pytest

import benchkit
from benchlib import harness, hlo, phases
from benchlib import trace as T

FIXTURE = os.path.join(benchkit.FIXTURES, "small.xplane.pb")

GRAD = "jit(train_step)/train.grad"
HLO = f"""HloModule jit_train_step, entry_computation_layout={{()->f32[]}}

%fused_computation (param_0: bf16[1,8,8,4], param_1: bf16[1,8,8,4], param_2: f32[3,3,4,4]) -> f32[3,3,4,4] {{
  %param_0 = bf16[1,8,8,4]{{3,2,1,0}} parameter(0)
  %param_1 = bf16[1,8,8,4]{{3,2,1,0}} parameter(1)
  %wgrad = bf16[3,3,4,4]{{3,2,1,0}} convolution(%param_0, %param_1), window={{size=8x8 pad=1_1x1_1}}, dim_labels=f01b_i01o->01bf, metadata={{op_name="{GRAD}/transpose(jvp(deepcam.decoder))/conv_general_dilated"}}
  %small = bf16[1,8,8,4]{{3,2,1,0}} convolution(%param_0, %wgrad), window={{size=1x1}}, dim_labels=b01f_01io->b01f, metadata={{op_name="{GRAD}/jvp(deepcam.head)/conv_general_dilated"}}
  %param_2 = f32[3,3,4,4]{{3,2,1,0}} parameter(2)
  %convert = f32[3,3,4,4]{{3,2,1,0}} convert(%wgrad)
  ROOT %update = f32[3,3,4,4]{{3,2,1,0}} subtract(%param_2, %convert), metadata={{op_name="jit(train_step)/train.optimizer/sub"}}
}}

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {{
  %param_0.1 = f32[4]{{0}} parameter(0)
  ROOT %mul = f32[4]{{0}} multiply(%param_0.1, %param_0.1), metadata={{op_name="jit(train_step)/train.optimizer/mul"}}
}}

%fused_computation.2 (param_0.2: f32[4]) -> f32[4] {{
  %param_0.2 = f32[4]{{0}} parameter(0)
  ROOT %relu = f32[4]{{0}} maximum(%param_0.2, %param_0.2), metadata={{op_name="{GRAD}/jvp(deepcam.stage1)/max"}}
}}

ENTRY %main (p0: bf16[1,8,8,4], p1: bf16[1,8,8,4], p2: f32[3,3,4,4], p3: f32[4]) -> f32[] {{
  %p0 = bf16[1,8,8,4]{{3,2,1,0}} parameter(0)
  %p1 = bf16[1,8,8,4]{{3,2,1,0}} parameter(1)
  %p2 = f32[3,3,4,4]{{3,2,1,0}} parameter(2)
  %p3 = f32[4]{{0}} parameter(3)
  %fusion = f32[3,3,4,4]{{3,2,1,0}} fusion(%p0, %p1, %p2), kind=kOutput, calls=%fused_computation
  %fusion.1 = f32[4]{{0}} fusion(%p3), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(train_step)/other"}}
  %fusion.2 = f32[4]{{0}} fusion(%p3), kind=kLoop, calls=%fused_computation.2
  %copy = f32[4]{{0}} copy(%fusion.2), metadata={{op_name="{GRAD}/transpose(jvp(deepcam.stage1))/copy"}}
  %bare = f32[4]{{0}} add(%fusion.2, %bare.1)
  %bare.1 = f32[4]{{0}} negate(%copy)
  %bare.2 = f32[4]{{0}} negate(%p3)
  ROOT %sum = f32[] reduce(%copy), metadata={{op_name="jit(train_step)/train.metrics/reduce_sum"}}
}}
"""


# --------------------------------------------------------------------------
# phase scopes
# --------------------------------------------------------------------------

@pytest.mark.parametrize("op_name, phase", [
    (f"{GRAD}/jvp(deepcam.stage1)/conv_general_dilated", "forward"),
    (f"{GRAD}/transpose(jvp(deepcam.stage1))/conv_general_dilated",
     "backward"),
    ("jit(train_step)/train.optimizer/sub", "optimizer"),
    ("jit(train_step)/train.metrics/reduce_sum", "other"),
    ("", "other")])
def test_phase_of_an_op_name(op_name, phase):
    assert phases.phase_of(op_name) == phase


def test_scopes_judge_a_fusion_by_its_heaviest_convolution():
    sc = phases.scopes(HLO)
    # the weight gradient fused with its update counts as backward, not by
    # its optimizer-named root nor by its lighter convolution
    assert sc["fusion"] == "backward"
    # no convolution: the root's op_name, before the fusion's own
    assert sc["fusion.1"] == "optimizer"
    assert sc["fusion.2"] == "forward"
    assert sc["copy"] == "backward"
    assert sc["sum"] == "other"
    assert phases.op_names(HLO)["fusion"].endswith(
        "transpose(jvp(deepcam.decoder))/conv_general_dilated")


def test_an_instruction_without_an_op_name_takes_its_latest_operands():
    # the compiler's own instructions: a forward and a backward operand
    # make a backward instruction, found through another unnamed one
    names = phases.op_names(HLO)
    assert names["bare.1"] == names["copy"] == names["bare"]
    assert phases.scopes(HLO)["bare"] == "backward"
    assert names["bare.2"] == "" and phases.scopes(HLO)["bare.2"] == "other"


def test_a_step_without_the_scopes_has_no_phases():
    # JAX names the backward itself; only the step's scope names the rest
    unscoped = HLO.replace("/train.grad", "")
    assert "transpose(" in unscoped and "train.grad" not in unscoped
    assert phases.scopes(unscoped) == {}
    r = _reading(_device_trace(), scopes=phases.scopes(unscoped))
    assert phases.backward_ms(r) is None


def test_scopes_key_what_costs_key():
    # the instructions that run as ops: none inside a fusion
    assert set(phases.scopes(HLO)) == set(hlo.costs(HLO))
    assert "wgrad" not in phases.scopes(HLO)


def test_part_of_an_op_name():
    assert phases.part_of(f"{GRAD}/transpose(jvp(deepcam.stage3))/add") \
        == "stage3"
    assert phases.part_of("jit(train_step)/train.optimizer/sub") == ""


# --------------------------------------------------------------------------
# device time by phase
# --------------------------------------------------------------------------

def _device_trace(window=(0, 100e6)):
    # three executions of the step at 10, 45 and 80 ms, each a forward op
    # of 4 ms, a backward op of 8 ms, an optimizer op of 2 ms and one
    # unscoped op of 1 ms; then another program, whose op is named "%f" too
    ops, modules = [], []
    for k in range(3):
        s = 10e6 + 35e6 * k
        m = "jit_train_step(3)"
        modules.append((s, s + 16e6, m))
        for name, a, b in (("%f", 0, 4), ("%b", 4, 12), ("%o", 12, 14),
                           ("%x", 14, 15)):
            ops.append(T.Op(s + a * 1e6, s + b * 1e6, name, m))
    modules.append((90e6, 95e6, "jit_norms(4)"))
    ops.append(T.Op(90e6, 95e6, "%f", "jit_norms(4)"))
    return T.Trace({0: T.Device(ops, modules)}, [], window)


SCOPES = {"f": "forward", "b": "backward", "o": "optimizer"}


def _reading(trace, **kw):
    return harness.Reading(trace=trace, chips=1, program="jit_train_step",
                           **kw)


def test_phase_ms_is_self_time_over_whole_executions():
    r = _reading(_device_trace(), scopes=SCOPES)
    assert phases.forward_ms(r) == pytest.approx(4.0)
    assert phases.backward_ms(r) == pytest.approx(8.0)
    assert phases.optimizer_ms(r) == pytest.approx(2.0)
    assert phases.device_ms_by(r, SCOPES) == pytest.approx(
        {"forward": 4.0, "backward": 8.0, "optimizer": 2.0, "other": 1.0})


def test_executions_cut_by_the_window_are_left_out():
    # the window cuts the first and last executions: one is whole
    r = _reading(_device_trace((12e6, 90e6)), scopes=SCOPES)
    ops, _, n = phases.step_ops(r)
    assert n == 1 and len(ops) == 4
    assert phases.backward_ms(r) == pytest.approx(8.0)


def test_phase_ms_without_scopes_or_steps_is_nothing():
    tr = _device_trace()
    assert phases.forward_ms(_reading(tr)) is None               # no scopes
    assert phases.forward_ms(_reading(tr, scopes={})) is None
    other = harness.Reading(trace=tr, chips=1, program="jit_other",
                            scopes=SCOPES)
    assert phases.optimizer_ms(other) is None                    # no program
    empty = T.Trace({}, [], (0, 1e6))
    assert phases.backward_ms(_reading(empty, scopes=SCOPES)) is None


# --------------------------------------------------------------------------
# host spans
# --------------------------------------------------------------------------

def _spans(batch_ms=(1, 2, 3)):
    """Three steps of 12 ms at 0, 20 and 40 ms: batch, put 2 ms, dispatch
    3 ms (the last step's twice, a retried attempt of 1 ms after it),
    wait 2 ms, readback 1 ms."""
    out = []
    for k, b in enumerate(batch_ms):
        s = 20e6 * k
        out.append((s, s + 12e6, phases.STEP))
        t = s
        parts = [(phases.BATCH, b), (phases.PUT, 2), (phases.DISPATCH, 3)]
        if k == 2:
            parts.append((phases.DISPATCH, 1))
        parts += [(phases.WAIT, 2), (phases.READBACK, 1)]
        for name, ms in parts:
            out.append((t, t + ms * 1e6, name))
            t += ms * 1e6
    return sorted(out)


def _host(spans, window=(0, 100e6)):
    return harness.Reading(trace=T.Trace({}, [], window),
                           program_spans=spans)


def test_host_input_is_the_median_step_batch_and_put():
    assert phases.host_input_ms(_host(_spans())) == pytest.approx(4.0)


def test_host_dispatch_is_the_median_dispatch_span():
    # dispatch spans of 3, 3, 3 and 1 ms: one per attempt
    assert phases.host_dispatch_ms(_host(_spans())) == pytest.approx(3.0)


def test_steps_cut_by_the_window_are_left_out():
    # the window opens inside the first step and closes inside the last
    r = _host(_spans((1, 4, 9)), window=(5e6, 45e6))
    assert phases.host_input_ms(r) == pytest.approx(6.0)
    assert len(phases.step_parts(r)) == 1
    # dispatch spans whole in the window: the second step's only
    assert phases.host_dispatch_ms(r) == pytest.approx(3.0)


def test_host_split_covers_the_step():
    split = phases.host_split_ms(_host(_spans()))
    assert split[phases.STEP] == pytest.approx(12.0)
    assert split[phases.BATCH] == pytest.approx(2.0)
    # per step, the attempts summed: 3, 3 and 4 ms
    assert split[phases.DISPATCH] == pytest.approx(3.0)
    assert split[phases.CHECKPOINT] == 0.0
    # steps of 12 ms holding 9, 10 and 12 ms of parts
    assert split["self"] == pytest.approx(2.0)


def test_host_readers_without_spans_give_nothing():
    for spans in ([], None, [(0, 1e6, "repro.other")]):
        r = _host(spans)
        assert phases.host_input_ms(r) is None
        assert phases.host_dispatch_ms(r) is None
        assert phases.host_split_ms(r) is None
    # a step without its input spans reads nothing, not zero
    only_steps = [s for s in _spans() if s[2] == phases.STEP]
    assert phases.host_input_ms(_host(only_steps)) is None


def test_the_recorded_fixture_has_no_program_spans_and_reads_as_before():
    tr = T.load(FIXTURE)
    assert phases.load_spans(FIXTURE) == []
    assert [s[2] for s in tr.spans] == ["bench.conv", "bench.host_wait",
                                       "bench.matmul"] * 2
    r = harness.Reading(trace=tr, chips=1, program="jit_conv",
                        scopes={}, program_spans=[])
    assert phases.host_input_ms(r) is None
    assert phases.forward_ms(r) is None
