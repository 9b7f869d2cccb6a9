"""The serving runner at minitron-4b's SMOKE widths on the CPU: the engine
agrees with the reference, a sound run is correct, and the control and
each fault the timed path can have are not."""

import os

import numpy as np
import pytest

import benchkit
import servekit
from benchlib import harness

S = harness.runner_of(servekit.config())


@pytest.fixture(scope="module")
def built():
    """One engine and one reference for the file: each compiles once."""
    c = servekit.ctx()
    return S.Program(c), S.Reference(c)


def _run(built, traffic="serve-tiny", seed=2 ** 31 + 77, fault=None):
    prog, ref = built
    c = servekit.ctx(traffic, seed)
    prog.ctx = c
    saved = dict(prog.executables), prog.engine._maybe_finish
    if fault:
        fault(prog)
    try:
        return S.run(c, prog=prog, reference=ref)
    finally:
        prog.executables.update(saved[0])
        prog.engine._maybe_finish = saved[1]


def test_engine_logits_agree_with_the_reference(built):
    prog, ref = built
    prog.start(5)
    S.warm(prog)
    spec = prog.ctx.traffic
    d = S.drive(prog, spec, 5, 0.8, 4)
    params = prog.engine.params
    for uid in d.sample:
        r = d.requests[uid]
        g = ref.gaps(params, r.prompt, list(r.out), prog.rows[uid])
        # prefill-then-decode through the paged cache, bf16, against the
        # float32 forward pass over prompt and served tokens
        assert len(g["logit_gap"]) == len(r.out) >= 2
        assert np.max(g["logit_gap"]) < 0.05
        assert np.max(g["token_gap"]) < 0.05


@pytest.mark.parametrize("traffic", ["serve-tiny", "serve-backlog-tiny"])
def test_a_sound_run_is_correct(built, traffic):
    out = _run(built, traffic)
    assert out.correct and out.failed == 0 and out.attempted > 0
    assert list(out.checks) == ["token_gap", "logit_gap"]
    assert out.end_to_end["output_tokens_per_s"] > 0
    assert out.end_to_end["setup_s"] > 0
    if traffic == "serve-tiny":
        assert out.end_to_end["ttft_p90_ms"] > 0
        assert out.end_to_end["itl_p99_ms"] > 0
        assert 0 < out.end_to_end["itl_mean_ms"] <= \
            out.end_to_end["itl_p99_ms"]
        assert out.extra["itl_ms"]["mean"] == out.end_to_end["itl_mean_ms"]
    lat = out.extra["lateness_ms"]
    assert 0 <= lat["p50"] <= lat["p99"]


def test_the_float8_control_is_not_correct(built):
    prog, ref = built
    prog.start(9)
    S.warm(prog)
    c = prog.ctx
    d = S.drive(prog, c.traffic, 9, 0.8, 4)
    params = prog.engine.params
    served = [(d.requests[u].prompt, list(d.requests[u].out))
              for u in d.sample]
    ctl = S.compare([ref.gaps(params, p, o, None, quant=True)
                     for p, o in served])
    assert not harness.all_within(harness.fmt_checks(ctl, c.limits))
    assert ctl["logit_gap"] > c.limits["logit_gap"]


def _state_unchanged(prog):
    fn = prog.executables["decode"]

    def decode(params, tokens, kp, vp, *rest):
        logits, _k, _v = fn(params, tokens, kp, vp, *rest)
        return logits, kp, vp
    prog.executables["decode"] = decode


def _half_batch(prog):
    fn = prog.executables["decode"]

    def decode(*args):
        logits, kp, vp = fn(*args)
        half = logits.shape[0] // 2
        return logits.at[:half].set(0), kp, vp
    prog.executables["decode"] = decode


def _token_altered(prog):
    control = harness.load_module(os.path.join(benchkit.BENCH, "control.py"))
    control.alter_tokens(prog.engine)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered])
def test_a_broken_timed_path_is_not_correct(built, fault):
    out = _run(built, fault=fault)
    assert not out.correct


def test_an_engine_that_stops_decoding_gives_no_result(built):
    eng = built[0].engine
    step, calls = eng._decode_step, []

    def decode_step():
        calls.append(1)
        if len(calls) <= 20:         # the warm-up and the lead's first ticks
            step()
    eng._decode_step = decode_step
    try:
        with pytest.raises(harness.BenchError, match="no token"):
            _run(built)
    finally:
        del eng._decode_step


def test_requests_are_timed_from_when_they_were_due(built):
    from benchlib import traffic as T
    prog, _ref = built
    prog.start(3)
    spec = prog.ctx.traffic
    d = S.drive(prog, spec, 3, 0.8, 4)
    plan = T.serve_plan(spec, 3, 0.8, prog.vocab)
    assert d.counted
    for r in d.counted:
        assert r.t_arrival == d.t_arr + plan.due[r.uid]
        assert d.t0 <= r.t_arrival < d.t0 + 0.8
        assert r.t_first is not None and r.t_first > r.t_arrival
    assert min(d.late_ms) >= 0
    m = S.metrics(d)
    ttft = sorted((r.t_first - r.t_arrival) * 1e3 for r in d.counted)
    assert m["ttft_p90_ms"] == ttft[int(np.ceil(0.9 * len(ttft))) - 1]


def test_a_traced_run_describes_each_executable():
    import test_bench_serve_readers as R
    text = S.describe(R._reading())
    assert text == ("device ms per call: decode 17.0 over 1, prefill_ext "
                    "17.0 over 1, prefill_first 18.0 over 1")


def test_requests_due_during_a_stall_at_the_close_are_still_served(built):
    # the profiler's stop can hold the loop past the window's end; every
    # request due inside the window is submitted and counted all the same
    from benchlib import traffic as T
    prog, _ref = built
    prog.start(4)
    c = prog.ctx
    poll, stalled = c.tracer.poll, []

    def stall(now):
        if c.tracer.start_at is not None and now > c.tracer.start_at \
                and not stalled:
            stalled.append(now)
            import time
            time.sleep(0.8)
        return poll(now)
    c.tracer.poll = stall
    try:
        d = S.drive(prog, c.traffic, 4, 0.8, 4)
    finally:
        c.tracer.poll = poll
    plan = T.serve_plan(c.traffic, 4, 0.8, prog.vocab)
    lead = c.traffic["lead_seconds"]
    due = [i for i in range(len(plan)) if lead <= plan.due[i] < lead + 0.8]
    assert stalled and sorted(r.uid for r in d.counted) == due
    assert all(r.t_first is not None for r in d.counted)
