"""The serving readers on a made-up trace, and the work each call is
counted to need, by hand."""

import json
import os

import pytest

import benchkit
from benchlib import harness, lmcost, readers
from benchlib import trace as T

PEAKS = {"bf16_flop_per_s": 197e12, "hbm_byte_per_s": 819e9}
MS = 1e6

#: 2 layers, width 8, MLP 16, 2 query heads over 1 KV head of 4, vocab 32
TINY = {"n_layers": 2, "d_model": 8, "d_ff": 16, "n_heads": 2,
        "n_kv_heads": 1, "head_dim": 4, "vocab_size": 32, "weight_bytes": 2,
        "kv_bytes": 2, "logit_bytes": 2, "params": 1000, "mlp_matrices": 2}
#: the same with a gated MLP (swiglu, geglu): gate, up and down
GATED = {**TINY, "mlp_matrices": 3}


def test_a_prefill_is_counted_by_hand():
    # 448 matrix weights a layer: q, o 2*8*2*4; k, v 2*8*1*4; MLP 2*8*16
    # 4 tokens at 16: 2*448*2*4 = 7168; pairs 4*16 + 10 = 74 at 4*2*4*2
    # each = 4736; one row of logits 2*8*32 = 512
    assert lmcost.prefill(TINY, 16, 4) == (
        7168 + 4736 + 512,
        # weights 2000, K/V of 20 tokens at 2*2*1*4*2 = 640, 4 embedding
        # rows 64, logits 64
        2000 + 640 + 64 + 64)


def test_a_gated_mlp_is_counted_by_hand():
    # 576 matrix weights a layer: q, o 2*8*2*4; k, v 2*8*1*4; MLP 3*8*16
    # 4 tokens at 16: 2*576*2*4 = 9216; a decode of two: 2*576*2*2 = 4608
    assert lmcost.prefill(GATED, 16, 4) == (9216 + 4736 + 512,
                                            2000 + 640 + 64 + 64)
    assert lmcost.decode(GATED, [20, 5]) == (4608 + 1728 + 1024,
                                             2000 + 864 + 32 + 128)


@pytest.mark.parametrize("act,matrices", [("relu2", 2), ("gelu", 2),
                                          ("swiglu", 3), ("geglu", 3)])
def test_the_mlp_matrices_follow_the_activation(act, matrices):
    cfg = {**TINY, "parameters": 1000, "act": act}
    assert lmcost.shapes(cfg)["mlp_matrices"] == matrices


@pytest.mark.parametrize("name", ["minitron-4b", "glm4-9b"])
def test_the_layer_weights_add_up_to_the_programs_count(name):
    # a second witness: the program's own parameter count, less the
    # embedding (twice if the head is untied) and the norms
    from repro.configs.registry import get_config
    mc = get_config(name)
    cfg = {k: getattr(mc, k) for k in (
        "n_layers", "d_model", "d_ff", "n_heads", "n_kv_heads", "head_dim",
        "vocab_size", "act")}
    s = lmcost.shapes({**cfg, "parameters": mc.param_count(),
                       "weight_bytes": 2, "kv_bytes": 2, "logit_bytes": 2})
    D, V, L = mc.d_model, mc.vocab_size, mc.n_layers
    embed = V * D * (1 if mc.tie_embeddings else 2)
    norms = L * 2 * D + D
    assert L * lmcost._layer_matrix_weights(s) == mc.param_count() - embed \
        - norms


def test_a_decode_is_counted_by_hand():
    # two slots holding 20 and 5 tokens: 2*448*2*2 = 3584; pairs 21 + 6
    # at 32 each = 864 ... times 2 = 1728; two rows of logits 1024
    assert lmcost.decode(TINY, [20, 5]) == (
        3584 + 1728 + 1024,
        # weights 2000, K/V read 25 tokens and written 2, at 32 each 864;
        # embedding rows 32, logits 128
        2000 + 864 + 32 + 128)


def test_minitron_4b_reads_its_weights_once_a_call():
    with open(os.path.join(benchkit.BENCH, "configs",
                           "minitron-4b.json")) as f:
        s = lmcost.shapes(json.load(f))
    flops, nbytes = lmcost.decode(s, [0] * 8)
    assert nbytes == pytest.approx(6.81e9, rel=2e-3)
    # 2 x 3.40e9 parameters x 8 tokens, the attention's 8 pairs aside
    assert flops == pytest.approx(2 * 3.4039e9 * 8, rel=1e-3)


def test_the_configuration_is_the_programs():
    from benchlib import harness as H
    from repro.configs.registry import get_config
    path = os.path.join(benchkit.BENCH, "configs", "minitron-4b.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["_file"] = path
    mc = get_config(cfg["program"]["registry"])
    for k in ("n_layers", "d_model", "d_ff", "n_heads", "n_kv_heads",
              "head_dim", "vocab_size", "act", "tie_embeddings"):
        assert getattr(mc, k) == cfg[k]
    ref = H.reference_of(cfg)
    assert ref.n_params(cfg) == cfg["parameters"] == mc.param_count()


def _trace():
    # two ticks: a first chunk; then a later chunk and a decode.  Device
    # executions start 1 ms before their spans, as on the v5e
    spans = [(0, 30 * MS, "bench.serve.tick"),
             (2 * MS, 20 * MS, "bench.serve.prefill_first"),
             (32 * MS, 70 * MS, "bench.serve.tick"),
             (33 * MS, 50 * MS, "bench.serve.prefill_ext"),
             (51 * MS, 68 * MS, "bench.serve.decode")]
    modules = [(1 * MS, 19 * MS, "jit_fn(1)"), (32 * MS, 49 * MS, "jit_fn(2)"),
               (50 * MS, 67 * MS, "jit_fn(3)"), (75 * MS, 76 * MS, "jit_x(4)")]
    ops = [T.Op(s, e, "%fusion", m) for s, e, m in modules]
    return T.Trace({0: T.Device(ops, modules)}, spans, (0, 100 * MS))


CALLS = [{"kind": "decode", "traced": False, "lengths": [3]},
         {"kind": "prefill_first", "traced": True, "start": 0, "valid": 16},
         {"kind": "prefill_ext", "traced": True, "start": 16, "valid": 4},
         {"kind": "decode", "traced": True, "lengths": [20, 5]}]


def _reading(calls=CALLS):
    return harness.Reading(trace=_trace(), peaks=PEAKS, chips=1,
                           calls=calls, model=TINY)


def _least(rec):
    f, b = lmcost.call(TINY, rec)
    return max(f / PEAKS["bf16_flop_per_s"], b / PEAKS["hbm_byte_per_s"])


def test_calls_are_paired_with_executions_by_span():
    ex = readers.serve_executions(_reading())
    assert [(c["kind"], t) for c, t in ex] == [
        ("prefill_first", 0.018), ("prefill_ext", 0.017), ("decode", 0.017)]


def test_roofline_shares_and_mfu_divide_the_counted_work_by_device_time():
    r = _reading()
    pre, dec = CALLS[1:3], CALLS[3]
    assert readers.prefill_roofline(r) == pytest.approx(
        100 * sum(map(_least, pre)) / 0.035)
    assert readers.decode_hbm_roofline(r) == pytest.approx(
        100 * _least(dec) / 0.017)
    flops = sum(lmcost.call(TINY, c)[0] for c in CALLS[1:])
    assert readers.tick_mfu(r) == pytest.approx(
        100 * flops / (0.052 * PEAKS["bf16_flop_per_s"]))


def test_host_time_per_tick_leaves_out_the_executables():
    # 30 - 18 and 38 - (17 + 17) ms: the median of 12 and 4
    assert readers.host_ms_per_tick(_reading()) == pytest.approx(8.0)


def test_records_that_do_not_match_the_spans_give_nothing():
    r = _reading(CALLS[:-1])
    assert readers.serve_executions(r) is None
    assert readers.tick_mfu(r) is None
    assert readers.decode_hbm_roofline(r) is None
    assert readers.prefill_roofline(_reading(CALLS[:1])) is None


def test_the_gap_tail_is_the_runners_own_count():
    assert readers.itl_p99_ms(harness.Reading(itl_p99_ms=112.5)) == 112.5
    assert readers.itl_p99_ms(harness.Reading(itl_p99_ms=None)) is None
    assert readers.itl_p99_ms(_reading()) is None
