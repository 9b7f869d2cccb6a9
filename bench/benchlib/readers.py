"""The arithmetic of the per-layer metrics.  Each metric's own file in
``bench/metrics/`` names one of these functions; a function returns
``None`` where the trace holds nothing for it to read.  Shares are in
percent."""

from __future__ import annotations

import bisect

from benchlib import lmcost
from benchlib import trace as T


def _dev0(r):
    return r.trace.devices[min(r.trace.devices)]


def _window_ns(r) -> float:
    lo, hi = r.trace.window
    return hi - lo


# --------------------------------------------------------------------------
# any cell
# --------------------------------------------------------------------------

def device_idle_share(r):
    """1 - the union of the device's op intervals over the window, the
    mean over the chips."""
    devs = [r.trace.devices[i] for i in sorted(r.trace.devices)][:r.chips]
    if not devs:
        return None
    busy = sum(T.busy_ns(d, r.trace.window) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / _window_ns(r))


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------

def _executions_s(r) -> list[float]:
    """Device seconds of each execution of the runner's step program on
    the first chip that lies whole in the window."""
    lo, hi = r.trace.window
    return [(e - s) / 1e9 for s, e, m in _dev0(r).modules
            if m.startswith(r.program + "(") and s >= lo and e <= hi]


def mfu_train(r):
    """The step program's share of the bf16 peak while it runs: FLOPs of
    its executions on the first chip (forward and backward of each chip's
    images, from the configuration's shapes) over their summed device
    time.  The device's idle time is ``device_idle_share``'s."""
    if not r.get("program"):
        return None
    t = _executions_s(r)
    if not t:
        return None
    flops = r.flops_per_example * r.examples_per_chip * len(t)
    return 100.0 * flops / (sum(t) * r.peaks["bf16_flop_per_s"])


def conv_roofline_train(r):
    """For the convolutions of the step: the sum over their executions of
    the least time the chip could take (the larger of FLOPs over the bf16
    peak and HBM bytes over HBM bandwidth, from the compiled HLO's
    shapes) over the sum of their device time."""
    costs = r.get("costs")
    if not costs:
        return None
    peak, bw = r.peaks["bf16_flop_per_s"], r.peaks["hbm_byte_per_s"]
    least = spent = 0.0
    for op in T.in_window(_dev0(r).ops, r.trace.window):
        if not op.module.startswith(r.program + "("):
            continue
        c = costs.get(op.name.lstrip("%"))
        if c is None or "convolution" not in c.opcodes:
            continue
        least += max(c.flops / peak, c.hbm_bytes / bw)
        spent += (op.end - op.start) / 1e9
    return 100.0 * least / spent if spent else None


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

#: the host spans the serving runner opens around each executable's call
#: (each blocks until its outputs are ready) and around each engine tick
SERVE_CALLS = ("bench.serve.prefill_first", "bench.serve.prefill_ext",
               "bench.serve.decode")
SERVE_TICK = "bench.serve.tick"


def _in_window(r, spans):
    lo, hi = r.trace.window
    return [s for s in spans if s[0] >= lo and s[1] <= hi]


def serve_executions(r) -> list[tuple[dict, float]] | None:
    """(the runner's record of a call, its device seconds on the first
    chip) for every executable call whose span lies whole in the window.

    The runner records its calls in order, marking those made while the
    profiler ran; the k-th such record is the k-th call span in the
    window.  The executables share one module name, so a device
    execution is given to the call span it overlaps most (device and host
    clocks differ by about a millisecond, against executions of tens).
    ``None`` where the records and spans disagree, or nothing was read."""
    recs = [c for c in r.get("calls") or [] if c.get("traced")]
    spans = _in_window(r, [s for s in r.trace.spans if s[2] in SERVE_CALLS])
    if not recs or len(recs) != len(spans) or any(
            s[2] != "bench.serve." + c["kind"] for s, c in zip(spans, recs)):
        return None
    starts = [s[0] for s in spans]
    spent = [0.0] * len(spans)
    lo, hi = r.trace.window
    for s, e, _m in _dev0(r).modules:
        if s < lo or e > hi:
            continue
        i = bisect.bisect_right(starts, e) - 1
        best, arg = 0.0, None
        for j in (i - 1, i, i + 1):
            if 0 <= j < len(spans):
                ov = min(e, spans[j][1]) - max(s, spans[j][0])
                if ov > best:
                    best, arg = ov, j
        if arg is not None:
            spent[arg] += (e - s) / 1e9
    out = [(c, t) for c, t in zip(recs, spent) if t > 0]
    return out or None


def _serve_roofline(r, kinds):
    ex = serve_executions(r)
    if ex is None:
        return None
    peak, bw = r.peaks["bf16_flop_per_s"], r.peaks["hbm_byte_per_s"]
    least = spent = 0.0
    for rec, t in ex:
        if rec["kind"] in kinds:
            f, b = lmcost.call(r.model, rec)
            least += max(f / peak, b / bw)
            spent += t
    return 100.0 * least / spent if spent else None


def tick_mfu(r):
    """Model FLOPs of the prefill and decode executions in the window over
    their summed device time x the bf16 peak."""
    ex = serve_executions(r)
    if ex is None:
        return None
    flops = sum(lmcost.call(r.model, rec)[0] for rec, _t in ex)
    return 100.0 * flops / (sum(t for _r, t in ex)
                            * r.peaks["bf16_flop_per_s"])


def prefill_roofline(r):
    """Over the prefill executions: the least time the chip could take
    (the larger of FLOPs over the bf16 peak and bytes over HBM bandwidth)
    over their device time."""
    return _serve_roofline(r, ("prefill_first", "prefill_ext"))


def decode_hbm_roofline(r):
    """The same over the decode executions, which are bound by HBM."""
    return _serve_roofline(r, ("decode",))


def host_ms_per_tick(r):
    """Median over the window's whole engine ticks that ran an executable
    of the tick's span less its executables' spans, in milliseconds."""
    ticks = _in_window(r, [s for s in r.trace.spans if s[2] == SERVE_TICK])
    calls = [s for s in r.trace.spans if s[2] in SERVE_CALLS]
    own = []
    j = 0
    for s, e, _n in ticks:
        while j < len(calls) and calls[j][0] < s:
            j += 1
        inner = 0.0
        k = j
        while k < len(calls) and calls[k][1] <= e:
            inner += calls[k][1] - calls[k][0]
            k += 1
        if k > j:
            own.append((e - s - inner) / 1e6)
    if not own:
        return None
    own.sort()
    mid = len(own) // 2
    return own[mid] if len(own) % 2 else (own[mid - 1] + own[mid]) / 2


def itl_p99_ms(r):
    """The 99th percentile (nearest rank) of the gaps between output tokens
    that end in the run's whole window, on the host clock, as the serving
    runner counted them; ``None`` where it counted none."""
    return r.get("itl_p99_ms")
