"""The arithmetic of the per-layer metrics.  Each metric's own file in
``bench/metrics/`` names one of these functions; a function returns
``None`` where the trace holds nothing for it to read.  Shares are in
percent."""

from __future__ import annotations

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
