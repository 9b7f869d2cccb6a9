#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout, on the machine that holds the chips the
cell asks for, and exits non-zero without a result where JAX finds no TPU
or too few of them.  Set-up (loading, weights from the seed, compiling or
loading every program the cell uses, warming up) is timed as ``setup_s``;
then the cell's runner measures for ``--seconds``.  With ``--trace 0``
the last line of standard output is a JSON object whose ``metrics`` are
the cell's end-to-end metrics; with ``--trace 1`` the profiler records a
sub-window and ``metrics`` are the cell's per-layer metrics, read from
the trace by ``bench/metrics/<name>.py``, with a ``breakdown`` of device
time.  Every run compares what the timed path produced with the
configuration's plain reference; each number compared is printed beside
its limit as the last lines of standard error and under ``checks``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import harness  # noqa: E402


def per_layer(bm: dict, cell: dict, ctx: harness.Ctx,
              out: harness.Outcome) -> tuple[dict, dict, dict]:
    """(metrics, device fields, breakdown) of a traced run."""
    from benchlib import trace
    tr = trace.load(ctx.tracer.xplane())
    ctx.tracer.cleanup()
    reading = harness.Reading(
        trace=tr, chips=ctx.chips, cfg=ctx.cfg,
        peaks=harness.peaks_for(ctx.devices[0].device_kind),
        window_host_s=ctx.tracer.stopped - ctx.tracer.started,
        **out.reading)
    describe = getattr(harness.runner_of(ctx.cfg), "describe", None)
    if describe is not None:
        harness.say(describe(reading))
    metrics = {}
    for m in harness.cell_metrics(bm, cell, "per_layer"):
        value = harness.metric_reader(m["name"]).read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    lo, hi = tr.window
    busy = [trace.busy_ns(tr.devices[i], tr.window) for i in sorted(
        tr.devices)[:ctx.chips]]
    dev0 = tr.devices[min(tr.devices)]
    ops = trace.in_window(dev0.ops, tr.window)
    breakdown = {
        "device_ops": trace.top(trace.op_time_by_name(ops)),
        "idle_gaps": trace.top(trace.idle_by_span(dev0, tr.spans, tr.window))}
    device = {"busy_s": sum(busy) / len(busy) / 1e9,
              "window_s": (hi - lo) / 1e9}
    return metrics, device, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bm, ctx = harness.build_ctx(args.workload, args.seed, args.seconds,
                                    bool(args.trace), T_START)
        cell, cfg, devices = ctx.cell, ctx.cfg, ctx.devices
        out = harness.runner_of(cfg).run(ctx)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": out.memory_peak_bytes}
        result = {"correct": out.correct, "attempted": out.attempted,
                  "failed": out.failed}
        if args.trace:
            metrics, extra, breakdown = per_layer(bm, cell, ctx, out)
            device.update(extra)
            result.update(metrics=metrics, device=device,
                          breakdown=breakdown)
        else:
            result.update(metrics={
                m["name"]: {"value": float(out.end_to_end[m["name"]]),
                            "unit": m["unit"]}
                for m in harness.cell_metrics(bm, cell, "end_to_end")},
                device=device)
        result.update(out.extra)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.emit(result, out.checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
