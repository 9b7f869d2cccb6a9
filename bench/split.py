#!/usr/bin/env python3
"""Where a training cell's step goes, by the program's own spans and scopes.

    python3 bench/split.py --workload <cell> --seed <n> --seconds <s>

Runs the cell once as ``run.py --trace 1`` does (the same runner, window
and profiler sub-window), then reads the trace by what the program
records about itself (``benchlib/phases.py``): the ``repro.train.*``
host spans of ``Trainer.fit`` and the phase of each instruction of the
step from its HLO ``op_name``.  Prints one JSON object as the last line
of standard output:

``metrics``        the cell's per-layer metrics, as ``run.py`` reads them,
                   and ``forward_ms``, ``backward_ms``, ``optimizer_ms``,
                   ``host_input_ms``, ``host_dispatch_ms``
``host_ms``        the median over whole steps of each part of a step
``device_ms``      device self time per step by phase, by model part and
                   by both; ``execution`` is the step program's own time
``idle_by_span``   the device's idle seconds by the program span open
``other_ops``      the instructions of phase ``other`` that take most time
``correct``        the run's comparison with the reference
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import harness, phases  # noqa: E402
from benchlib import trace as T  # noqa: E402


def measure(bm: dict, ctx: harness.Ctx, prog=None) -> dict:
    """One traced run of the cell in ``ctx``, read by the program's spans
    and scopes; a test may hand in a runner's program."""
    drv = harness.runner_of(ctx.cfg)
    prog = prog or drv.Program(ctx)
    out = drv.run(ctx, prog)
    text = prog.hlo_text()
    names = phases.op_names(text)
    path = ctx.tracer.xplane()
    tr = T.load(path)
    spans = phases.load_spans(path)
    ctx.tracer.cleanup()
    peaks = ({} if ctx.devices[0].platform != "tpu"
             else harness.peaks_for(ctx.devices[0].device_kind))
    scopes = phases.scopes(text)
    r = harness.Reading(
        trace=tr, chips=ctx.chips, cfg=ctx.cfg, peaks=peaks,
        window_host_s=ctx.tracer.stopped - ctx.tracer.started,
        scopes=scopes, program_spans=spans, **out.reading)

    metrics = {}
    if tr.devices:
        for m in harness.cell_metrics(bm, ctx.cell, "per_layer"):
            metrics[m["name"]] = harness.metric_reader(m["name"]).read(r)
    for f in (phases.forward_ms, phases.backward_ms, phases.optimizer_ms,
              phases.host_input_ms, phases.host_dispatch_ms):
        metrics[f.__name__] = f(r)

    parts = {k: phases.part_of(v) or "none" for k, v in names.items()}
    both = {k: f"{scopes.get(k, 'other')} {parts[k]}" for k in names}
    device = {"by_phase": phases.device_ms_by(r, scopes),
              "by_part": phases.device_ms_by(r, parts),
              "by_phase_part": phases.device_ms_by(r, both)}
    got = phases.step_ops(r)
    other: list = []
    if got is not None:
        ops, self_t, n = got
        by_name: dict[str, float] = {}
        for o, t in zip(ops, self_t):
            if scopes.get(o.name.lstrip("%"), "other") == "other":
                by_name[o.name] = by_name.get(o.name, 0.0) + t / n / 1e6
        other = [[k, v, names.get(k.lstrip("%"), "")] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:15]]
        lo, hi = tr.window
        dev = tr.devices[min(tr.devices)]
        device["execution"] = statistics.mean(
            (e - s) / 1e6 for s, e, m in dev.modules
            if m.startswith(r.program + "(") and s >= lo and e <= hi)
        device["steps"] = n
    idle = {}
    if tr.devices:
        idle = dict(T.top(T.idle_by_span(tr.devices[min(tr.devices)], spans,
                                         tr.window), 12))
    return {"correct": out.correct, "metrics": metrics,
            "host_ms": phases.host_split_ms(r), "device_ms": device,
            "idle_by_span": idle, "other_ops": other,
            "window_s": (tr.window[1] - tr.window[0]) / 1e9}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    # JAX's compilation cache leaves op_name out of its key: without this,
    # a step compiled from a program without the scopes would be loaded
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        bm, ctx = harness.build_ctx(args.workload, args.seed, args.seconds,
                                    True, T_START)
        result = measure(bm, ctx)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
