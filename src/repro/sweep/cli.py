"""``python -m repro.sweep`` — run / report cross-config roofline campaigns.

Subcommands:

* ``run``    — expand a sweep spec (registry configs × mesh shapes × AMP
  policies × batch sizes) into a work list, execute every point through the
  analytical pipeline (+ the measured ``repro.trace`` pass unless
  ``--no-measure``) on a pool of worker processes, and persist one
  schema-versioned record per point into the trace store.  ``--smoke`` is
  the CI preset: ≥ 8 smoke configs, single-device, measured, minutes on a
  CPU host.
* ``report`` — re-render the campaign from the store only (no re-running):
  the ranked achieved-vs-bound summary table across every config, plus the
  per-config hierarchical roofline gallery.

Examples::

    PYTHONPATH=src python -m repro.sweep run --smoke
    PYTHONPATH=src python -m repro.sweep run --configs family:ssm,minitron-4b \
        --amp O0,O1 --batch 2,4 --no-measure
    PYTHONPATH=src python -m repro.sweep run --spec campaign.json --workers 4
    PYTHONPATH=src python -m repro.sweep report
    PYTHONPATH=src python -m repro.sweep report --name smoke --charts 4
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Sequence

from repro.compile_cache import enable_compile_cache
from repro.session.workspace import (LEGACY_SWEEP_CACHE, LEGACY_SWEEP_STORE,
                                     resolve_sweep_cache, resolve_sweep_store)
from repro.sweep.spec import (SweepSpec, parse_int_list, parse_mesh,
                              smoke_spec)

# flags that define the sweep's axes: they conflict with --spec/--smoke
# (which define the axes themselves) instead of being silently ignored
_AXIS_FLAGS = ("configs", "seq", "batch", "amp", "fusion", "mesh", "full")
_AXIS_DEFAULTS = {"configs": "all", "seq": "32", "batch": "4", "amp": "O1",
                  "fusion": "off", "mesh": "1x1", "full": False}


def spec_from_args(ap: argparse.ArgumentParser, args) -> SweepSpec:
    if args.spec or args.smoke:
        explicit = [f"--{k}" for k in _AXIS_FLAGS
                    if getattr(args, k) is not None]
        if explicit:
            which = "--spec" if args.spec else "--smoke"
            ap.error(f"{' '.join(explicit)} conflict(s) with {which} "
                     "(the axes come from the spec)")
    if args.spec:
        with open(args.spec) as f:
            spec = SweepSpec.from_json(f.read())
    elif args.smoke:
        spec = smoke_spec(args.smoke_configs)
    else:
        flags = {k: (getattr(args, k) if getattr(args, k) is not None
                     else _AXIS_DEFAULTS[k]) for k in _AXIS_FLAGS}
        spec = SweepSpec(
            configs=tuple(s.strip() for s in flags["configs"].split(",")
                          if s.strip()),
            seqs=parse_int_list(flags["seq"]),
            batches=parse_int_list(flags["batch"]),
            amps=tuple(a.strip() for a in flags["amp"].split(",")
                       if a.strip()),
            fusions=tuple(f.strip() for f in flags["fusion"].split(",")
                          if f.strip()),
            meshes=tuple(parse_mesh(m) for m in flags["mesh"].split(",")
                         if m.strip()),
            smoke=not flags["full"])
    # run-policy knobs apply to every source, spec files and presets
    # included (a spec file declares the axes; how hard to measure and
    # against which machine stay operator choices)
    overrides = {"measure": False if args.no_measure else None,
                 "machine": args.machine, "iters": args.iters,
                 "warmup": args.warmup, "name": args.name}
    applied = {k: v for k, v in overrides.items() if v is not None}
    return dataclasses.replace(spec, **applied) if applied else spec


def cmd_run(ap: argparse.ArgumentParser, args) -> int:
    import os

    from repro.resilience.faults import FAULT_ENV, parse_plan
    from repro.sweep.aggregate import latest_per_point, render_summary
    from repro.sweep.engine import run_sweep
    from repro.trace.store import TraceStore

    args.store = resolve_sweep_store(args.store)
    try:
        spec = spec_from_args(ap, args)
        points, skipped = spec.expand()
        if args.faults is not None:
            parse_plan(args.faults)       # reject typos before any work
            # via the environment so spawned workers inherit the plan
            os.environ[FAULT_ENV] = args.faults
    except (KeyError, ValueError, OSError) as e:
        # bad user input (unknown selector, malformed mesh/spec file):
        # message + exit 2, not a traceback — same convention as
        # repro.trace and benchmarks.run
        msg = e.args[0] if e.args else e
        print(f"run: {msg}", file=sys.stderr)
        return 2
    print(f"[{spec.name}] {len(points)} point(s) "
          f"({len(skipped)} skipped) -> {args.store}")
    enable_compile_cache()
    result = run_sweep(
        spec, store_path=args.store, workers=args.workers,
        cache_dir=None if args.no_cache else resolve_sweep_cache(
            args.cache_dir),
        progress=print,
        deadline_s=args.deadline, retries=args.retries,
        backoff_s=args.backoff, resume=args.resume,
        journal_path=args.journal if args.journal else ...)
    resumed = (f", {result.n_resumed} resumed" if result.n_resumed else "")
    quar = (f" ({result.n_quarantined} quarantined)"
            if result.n_quarantined else "")
    print(f"[{spec.name}] {result.n_ok} ok ({result.n_cached} cached"
          f"{resumed}), {result.n_failed} failed{quar}, "
          f"{len(result.skipped)} skipped")
    if result.n_failed:
        print(f"[{spec.name}] failures:", file=sys.stderr)
        for line in result.failure_summary():
            print(f"  {line}", file=sys.stderr)
    if result.n_ok:
        from repro.sweep.aggregate import sweep_records
        recs = latest_per_point(sweep_records(TraceStore(args.store),
                                              spec.name))
        print()
        print(render_summary(recs))
    return 1 if result.n_failed else 0


def cmd_report(ap: argparse.ArgumentParser, args) -> int:
    del ap
    from repro.sweep.aggregate import (gallery, latest_per_point,
                                       render_summary, sweep_records)
    from repro.trace.store import TraceStore

    args.store = resolve_sweep_store(args.store)
    store = TraceStore(args.store)
    recs = latest_per_point(sweep_records(store, args.name))
    if not recs:
        which = f"sweep {args.name!r}" if args.name else "any sweep"
        print(f"report: no records for {which} in {args.store}",
              file=sys.stderr)
        return 2
    print(render_summary(recs))
    from repro.sweep.aggregate import kernel_config_lines, tune_mismatches
    for line in kernel_config_lines(recs):
        print(line)
    flags = tune_mismatches(recs, args.tune_store)
    for flag in flags:
        print(f"! tuned-config mismatch: {flag}")
    if args.charts:
        print()
        print(gallery(recs, max_charts=args.charts))
    return 0


def main(argv: Sequence[str] | None = None,
         prog: str = "python -m repro.sweep") -> int:
    ap = argparse.ArgumentParser(prog=prog, description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    run = sub.add_parser("run", help="expand a spec, run every point, "
                                     "persist one record per point")
    run.add_argument("--spec", default=None,
                     help="sweep spec JSON file (overrides the axis flags)")
    run.add_argument("--smoke", action="store_true",
                     help="CI preset: >=8 smoke configs, 1x1 mesh, measured")
    run.add_argument("--smoke-configs", type=int, default=8,
                     help="how many configs the --smoke preset sweeps")
    run.add_argument("--name", default=None, help="campaign name, stamped "
                     "into every record's meta (default: the spec/preset "
                     "name, or 'sweep')")
    run.add_argument("--configs", default=None,
                     help="comma list of selectors: names, family:<fam>, "
                          "all (default all)")
    run.add_argument("--seq", default=None,
                     help="comma list of seq lengths (default 32)")
    run.add_argument("--batch", default=None,
                     help="comma list of batches (default 4)")
    run.add_argument("--amp", default=None,
                     help="comma list of AMP policies (default O1)")
    run.add_argument("--fusion", default=None,
                     help="comma list of fused-kernel modes: off, auto "
                          "(default off) — 'off,auto' sweeps every config "
                          "reference vs fused for before/after comparison")
    run.add_argument("--mesh", default=None,
                     help="comma list of DxM meshes (data x model), "
                          "e.g. 1x1,2x4 (default 1x1) — multi-device meshes "
                          "run on forced-host virtual devices in worker "
                          "processes")
    run.add_argument("--machine", default=None,
                     help="machine model the bounds are against "
                          "(default cpu-host)")
    run.add_argument("--no-measure", action="store_true",
                     help="analytical bounds only (cacheable, no execution)")
    run.add_argument("--full", action="store_true", default=None,
                     help="full configs instead of smoke variants")
    run.add_argument("--iters", type=int, default=None)
    run.add_argument("--warmup", type=int, default=None)
    run.add_argument("--workers", type=int, default=None,
                     help="worker processes (default: min(4, cpus) for "
                          "analytical sweeps, 1 for measured — concurrent "
                          "wall-clock samples contend; 0 = inline, "
                          "single-device points only)")
    run.add_argument("--store", default=None,
                     help="JSONL store path (default: "
                          "$REPRO_WORKSPACE/sweep.jsonl, else "
                          f"{LEGACY_SWEEP_STORE})")
    run.add_argument("--cache-dir", default=None,
                     help="per-point analysis cache (analytical runs; "
                          "default: $REPRO_WORKSPACE/sweep_cache, else "
                          f"{LEGACY_SWEEP_CACHE})")
    run.add_argument("--no-cache", action="store_true")
    run.add_argument("--resume", action="store_true",
                     help="skip points whose record already landed for "
                          "this campaign (journal + store scan, keyed by "
                          "the point content hash) — continue a crashed "
                          "or quarantine-interrupted run")
    run.add_argument("--deadline", type=float, default=None,
                     help="per-point wall-clock deadline in seconds; a "
                          "point past it has its worker killed and "
                          "replaced (counts as one failed attempt). "
                          "A worker's first point pays the jax import — "
                          "keep deadlines comfortably above it")
    run.add_argument("--retries", type=int, default=1,
                     help="extra attempts per failed point before it is "
                          "quarantined (default 1)")
    run.add_argument("--backoff", type=float, default=0.25,
                     help="base retry backoff seconds, doubling each "
                          "round (default 0.25)")
    run.add_argument("--faults", default=None,
                     help="fault-injection plan (same grammar as "
                          "REPRO_FAULTS, e.g. 'crash_point:0;"
                          "hang_point:1:30') — exported to workers")
    run.add_argument("--journal", default=None,
                     help="campaign journal path (default: "
                          "sweep_journal.jsonl beside the store)")
    run.set_defaults(fn=cmd_run, parser=run)

    rep = sub.add_parser("report", help="render the stored campaign: ranked "
                                        "table + roofline gallery")
    rep.add_argument("--store", default=None,
                     help="JSONL store path (default: "
                          "$REPRO_WORKSPACE/sweep.jsonl, else "
                          f"{LEGACY_SWEEP_STORE})")
    rep.add_argument("--name", default=None,
                     help="campaign name (default: every sweep record)")
    rep.add_argument("--charts", type=int, default=0,
                     help="also render up to N per-config roofline charts")
    rep.add_argument("--tune-store", default=None,
                     help="tune store to check measured points' kernel "
                          "configs against (default: repro.tune's default)")
    rep.set_defaults(fn=cmd_report, parser=rep)

    args = ap.parse_args(argv)
    return args.fn(args.parser, args)


if __name__ == "__main__":
    sys.exit(main())
