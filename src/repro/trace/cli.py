"""``python -m repro.trace`` — record / compare / report measured rooflines.

Subcommands (all sweep any subset of ``repro.configs.registry``):

* ``record``  — build a config's train phases (fwd / bwd / opt), compile
  once, analyze + execute the same executables, and append one
  schema-versioned record per config to the JSONL store:
  measured wall time, achieved GFLOP/s and %-of-roofline per phase,
  bound envelope, top kernels, git SHA + host fingerprint.
* ``compare`` — diff the last two runs per config (or two explicit run
  ids) cell by cell and flag regressions past ``--threshold``; exits
  non-zero when any cell regressed, so CI can gate on it.
* ``report``  — pretty-print the newest stored record per config
  (achieved table + step timeline) without re-running anything.

Examples::

    PYTHONPATH=src python -m repro.trace record --config minitron-4b
    PYTHONPATH=src python -m repro.trace record --all --iters 10
    PYTHONPATH=src python -m repro.trace compare --config minitron-4b
    PYTHONPATH=src python -m repro.trace report
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import traceback
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs.base import FUSION_MODES, RunConfig, ShapeSpec
from repro.configs.registry import ALL, ARCHS, get_config, get_smoke
from repro.core.machine import MACHINES
from repro.session.workspace import LEGACY_TRACE_STORE, resolve_trace_store
from repro.trace.collector import PhaseMeasurement, collect_phases
from repro.trace.compare import (compare_last, compare_records, format_deltas,
                                 has_regressions)
from repro.trace.store import TraceStore, record_from_phases
from repro.trace.timeline import ascii_timeline, build_timeline, timeline_from_record

# legacy constant (pre-workspace callers import it); the CLI itself
# resolves through repro.session.workspace so REPRO_WORKSPACE governs it
DEFAULT_STORE = LEGACY_TRACE_STORE


# --------------------------------------------------------------------------
# record
# --------------------------------------------------------------------------

def build_phase_args(model, run: RunConfig, *, seq: int = 32, batch: int = 4,
                     seed: int = 0, concrete: bool = True):
    """fwd / bwd / opt phase programs for a built model:
    ``{phase: (fn, args)}`` ready for ``repro.core.profiler`` /
    ``repro.trace.collector``.

    ``concrete=True`` allocates real buffers (the measured path needs them
    anyway); ``concrete=False`` produces ShapeDtypeStruct trees instead —
    the analytical path (``repro.sweep`` campaigns) lowers without
    allocating a single array.
    """
    from repro.models import api as M
    from repro.models.params import init
    from repro.train import optim
    from repro.train.step import make_phases

    cfg = model.cfg
    shape = ShapeSpec("trace", seq, batch, "train")
    fns = make_phases(model, run)
    if concrete:
        params = init(jax.random.PRNGKey(seed), model.spec, run.param_dtype)
        batch_c = M.synthetic_batch(cfg, shape, batch, seed)
        opt_state = optim.optimizer_init(params, run)
    else:
        params = jax.eval_shape(
            lambda k: init(k, model.spec, run.param_dtype),
            jax.random.PRNGKey(seed))
        batch_c = {k: jax.ShapeDtypeStruct(s, dt) for k, (s, dt)
                   in M.batch_schema(cfg, shape, batch).items()}
        opt_state = jax.eval_shape(
            lambda p: optim.optimizer_init(p, run), params)
    grads = jax.tree.map(lambda p: (
        p if isinstance(p, jax.ShapeDtypeStruct) else jnp.zeros_like(p)),
        params)
    return {
        "fwd": (fns["fwd"], (params, batch_c)),
        "bwd": (fns["bwd"], (params, batch_c)),
        "opt": (fns["opt"], (params, grads, opt_state)),
    }


def build_measured_phases(config: str, *, smoke: bool = True, seq: int = 32,
                          batch: int = 4, amp: str = "O1", seed: int = 0,
                          fusion: str = "off",
                          run: RunConfig | None = None):
    """(phases, run): fwd / bwd / opt with *concrete* args, ready to both
    analyze and execute (the measured path needs real buffers anyway)."""
    from repro.models import api as M

    cfg = get_smoke(config) if smoke else get_config(config)
    run = run or RunConfig(amp=amp, fusion=fusion)
    model = M.build(cfg)
    return build_phase_args(model, run, seq=seq, batch=batch,
                            seed=seed), run


def scale_measurement(m: PhaseMeasurement, factor: float) -> PhaseMeasurement:
    """Scale a measurement's wall time (regression drills / tests)."""
    if factor == 1.0:
        return m
    kernels = [dataclasses.replace(
        k, attributed_s=k.attributed_s * factor,
        achieved_flops_per_s=k.achieved_flops_per_s / factor,
        pct_of_roofline=k.pct_of_roofline / factor)
        for k in m.kernels]
    return dataclasses.replace(m, wall_s=m.wall_s * factor, kernels=kernels)


def cmd_record(args) -> int:
    enable_compile_cache()
    from repro.core.report import achieved_table
    args.store = resolve_trace_store(args.store)
    if args.machine is None:
        from repro.core.machine import machine_for_device
        args.machine = machine_for_device().name
    store = TraceStore(args.store)
    configs = list(ARCHS) if args.all else (args.config or [])
    if not configs:
        print("record: need --config <name> (repeatable) or --all",
              file=sys.stderr)
        return 2
    failures = 0
    for name in configs:
        try:
            phases, run = build_measured_phases(
                name, smoke=not args.full, seq=args.seq, batch=args.batch,
                amp=args.amp, fusion=args.fusion)
            # dot/conv FLOPs classify onto the AMP policy's compute-dtype
            # ceiling (CPU bf16 legalization, docs/DESIGN.md §9) — keeps
            # trace records consistent with repro.sweep / launch.dryrun
            mm_class = ("bf16" if run.compute_dtype == jnp.bfloat16
                        else None)
            ms = collect_phases(phases, machine=args.machine,
                                iters=args.iters, warmup=args.warmup,
                                matmul_class=mm_class)
            if args.scale_wall != 1.0:
                ms = {k: scale_measurement(m, args.scale_wall)
                      for k, m in ms.items()}
            # the fusion mode is part of the record's identity: a fused
            # wall time is only comparable against other fused runs; the
            # kernel_configs stamp is what the tune store offered at
            # measurement time (repro.obs advisor diffs it later)
            from repro.tune import (active_dispatch_table,
                                    active_kernel_configs)
            rec = record_from_phases(
                name, ms, machine=args.machine,
                meta={"smoke": not args.full, "seq": args.seq,
                      "batch": args.batch, "amp": args.amp,
                      "fusion": args.fusion,
                      "scale_wall": args.scale_wall,
                      "kernel_configs": active_kernel_configs(
                          machine=args.machine),
                      "dispatch_table": active_dispatch_table(
                          machine=args.machine)})
            store.append(rec)
        except Exception:
            failures += 1
            print(f"[FAIL] {name}", file=sys.stderr)
            traceback.print_exc()
            continue
        print(f"[{name}] run {rec.run_id} @ {rec.git_sha[:12]} "
              f"-> {args.store}")
        print(achieved_table({name: ms}))
        print(ascii_timeline(build_timeline(ms)))
        print()
    return 1 if failures else 0


# --------------------------------------------------------------------------
# compare / report
# --------------------------------------------------------------------------

def cmd_compare(args) -> int:
    args.store = resolve_trace_store(args.store)
    store = TraceStore(args.store)
    if args.base or args.new:
        if not (args.base and args.new):
            print("compare: --base and --new go together", file=sys.stderr)
            return 2
        base, new = store.run(args.base), store.run(args.new)
        if base is None or new is None:
            print(f"compare: run id not found in {args.store}",
                  file=sys.stderr)
            return 2
        deltas = compare_records(base, new, args.threshold)
    else:
        configs = args.config or [None]
        deltas = []
        for name in configs:
            deltas.extend(compare_last(store, name, args.threshold,
                                       window=args.window))
    print(format_deltas(deltas, only_flagged=not args.all_cells))
    return 1 if has_regressions(deltas) else 0


def cmd_report(args) -> int:
    from repro.core.report import achieved_table
    args.store = resolve_trace_store(args.store)
    store = TraceStore(args.store)
    configs = args.config or store.configs()
    if not configs:
        print(f"report: no records in {args.store}", file=sys.stderr)
        return 2
    status = 0
    for name in configs:
        recs = store.last(name, n=1)
        if not recs:
            print(f"[{name}] no records", file=sys.stderr)
            status = 2
            continue
        rec = recs[0]
        print(f"[{name}] run {rec.run_id} @ {rec.git_sha[:12]} "
              f"machine={rec.machine} host={rec.host.get('host', '?')} "
              f"backend={rec.host.get('backend', '?')}")
        print(achieved_table({name: rec.phases}))
        print(ascii_timeline(timeline_from_record(rec)))
        print()
    return status


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def _add_store(p) -> None:
    p.add_argument("--store", default=None,
                   help="JSONL store path (default: "
                        f"$REPRO_WORKSPACE/trace.jsonl, else "
                        f"{LEGACY_TRACE_STORE})")


def add_record_parser(sub):
    """``record`` subcommand — shared by ``python -m repro.trace`` and
    the unified ``python -m repro`` CLI (same flags, same cmd)."""
    rec = sub.add_parser("record", help="measure configs, append records")
    rec.add_argument("--config", action="append", choices=list(ALL),
                     help="config name (repeatable)")
    rec.add_argument("--all", action="store_true",
                     help=f"sweep all {len(ARCHS)} assigned archs")
    _add_store(rec)
    rec.add_argument("--machine", default=None,
                     choices=sorted(MACHINES),
                     help="machine model the %%-of-roofline is against "
                          "(default: the model of this process's device)")
    rec.add_argument("--iters", type=int, default=5)
    rec.add_argument("--warmup", type=int, default=2)
    rec.add_argument("--seq", type=int, default=32)
    rec.add_argument("--batch", type=int, default=4)
    rec.add_argument("--amp", default="O1", choices=("O0", "O1", "O2"))
    rec.add_argument("--fusion", default="off", choices=FUSION_MODES,
                     help="fused-kernel routing (repro.kernels.fused); "
                          "stamped into the record's meta so before/after "
                          "traces stay distinguishable")
    rec.add_argument("--full", action="store_true",
                     help="full config instead of the smoke variant")
    rec.add_argument("--scale-wall", type=float, default=1.0,
                     help="multiply measured wall times before storing "
                          "(regression drills / tests)")
    rec.set_defaults(fn=cmd_record)
    return rec


def add_compare_parser(sub):
    cmp_ = sub.add_parser("compare", help="diff runs, flag regressions")
    cmp_.add_argument("--config", action="append",
                      help="restrict to config(s); default: every config "
                           "with >= 2 runs")
    _add_store(cmp_)
    cmp_.add_argument("--base", default=None, help="base run id (prefix ok)")
    cmp_.add_argument("--new", default=None, help="new run id (prefix ok)")
    cmp_.add_argument("--threshold", type=float, default=0.10,
                      help="relative regression threshold (default 0.10)")
    cmp_.add_argument("--window", type=int, default=2,
                      help="compare newest vs (window-1) runs back")
    cmp_.add_argument("--all-cells", action="store_true",
                      help="print every cell, not only flagged ones")
    cmp_.set_defaults(fn=cmd_compare)
    return cmp_


def add_report_parser(sub):
    rep = sub.add_parser("report", help="render the newest stored records")
    rep.add_argument("--config", action="append")
    _add_store(rep)
    rep.set_defaults(fn=cmd_report)
    return rep


def main(argv: Sequence[str] | None = None,
         prog: str = "python -m repro.trace") -> int:
    ap = argparse.ArgumentParser(prog=prog, description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    add_record_parser(sub)
    add_compare_parser(sub)
    add_report_parser(sub)
    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
