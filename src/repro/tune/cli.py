"""``python -m repro.tune`` — search / show / apply kernel autotuning.

Subcommands (same store/CLI conventions as ``repro.trace`` and
``repro.sweep``):

* ``search`` — time every candidate config of one or more kernels through
  the shared compile-once harness and persist the winner per
  (kernel, shape, dtype, machine, backend) in the tune store.  A point
  already in the store is a pure hit (no re-timing) unless ``--force``.
  ``--ceilings`` additionally runs the XLA-oracle ceiling searches that
  feed ``empirical_cpu_spec``; ``--smoke`` is the CI preset (tiny shapes,
  tiny spaces, ceilings included).
* ``show``   — print the stored winners (params, wall, objective,
  speedup vs the hardcoded default) without running anything.
* ``apply``  — re-time default vs tuned for every stored Pallas winner
  and verify the speedup still holds on this host; exits non-zero if a
  "winner" has gone stale (slower than default beyond --tolerance).
* ``dispatch {search,show,apply}`` — the site-keyed fused-vs-reference
  dispatch table (docs/DESIGN.md §16).  ``dispatch search`` traces one
  config's train phases under ``fusion="auto"`` and measures every
  dispatch site it encounters (store hit → no re-timing, so a second
  pass over the same workspace performs zero timings); ``dispatch show``
  prints the stored winners; ``dispatch apply`` re-times each site and
  exits non-zero if a stored winner is now slower than the impl it beat
  beyond --tolerance.

Examples::

    PYTHONPATH=src python -m repro.tune search --kernel triad --kernel ert_gemm
    PYTHONPATH=src python -m repro.tune search --smoke --store /tmp/tune.json
    PYTHONPATH=src python -m repro.tune show
    PYTHONPATH=src python -m repro.tune apply --tolerance 0.10
    PYTHONPATH=src python -m repro.tune dispatch search --config minitron-4b
    PYTHONPATH=src python -m repro.tune dispatch show
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback
from typing import Sequence

from repro.compile_cache import enable_compile_cache
from repro.core.machine import MACHINES
from repro.tune import space as sp
from repro.tune.search import search, search_all, tune_ceilings
from repro.tune.store import TuneStore, default_store_path


def _parse_shape(text: str) -> tuple[int, ...]:
    for sep in ("x", ","):
        if sep in text:
            return tuple(int(p) for p in text.split(sep) if p.strip())
    return (int(text),)


def cmd_search(args) -> int:
    enable_compile_cache()
    store = TuneStore(args.store)
    known = (sp.XLA_KERNELS if args.backend == "xla"
             else sp.PALLAS_KERNELS)
    kernels = args.kernel or list(known)
    bad = [k for k in kernels if k not in known]
    if bad:
        print(f"search: no {args.backend} search space for "
              f"{', '.join(bad)} (valid: {', '.join(known)})",
              file=sys.stderr)
        return 2
    if args.shape and len(kernels) != 1:
        print("search: --shape needs exactly one --kernel", file=sys.stderr)
        return 2
    failures = 0
    for kernel in kernels:
        try:
            outcome = search(
                kernel,
                shape=_parse_shape(args.shape) if args.shape else None,
                dtype=args.dtype, machine=args.machine,
                backend=args.backend, store=store, iters=args.iters,
                warmup=args.warmup, smoke=args.smoke, force=args.force)
            print(outcome.describe())
        except Exception:
            failures += 1
            print(f"[FAIL] {kernel}", file=sys.stderr)
            traceback.print_exc()
    if args.ceilings or args.smoke:
        try:
            tune_ceilings(machine=args.machine, store=store,
                          iters=args.iters, warmup=args.warmup,
                          smoke=args.smoke, force=args.force,
                          progress=print)
        except Exception:
            failures += 1
            print("[FAIL] ceilings", file=sys.stderr)
            traceback.print_exc()
    print(f"store: {store.path} ({len(list(store.keys()))} winners)")
    return 1 if failures else 0


def cmd_show(args) -> int:
    store = TuneStore(args.store)
    recs = store.records()
    if args.kernel:
        recs = [r for r in recs if r.kernel in args.kernel]
    if not recs:
        print(f"show: no tuned records in {store.path}", file=sys.stderr)
        return 2
    hdr = (f"{'kernel':<16} {'be':<6} {'shape':<18} {'dtype':<9} "
           f"{'params':<38} {'wall':>10} {'speedup':>8}  age")
    print(hdr)
    print("-" * len(hdr))
    now = time.time()
    for r in recs:
        params = ",".join(f"{k}={v}" for k, v in sorted(r.params.items()))
        age_h = (now - r.timestamp) / 3600 if r.timestamp else 0.0
        print(f"{r.kernel:<16} {r.backend:<6} "
              f"{'x'.join(map(str, r.shape)):<18} {r.dtype:<9} "
              f"{params or '-':<38} {r.wall_s*1e6:>8.1f}us "
              f"{r.speedup:>7.2f}x  {age_h:.1f}h")
    return 0


def cmd_apply(args) -> int:
    enable_compile_cache()
    from repro.tune.search import _time_candidate
    store = TuneStore(args.store)
    recs = [r for r in store.records() if r.backend == "pallas"]
    if args.kernel:
        recs = [r for r in recs if r.kernel in args.kernel]
    if not recs:
        print(f"apply: no Pallas winners in {store.path}", file=sys.stderr)
        return 2
    stale = 0
    for r in recs:
        cands = sp.candidates(r.kernel, r.shape, r.dtype, "pallas")
        tuned = next((c for c in cands if c.dict == r.params), None)
        default = next(
            (c for c in cands
             if sp.is_default(r.kernel, "pallas", r.shape, c.dict,
                              r.dtype)), None)
        if tuned is None or default is None:
            print(f"[stale] {r.kernel} {r.shape}: stored params "
                  f"{r.params} no longer in the search space — re-search")
            stale += 1
            continue
        wall_d = _time_candidate(default, args.iters, args.warmup)
        wall_t = (wall_d if tuned.params == default.params
                  else _time_candidate(tuned, args.iters, args.warmup))
        speed = wall_d / wall_t if wall_t else 0.0
        ok = speed >= 1.0 - args.tolerance
        mark = "ok  " if ok else "LOST"
        print(f"[{mark}] {r.kernel:<16} {'x'.join(map(str, r.shape)):<16} "
              f"default {wall_d*1e6:9.1f}us -> tuned {wall_t*1e6:9.1f}us "
              f"({speed:.2f}x)")
        if not ok:
            stale += 1
    return 1 if stale else 0


def cmd_dispatch_search(args) -> int:
    enable_compile_cache()
    from repro.tune import dispatch as dsp
    store = TuneStore(args.store)
    try:
        outcome = dsp.search_sites(
            args.config, seq=args.seq, batch=args.batch, amp=args.amp,
            machine=args.machine, store=store, iters=args.iters,
            warmup=args.warmup, smoke=not args.full, force=args.force)
    except Exception:
        print("[FAIL] dispatch search", file=sys.stderr)
        traceback.print_exc()
        return 1
    print(outcome.describe())
    print(f"store: {store.path} "
          f"({len(list(store.dispatch_keys()))} dispatch winners)")
    return 0


def cmd_dispatch_show(args) -> int:
    from repro.tune import dispatch as dsp
    recs = dsp.dispatch_table(TuneStore(args.store))
    if not recs:
        print(f"dispatch show: no dispatch records in {args.store}",
              file=sys.stderr)
        return 2
    hdr = (f"{'op':<14} {'shapes':<22} {'dtypes':<18} {'flags':<26} "
           f"{'fused':>10} {'ref':>10} {'winner':<10} {'speedup':>7}")
    print(hdr)
    print("-" * len(hdr))
    for r in recs:
        shapes = ",".join("x".join(map(str, s)) for s in r.shapes)
        flags = ",".join(f"{k}={v}" for k, v in sorted(r.flags.items()))
        print(f"{r.op:<14} {shapes:<22} {','.join(r.dtypes):<18} "
              f"{flags or '-':<26} {r.fused_wall_s*1e6:>8.1f}us "
              f"{r.ref_wall_s*1e6:>8.1f}us {r.impl:<10} "
              f"{r.speedup:>6.2f}x")
    return 0


def cmd_dispatch_apply(args) -> int:
    enable_compile_cache()
    from repro.tune import dispatch as dsp
    store = TuneStore(args.store)
    recs = dsp.dispatch_table(store)
    if not recs:
        print(f"dispatch apply: no dispatch records in {args.store}",
              file=sys.stderr)
        return 2
    stale = 0
    for old in recs:
        key = dsp.DispatchKey(
            op=old.op, shapes=tuple(tuple(s) for s in old.shapes),
            dtypes=tuple(old.dtypes),
            flags=tuple(sorted(old.flags.items())), machine=old.machine)
        new = dsp.measure_site(key, store=store, iters=args.iters,
                               warmup=args.warmup)
        walls = {"fused": new.fused_wall_s, "reference": new.ref_wall_s}
        held = (walls[old.impl]
                <= walls["fused" if old.impl == "reference" else
                         "reference"] * (1.0 + args.tolerance))
        mark = "ok  " if held else "LOST"
        print(f"[{mark}] {new.describe()}  (was {old.impl})")
        if not held:
            stale += 1
    return 1 if stale else 0


def main(argv: Sequence[str] | None = None,
         prog: str = "python -m repro.tune") -> int:
    ap = argparse.ArgumentParser(prog=prog, description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    def _common(p) -> None:
        p.add_argument("--store", default=default_store_path(),
                       help="tune store path (default "
                            f"{default_store_path()}; env REPRO_WORKSPACE "
                            "governs it, REPRO_TUNE_STORE is a deprecated "
                            "override)")
        p.add_argument("--kernel", action="append",
                       choices=list(sp.PALLAS_KERNELS),
                       help="kernel name (repeatable; default: all)")

    se = sub.add_parser("search", help="time candidate configs, persist "
                                       "winners (store hit = no re-timing)")
    _common(se)
    se.add_argument("--shape", default=None,
                    help="problem shape, e.g. 512x512x512 (needs exactly "
                         "one --kernel; default: per-kernel standard shape)")
    se.add_argument("--dtype", default="float32",
                    choices=("float32", "bfloat16"))
    se.add_argument("--machine", default="cpu-host",
                    choices=sorted(MACHINES),
                    help="machine key the winners are stored under")
    se.add_argument("--backend", default="pallas", choices=("pallas", "xla"),
                    help="pallas: tile search on the kernels themselves; "
                         "xla: oracle ceiling measurements")
    se.add_argument("--iters", type=int, default=3)
    se.add_argument("--warmup", type=int, default=1)
    se.add_argument("--smoke", action="store_true",
                    help="CI preset: tiny shapes + spaces, ceilings too")
    se.add_argument("--ceilings", action="store_true",
                    help="also run the XLA-oracle ceiling searches")
    se.add_argument("--force", action="store_true",
                    help="re-time even on a store hit")
    se.set_defaults(fn=cmd_search)

    sh = sub.add_parser("show", help="print stored winners, no re-running")
    _common(sh)
    sh.set_defaults(fn=cmd_show)

    app = sub.add_parser("apply", help="re-time default vs tuned winners, "
                                       "verify the speedup holds")
    _common(app)
    app.add_argument("--iters", type=int, default=3)
    app.add_argument("--warmup", type=int, default=1)
    app.add_argument("--tolerance", type=float, default=0.10,
                     help="allowed tuned-vs-default slowdown before a "
                          "winner counts as stale (default 0.10)")
    app.set_defaults(fn=cmd_apply)

    dp = sub.add_parser("dispatch", help="site-keyed fused-vs-reference "
                                         "dispatch table")
    dsub = dp.add_subparsers(dest="dispatch_cmd", required=True)

    def _dcommon(p) -> None:
        p.add_argument("--store", default=default_store_path(),
                       help="tune store path (the dispatch table lives in "
                            "its 'dispatch' namespace)")

    ds = dsub.add_parser("search", help="trace one config under "
                                        "fusion=auto and measure every "
                                        "dispatch site (store hit = no "
                                        "re-timing)")
    _dcommon(ds)
    ds.add_argument("--config", default="minitron-4b",
                    help="model config whose train phases to trace")
    ds.add_argument("--seq", type=int, default=16)
    ds.add_argument("--batch", type=int, default=2)
    ds.add_argument("--amp", default="O1", choices=("O0", "O1", "O2"))
    ds.add_argument("--machine", default="cpu-host",
                    choices=sorted(MACHINES))
    ds.add_argument("--iters", type=int, default=3)
    ds.add_argument("--warmup", type=int, default=1)
    ds.add_argument("--full", action="store_true",
                    help="trace the full config, not the smoke variant")
    ds.add_argument("--force", action="store_true",
                    help="re-measure even on a store hit")
    ds.set_defaults(fn=cmd_dispatch_search)

    dsh = dsub.add_parser("show", help="print the stored dispatch winners")
    _dcommon(dsh)
    dsh.set_defaults(fn=cmd_dispatch_show)

    dap = dsub.add_parser("apply", help="re-measure every stored site and "
                                        "verify each winner still wins")
    _dcommon(dap)
    dap.add_argument("--iters", type=int, default=3)
    dap.add_argument("--warmup", type=int, default=1)
    dap.add_argument("--tolerance", type=float, default=0.10,
                     help="allowed winner-vs-loser slowdown before a site "
                          "counts as stale (default 0.10)")
    dap.set_defaults(fn=cmd_dispatch_apply)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
