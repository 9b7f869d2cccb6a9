"""``python -m repro net`` / ``python -m repro.net`` — interconnect level.

* ``characterize`` — measure this host's collective ceilings (ICI/DCN
  bandwidth + latency) over forced host devices and persist them
  machine-keyed in the workspace tune store.  A second run with the
  same machine key is a pure store hit (zero re-timing) unless
  ``--force``.
* ``report``       — stored ceilings with provenance + the mesh-scale
  ranking over persisted sweep records: which points are
  network-bound, and where each config flips.

Examples::

    PYTHONPATH=src python -m repro net characterize --devices 8 --smoke
    PYTHONPATH=src python -m repro net report --sweep netscale
"""

from __future__ import annotations

import argparse
import sys
from typing import Sequence

from repro.compile_cache import enable_compile_cache

PROG = "python -m repro net"


def cmd_characterize(args) -> int:
    enable_compile_cache()
    from repro.net.characterize import characterize_net
    try:
        out = characterize_net(
            args.machine, n_devices=args.devices,
            sizes=tuple(int(s) for s in args.sizes.split(","))
            if args.sizes else None,
            iters=args.iters, warmup=args.warmup, store=args.store,
            force=args.force, smoke=args.smoke,
            deadline_s=args.deadline)
    except (RuntimeError, ValueError) as e:
        print(f"net characterize: {e}", file=sys.stderr)
        return 2
    tag = "store hit — nothing re-timed" if out["cached"] else \
        f"measured over {out['n_devices']} forced host device(s)"
    print(f"net characterize: {tag} (store {out['store']})")
    from repro.net.report import ceilings_text
    print(ceilings_text(out["machine"], args.store))
    for leg, ops in sorted(out.get("ops", {}).items()):
        for op, fit in sorted(ops.items()):
            print(f"    {leg}/{op:<15} {fit['bytes_per_s'] / 1e9:8.3f} "
                  f"GB/s  lat {fit['latency_s'] * 1e6:7.1f} us")
    return 0


def cmd_report(args) -> int:
    from repro.net.report import render_net_report
    from repro.session.workspace import resolve_sweep_store
    from repro.sweep.aggregate import latest_per_point, sweep_records
    from repro.trace.store import TraceStore
    store = TraceStore(resolve_sweep_store(args.sweep_store))
    recs = latest_per_point(sweep_records(store, args.sweep))
    rows = {k: r for k, r in recs.items()
            if args.config is None or r.config == args.config}
    print(render_net_report(rows, machine=args.machine, store=args.store))
    # same contract as Session.net_report: ceilings always print, but an
    # empty ranking is a non-zero exit (nothing swept yet)
    return 0 if rows else 1


def build_parser(prog: str | None = None) -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog=prog or PROG, description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd", required=True)

    ch = sub.add_parser("characterize",
                        help="measure collective ceilings into the "
                             "workspace tune store (store hit on re-run)")
    ch.add_argument("--machine", default="cpu-host",
                    help="machine key the ceilings are stored under")
    ch.add_argument("--devices", type=int, default=8,
                    help="forced host device count (even; default 8)")
    ch.add_argument("--sizes", default=None,
                    help="comma-separated per-device float32 elements "
                         "per sample (default: built-in sweep)")
    ch.add_argument("--iters", type=int, default=3)
    ch.add_argument("--warmup", type=int, default=1)
    ch.add_argument("--smoke", action="store_true",
                    help="small size sweep (CI preset)")
    ch.add_argument("--force", action="store_true",
                    help="re-measure even when the store already has "
                         "ceilings for this machine key")
    ch.add_argument("--store", default=None,
                    help="tune-store path (default: workspace tune.json)")
    ch.add_argument("--deadline", type=float, default=900.0,
                    help="watchdog kill deadline for the measurement "
                         "worker, seconds (default 900)")
    ch.set_defaults(fn=cmd_characterize)

    rp = sub.add_parser("report",
                        help="stored ceilings + mesh-scale network-bound "
                             "ranking over persisted sweep records")
    rp.add_argument("--machine", default="cpu-host",
                    help="machine key to read ceilings for")
    rp.add_argument("--sweep", default=None,
                    help="restrict to one campaign name")
    rp.add_argument("--config", default=None,
                    help="restrict to one registry config")
    rp.add_argument("--store", default=None,
                    help="tune-store path (default: workspace tune.json)")
    rp.add_argument("--sweep-store", default=None,
                    help="sweep-store path (default: workspace "
                         "sweep.jsonl)")
    rp.set_defaults(fn=cmd_report)
    return ap


def main(argv: Sequence[str] | None = None, prog: str | None = None) -> int:
    args = build_parser(prog).parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
