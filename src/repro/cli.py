"""``python -m repro`` — the paper's workflow as one CLI.

One entry point over every subsystem, all persisting into one workspace
root (``--workspace`` / ``REPRO_WORKSPACE``; default
``./.repro-workspace`` inside a checkout, ``~/.repro`` elsewhere):

* ``characterize`` — machine model: datasheet ceilings, or measured ERT
  ceilings of this host (tuned-empirical through the workspace tune
  store) — paper §II-A;
* ``profile``      — analytical HLO walk of a registry config (kernel
  table, three-term bound, roofline chart) — paper §II-B;
* ``record``       — measured trace appended to the workspace trace
  store (same flags as the old ``repro.trace record``);
* ``serve``        — continuous-batching serving under a seeded arrival
  trace; prefill/decode recorded as separate phases (``repro.serve``);
* ``report``       — re-render the newest stored records, no re-running;
* ``compare``      — cross-run regression gate (non-zero exit on
  regression);
* ``sweep``        — cross-config campaigns (``run`` / ``report``),
  forwarded to ``repro.sweep`` with the workspace store;
* ``tune``         — kernel autotuning (``search`` / ``show`` /
  ``apply``), forwarded to ``repro.tune`` with the workspace store;
* ``net``          — interconnect roofline level (``characterize`` /
  ``report``): measured collective ceilings into the workspace tune
  store, network-bound mesh-scale rankings (``repro.net``);
* ``trend``        — perf-trend sparklines over stored records +
  harvested ``BENCH_*.json`` (``--gate`` exits non-zero on regression);
* ``advise``       — mine stored records for known bottleneck patterns,
  ranked evidence-cited remediations;
* ``merge``        — union a remote workspace's stores into this one
  (fleet view; dedupe + skip-and-report conflicts).

The old ``python -m repro.trace`` / ``repro.sweep`` / ``repro.tune``
entry points still work (same flags, same output) but are deprecated
delegations to this surface.

Examples::

    PYTHONPATH=src python -m repro characterize --empirical --smoke
    PYTHONPATH=src python -m repro profile --config minitron-4b --charts 1
    PYTHONPATH=src python -m repro record --config minitron-4b --iters 5
    PYTHONPATH=src python -m repro serve --config minitron-4b --requests 16
    PYTHONPATH=src python -m repro report
    PYTHONPATH=src python -m repro compare --config minitron-4b
    PYTHONPATH=src python -m repro sweep run --smoke
    PYTHONPATH=src python -m repro tune search --smoke
    PYTHONPATH=src python -m repro net characterize --devices 8 --smoke
    PYTHONPATH=src python -m repro net report
    PYTHONPATH=src python -m repro trend --gate
    PYTHONPATH=src python -m repro advise
    PYTHONPATH=src python -m repro merge /mnt/fleet/hostB/.repro-workspace
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys
from typing import Sequence

from repro.compile_cache import enable_compile_cache
from repro.configs.base import FUSION_MODES
from repro.session.workspace import WORKSPACE_ENV, Workspace

PROG = "python -m repro"

#: workflow order — also the order the subcommands are registered in
SUBCOMMANDS = ("characterize", "profile", "record", "serve", "report",
               "compare", "sweep", "tune", "net", "trend", "advise",
               "merge")


@contextlib.contextmanager
def _workspace_env(root: str):
    """Pin ``REPRO_WORKSPACE`` for the duration of one command so every
    store-default resolution (trace / sweep / tune, including forwarded
    subcommands and spawned sweep workers) lands under one root."""
    prev = os.environ.get(WORKSPACE_ENV)
    os.environ[WORKSPACE_ENV] = root
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop(WORKSPACE_ENV, None)
        else:
            os.environ[WORKSPACE_ENV] = prev


def _session(args):
    """Commands with a ``--machine`` flag default to the model of this
    process's device.  The read-only ones (trend, advise, merge) take the
    machine the workspace was recorded against and never ask JAX for a
    device: on an accelerator that would claim the chip from the process
    measuring on it."""
    from repro.session import Session
    ws = Workspace(args.workspace)
    machine = (args.machine if hasattr(args, "machine")
               else ws.read_header().get("machine", "cpu-host"))
    return Session(machine=machine, workspace=ws)


# --------------------------------------------------------------------------
# session-backed commands
# --------------------------------------------------------------------------

def cmd_characterize(args) -> int:
    enable_compile_cache()
    s = _session(args)
    res = s.characterize(empirical=args.empirical, tuned=not args.untuned,
                         smoke=args.smoke)
    print(res.render())
    print()
    print(s.workspace.describe())
    return res.exit_code


def cmd_profile(args) -> int:
    enable_compile_cache()
    s = _session(args)
    from repro.session.session import TRAIN_PHASES
    try:
        res = s.profile(args.config,
                        phases=tuple(args.phase or TRAIN_PHASES),
                        seq=args.seq, batch=args.batch, amp=args.amp,
                        fusion=args.fusion, smoke=not args.full,
                        measure=args.measure, iters=args.iters,
                        warmup=args.warmup)
    except KeyError as e:
        # unknown registry config: message + exit 2, not a traceback —
        # same convention as repro.sweep / benchmarks.run
        print(f"profile: {e.args[0] if e.args else e}", file=sys.stderr)
        return 2
    print(res.render(charts=args.charts, top_kernels=args.top))
    return res.exit_code


def cmd_serve(args) -> int:
    enable_compile_cache()
    s = _session(args)
    try:
        res = s.serve(args.config, n_requests=args.requests,
                      trace=args.trace, rate=args.rate, burst=args.burst,
                      seed=args.seed, n_slots=args.slots,
                      max_len=args.max_len,
                      prefill_chunk=args.prefill_chunk,
                      page_size=args.page_size, amp=args.amp,
                      fusion=args.fusion, smoke=not args.full,
                      max_ticks=args.max_ticks)
    except KeyError as e:
        print(f"serve: {e.args[0] if e.args else e}", file=sys.stderr)
        return 2
    except ValueError as e:             # non-servable family, bad trace
        print(f"serve: {e}", file=sys.stderr)
        return 2
    print(res.render())
    return res.exit_code


def cmd_trend(args) -> int:
    s = _session(args)
    if args.action == "tag":
        if not args.name:
            print("trend tag: a tag name is required "
                  f"(`{PROG} trend tag NAME [--run RUN_ID]`)",
                  file=sys.stderr)
            return 2
        try:
            res = s.trend_tag(args.name, run_id=args.run)
        except LookupError as e:
            print(f"trend tag: {e}", file=sys.stderr)
            return 2
        print(res.render())
        return res.exit_code
    res = s.trend(config=args.config, gate=args.gate,
                  tolerance=args.tolerance, baseline=args.baseline,
                  max_rows=args.max_rows,
                  bench_dirs=args.bench_dir or None)
    print(res.render())
    return res.exit_code


def cmd_advise(args) -> int:
    s = _session(args)
    res = s.advise(config=args.config, top=args.top)
    print(res.render())
    return res.exit_code


def cmd_merge(args) -> int:
    s = _session(args)
    try:
        res = s.merge(args.remote)
    except FileNotFoundError as e:
        # missing remote root: message + exit 2, same convention as the
        # other subcommands' user errors
        print(f"merge: {e}", file=sys.stderr)
        return 2
    print(res.render())
    return res.exit_code


# record / compare / report share repro.trace.cli's parsers and cmd
# functions verbatim (same flags, same output); the workspace pin above
# makes their default --store land in the workspace.

def _record_with_header(inner):
    """After a successful unified ``record`` into the workspace store,
    refresh the shared machine-provenance header."""
    def run(args) -> int:
        rc = inner(args)            # resolves args.store as a side effect
        ws = Workspace(args.workspace)
        if rc == 0 and os.path.dirname(
                os.path.abspath(args.store)) == ws.root:
            ws.write_header(args.machine)
        return rc
    return run


def _forward(module_main, rest: Sequence[str], prog: str) -> int:
    """Run a sub-CLI's ``main`` on forwarded argv, normalizing SystemExit
    (argparse ``--help``/errors) into a return code."""
    rest = list(rest)
    if rest and rest[0] == "--":            # `repro sweep -- run ...` style
        rest = rest[1:]
    try:
        return int(module_main(rest, prog=prog) or 0)
    except SystemExit as e:                 # argparse --help / usage error
        return int(e.code or 0)


def _forward_subsystem(name: str, rest: Sequence[str]) -> int:
    if name == "sweep":
        from repro.sweep.cli import main as sub_main
    elif name == "net":
        from repro.net.cli import main as sub_main
    else:
        from repro.tune.cli import main as sub_main
    return _forward(sub_main, rest, f"{PROG} {name}")


def _extract_workspace(argv: list[str]) -> tuple[str | None, list[str]]:
    """Pull ``--workspace DIR`` / ``--workspace=DIR`` out of argv wherever
    it appears (before or after the subcommand).  The forwarding fast
    path can't rely on argparse for this: REMAINDER drops a leading
    optional like ``--help`` (bpo-17050), and the forwarded sub-CLIs
    don't know the flag."""
    ws, out, i = None, [], 0
    while i < len(argv):
        a = argv[i]
        if a.startswith("--workspace="):
            ws = a.split("=", 1)[1]
        elif a == "--workspace" and i + 1 < len(argv):
            ws = argv[i + 1]
            i += 1
        else:
            out.append(a)
        i += 1
    return ws, out


# --------------------------------------------------------------------------
# parser
# --------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    from repro.core.machine import MACHINES

    ap = argparse.ArgumentParser(
        prog=PROG, description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workspace", default=None, metavar="DIR",
                    help="workspace root for every store (default: "
                         "$REPRO_WORKSPACE, else ./.repro-workspace in a "
                         "checkout, else ~/.repro)")
    sub = ap.add_subparsers(dest="cmd", required=True)

    def _add_workspace(p) -> None:
        # also accepted after the subcommand (same dest as the top-level
        # flag; SUPPRESS keeps the subparser from clobbering a value the
        # top-level flag already set)
        p.add_argument("--workspace", default=argparse.SUPPRESS,
                       metavar="DIR", help=argparse.SUPPRESS)

    ch = sub.add_parser("characterize",
                        help="machine model: datasheet or measured ERT "
                             "ceilings (paper §II-A)")
    _add_workspace(ch)
    ch.add_argument("--machine", default=None,
                    choices=sorted(MACHINES),
                    help="machine model to start from (default: the "
                         "model of this process's device)")
    ch.add_argument("--empirical", action="store_true",
                    help="measure this host's ceilings (ERT micro-kernels) "
                         "instead of the datasheet numbers")
    ch.add_argument("--untuned", action="store_true",
                    help="single default-sample measurements instead of "
                         "best-of-tuned winners from the workspace tune "
                         "store")
    ch.add_argument("--smoke", action="store_true",
                    help="tiny shapes/spaces (CI preset)")
    ch.set_defaults(fn=cmd_characterize)

    pr = sub.add_parser("profile",
                        help="analytical HLO walk of a registry config "
                             "(paper §II-B)")
    _add_workspace(pr)
    pr.add_argument("--config", required=True,
                    help="registry config name (see repro.configs)")
    pr.add_argument("--machine", default=None,
                    choices=sorted(MACHINES),
                    help="machine model the bounds are against (default: "
                         "the model of this process's device)")
    pr.add_argument("--phase", action="append",
                    choices=("fwd", "bwd", "opt"),
                    help="phase to profile (repeatable; default all three)")
    pr.add_argument("--seq", type=int, default=32)
    pr.add_argument("--batch", type=int, default=4)
    pr.add_argument("--amp", default="O1", choices=("O0", "O1", "O2"))
    pr.add_argument("--fusion", default="off", choices=FUSION_MODES)
    pr.add_argument("--full", action="store_true",
                    help="full config instead of the smoke variant")
    pr.add_argument("--measure", action="store_true",
                    help="also execute the same compiled executables and "
                         "fold wall time in (not persisted; use `record`)")
    pr.add_argument("--iters", type=int, default=5)
    pr.add_argument("--warmup", type=int, default=2)
    pr.add_argument("--charts", type=int, default=0,
                    help="render up to N per-phase roofline charts")
    pr.add_argument("--top", type=int, default=10,
                    help="kernel-table rows per phase")
    pr.set_defaults(fn=cmd_profile)

    from repro.trace.cli import (add_compare_parser, add_record_parser,
                                 add_report_parser)
    rec = add_record_parser(sub)
    rec.set_defaults(fn=_record_with_header(rec.get_default("fn")))
    rep = add_report_parser(sub)
    cmp_ = add_compare_parser(sub)
    # the shared trace parsers gain --workspace only on the unified
    # surface; the legacy `python -m repro.trace` flags stay unchanged
    for p in (rec, rep, cmp_):
        _add_workspace(p)

    sv = sub.add_parser("serve",
                        help="continuous-batching serving under a seeded "
                             "arrival trace; prefill/decode recorded as "
                             "separate phases (repro.serve)")
    _add_workspace(sv)
    sv.add_argument("--config", required=True,
                    help="registry config name (dense/moe families)")
    sv.add_argument("--machine", default=None,
                    choices=sorted(MACHINES),
                    help="machine model the bounds are against (default: "
                         "the model of this process's device)")
    sv.add_argument("--requests", type=int, default=16,
                    help="arrival-trace length (default 16)")
    sv.add_argument("--trace", default="poisson",
                    choices=("poisson", "bursty"),
                    help="arrival process (default poisson)")
    sv.add_argument("--rate", type=float, default=1.0,
                    help="arrivals (or bursts) per tick (default 1.0)")
    sv.add_argument("--burst", type=int, default=4,
                    help="requests per burst for --trace bursty")
    sv.add_argument("--seed", type=int, default=0,
                    help="workload + weight-init seed (default 0)")
    sv.add_argument("--slots", type=int, default=4,
                    help="concurrent sequence slots (default 4)")
    sv.add_argument("--max-len", type=int, default=64,
                    help="max tokens per sequence incl. prompt")
    sv.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt tokens prefetched per tick (default 16)")
    sv.add_argument("--page-size", type=int, default=16,
                    help="KV-cache page size in tokens (default 16)")
    sv.add_argument("--amp", default="O1", choices=("O0", "O1", "O2"))
    sv.add_argument("--fusion", default="off", choices=FUSION_MODES)
    sv.add_argument("--full", action="store_true",
                    help="full config instead of the smoke variant")
    sv.add_argument("--max-ticks", type=int, default=4096,
                    help="tick budget before the run is cut off")
    sv.set_defaults(fn=cmd_serve)

    tr = sub.add_parser("trend",
                        help="perf-trend sparklines over stored records "
                             "+ BENCH_*.json; --gate = CI regression "
                             "gate (repro.obs)")
    _add_workspace(tr)
    tr.add_argument("action", nargs="?", choices=("tag",),
                    help="`trend tag NAME [--run ID]` pins a known-good "
                         "run for --baseline gating")
    tr.add_argument("name", nargs="?",
                    help="tag name for `trend tag`")
    tr.add_argument("--run", default=None,
                    help="run id to tag (default: newest trace record)")
    tr.add_argument("--config", default=None,
                    help="restrict trace series to one registry config")
    tr.add_argument("--machine", default="cpu-host",
                    choices=sorted(MACHINES),
                    help="machine model stamped into the result")
    tr.add_argument("--gate", action="store_true",
                    help="exit 1 when any lower-is-better series "
                         "regressed past --tolerance vs its history")
    tr.add_argument("--tolerance", type=float, default=None,
                    help="relative regression tolerance (default 0.25)")
    tr.add_argument("--baseline", default=None, metavar="TAG_OR_RUN",
                    help="pin the gate to a tagged known-good run "
                         "(`trend tag` name or run id) instead of the "
                         "rolling median")
    tr.add_argument("--max-rows", type=int, default=40,
                    help="series rows to print (default 40)")
    tr.add_argument("--bench-dir", action="append", metavar="DIR",
                    help="extra BENCH_*.json dir(s) instead of the "
                         "workspace bench/ default (repeatable)")
    tr.set_defaults(fn=cmd_trend)

    ad = sub.add_parser("advise",
                        help="mine stored records for bottleneck "
                             "patterns; ranked remediations (repro.obs)")
    _add_workspace(ad)
    ad.add_argument("--config", default=None,
                    help="restrict to one registry config")
    ad.add_argument("--machine", default="cpu-host",
                    choices=sorted(MACHINES),
                    help="machine key the tune-store rules check "
                         "(default cpu-host)")
    ad.add_argument("--top", type=int, default=0,
                    help="print only the top N findings (default: all)")
    ad.set_defaults(fn=cmd_advise)

    mg = sub.add_parser("merge",
                        help="union a remote workspace's stores into "
                             "this one (fleet view, repro.obs)")
    _add_workspace(mg)
    mg.add_argument("remote", metavar="REMOTE_ROOT",
                    help="root directory of the workspace to merge in")
    mg.add_argument("--machine", default="cpu-host",
                    choices=sorted(MACHINES),
                    help="machine model stamped into the result")
    mg.set_defaults(fn=cmd_merge)

    # stubs so the top-level --help lists them; actual dispatch happens in
    # main()'s forwarding fast path, never through these parsers
    for name, help_ in (
            ("sweep",
             "cross-config campaigns: run / report (repro.sweep flags)"),
            ("tune",
             "kernel autotuning: search / show / apply (repro.tune flags)"),
            ("net",
             "interconnect level: characterize / report (repro.net "
             "flags)")):
        p = sub.add_parser(name, help=help_, add_help=False)
        p.add_argument("rest", nargs=argparse.REMAINDER,
                       help=f"arguments for `{PROG} {name}` "
                            f"(try `{PROG} {name} --help`)")
    return ap


def main(argv: Sequence[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    explicit_ws, rest = _extract_workspace(argv)
    if rest[:1] and rest[0] in ("sweep", "tune", "net"):
        root = Workspace(explicit_ws).root
        with _workspace_env(root):
            return _forward_subsystem(rest[0], rest[1:])
    ap = build_parser()
    args = ap.parse_args(argv)
    root = Workspace(args.workspace).root
    args.workspace = root
    with _workspace_env(root):
        return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
