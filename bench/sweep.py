#!/usr/bin/env python3
"""Find a serving cell's knee on the chip: the highest arrival rate the
engine keeps up with.

    python3 bench/sweep.py --workload <cell> --rates 2,3,4,5 \
        [--seconds 20] [--seed N]

In one process, one engine serves the cell's mix at each rate in turn
(Poisson arrivals, the mix's lengths), for a lead of ``--lead`` seconds
and a window of ``--seconds``, as a run does, with no check against the
reference.  Each rate prints one JSON line: requests due in the window,
requests still queued and active at the window's close, and the
window's latencies.  A backlog that stays within the slots means the
engine kept up; one that grows with the rate means it did not.  The
cell's rate is set at about four fifths of the knee by hand, in its
traffic file.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="arrival rates to try, requests/s, comma-separated")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--lead", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=4_000_000_007)
    ap.add_argument("--out", default=None,
                    help="also append each line to this file")
    args = ap.parse_args(argv)
    try:
        _bm, ctx = harness.build_ctx(args.workload, args.seed, args.seconds,
                                     False, T_START)
    except harness.BenchError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    if ctx.cfg["runner"] != "serve":
        print("sweep: not a serving cell", file=sys.stderr)
        return 2
    drv = harness.runner_of(ctx.cfg)
    prog = drv.Program(ctx)
    drv.warm(prog)
    for rate in (float(r) for r in args.rates.split(",")):
        prog.idle()
        spec = dict(ctx.traffic, arrivals="poisson", rate_per_s=rate,
                    lead_seconds=args.lead)
        d = drv.drive(prog, spec, args.seed, args.seconds, 0)
        line = {"rate_per_s": rate, "due": len(d.counted),
                "queued_at_close": d.queued_at_close,
                "active_at_close": d.active_at_close,
                "slots": ctx.cfg["n_slots"], **drv.metrics(d),
                "lateness_ms_p99": drv.percentile(d.late_ms or [0.0], 99)}
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
