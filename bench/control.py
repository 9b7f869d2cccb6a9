#!/usr/bin/env python3
"""Readings behind each cell's limits, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seeds 12 --control 12 \
        [--first-seed N]

For each of ``--seeds`` seeds it reads every number the cell compares
with the program in place, as a run does, through the set-up steps the
reference follows; for the first ``--control`` of them also with the
control in the program's place: the reference computed with float8
inputs to its convolutions, the next precision below the configuration's
bfloat16.  Each seed prints one JSON line, with what a run's ``correct``
makes of each side's numbers under the cell's limits.  The benchmark's
own runs never run this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import harness  # noqa: E402


def _correct(numbers: dict, limits: dict) -> bool:
    """What a run's ``correct`` makes of these numbers under the cell's
    own limits."""
    return harness.all_within(harness.fmt_checks(numbers, limits))


def train_readings(ctx, seeds, n_control):
    drv = harness.runner_of(ctx.cfg)
    prog, ref = drv.Program(ctx), drv.Reference(ctx)
    quant = harness.reference_of(ctx.cfg).fp8_conv
    import jax
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(prog.trainer.state.params)[0]]
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        mine = prog.start(seed)
        want = ref.readings(seed, prog.pool)
        g, _d = drv.leaf_gaps(mine, want)
        numbers = drv.compare(mine, want)
        line = {"seed": seed, "program": numbers,
                "program_correct": _correct(numbers, ctx.limits),
                "losses": mine["losses"], "ref_losses": want["losses"],
                "worst_grad_leaves": [[paths[j], float(g[j])]
                                      for j in np.argsort(-g)[:3]]}
        if i < n_control:
            low = ref.readings(seed, prog.pool, quant=quant)
            line["control"] = drv.compare(low, want)
            line["control_correct"] = _correct(line["control"], ctx.limits)
            line["control_losses"] = low["losses"]
        line["seconds"] = time.perf_counter() - t
        yield line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--out", default=None,
                    help="also append each line to this file")
    args = ap.parse_args(argv)
    try:
        _bm, ctx = harness.build_ctx(args.workload, args.first_seed,
                                     0.0, False, T_START)
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    for line in train_readings(ctx, seeds, args.control):
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
