#!/usr/bin/env python3
"""Readings behind each cell's limits, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seeds 12 --control 12 \
        [--first-seed N]

For each of ``--seeds`` seeds it reads every number the cell compares
with the program in place, as a run does; for the first ``--control`` of
them also with the control in the program's place: the reference
computed with float8 inputs to its convolutions or matrix
multiplications, the next precision below the configuration's bfloat16.
A training cell's readings follow the set-up steps the reference
follows.  A serving cell's follow a short window of ``--seconds`` at the
cell's own load, with as many requests sampled as a run samples; the
control is read at the same prompts and served tokens.  Each seed prints
one JSON line, with what a run's ``correct`` makes of each side's numbers
under the cell's limits.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
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


def alter_tokens(engine):
    """The fault of a token altered where it is produced: each request's
    second token is replaced by the next id, and the engine goes on from
    it.  Returns a function that takes the fault out again."""
    finish = engine._maybe_finish
    vocab = engine.cfg.vocab_size

    def maybe_finish(slot_idx, tok):
        slot = engine._slots[slot_idx]
        if len(slot.req.out) == 2:
            tok = (tok + 1) % vocab
            slot.req.out[-1] = slot.next_tok = tok
        return finish(slot_idx, tok)
    engine._maybe_finish = maybe_finish
    return lambda: engine.__dict__.pop("_maybe_finish")


def serve_numbers(drv, prog, ref, ctx, seed, check, fault=False):
    """One short drive at the cell's load and the numbers compared."""
    prog.start(seed)
    drv.warm(prog)
    undo = alter_tokens(prog.engine) if fault else None
    try:
        d = drv.drive(prog, ctx.traffic, seed, ctx.seconds, check)
    finally:
        if undo:
            undo()
    params = prog.engine.params
    served = [(d.requests[u].prompt, list(d.requests[u].out), prog.rows[u])
              for u in d.sample]
    numbers = drv.compare([ref.gaps(params, *s) for s in served])
    return d, served, numbers


def serve_readings(ctx, seeds, n_control, n_faults=0):
    drv = harness.runner_of(ctx.cfg)
    prog, ref = drv.Program(ctx), drv.Reference(ctx)
    check = int(ctx.traffic["check_requests"])
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        d, served, numbers = serve_numbers(drv, prog, ref, ctx, seed, check)
        line = {"seed": seed, "program": numbers,
                "program_correct": _correct(numbers, ctx.limits),
                "served_tokens": [len(s[1]) for s in served],
                "prompt_tokens": [len(s[0]) for s in served],
                "window": drv.metrics(d)}
        if i < n_control:
            params = prog.engine.params
            line["control"] = drv.compare(
                [ref.gaps(params, p, out, None, quant=True)
                 for p, out, _rows in served])
            line["control_correct"] = _correct(line["control"], ctx.limits)
            del params
        # one set of weights on the chip at a time: the next drive's are
        # made while the engine holds none
        del served, d
        prog.rows = {}
        gc.collect()
        if i < n_faults:
            line["token_fault"] = serve_numbers(drv, prog, ref, ctx, seed,
                                                check, fault=True)[2]
            line["token_fault_correct"] = _correct(line["token_fault"],
                                                   ctx.limits)
            prog.rows = {}
            gc.collect()
        line["seconds"] = time.perf_counter() - t
        yield line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--faults", type=int, default=0,
                    help="a serving cell: for the first N seeds also read "
                    "the program with a token altered where it is produced")
    ap.add_argument("--seconds", type=float, default=12.0,
                    help="a serving cell's window at its own load")
    ap.add_argument("--out", default=None,
                    help="also append each line to this file")
    args = ap.parse_args(argv)
    try:
        _bm, ctx = harness.build_ctx(args.workload, args.first_seed,
                                     args.seconds, False, T_START)
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    if ctx.cfg["runner"] == "serve":
        lines = serve_readings(ctx, seeds, args.control, args.faults)
    else:
        lines = train_readings(ctx, seeds, args.control)
    for line in lines:
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
