"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Spins up the paged-KV continuous-batching engine with random weights (or
a checkpoint) and serves a seeded synthetic arrival trace, reporting
throughput, latency percentiles, and per-phase (prefill/decode) wall —
the runnable counterpart of the ``decode_*`` dry-run cells.  For the
roofline-attributed, workspace-persisted variant use
``python -m repro serve`` (docs/CLI.md).

Example::

    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --smoke \
        --requests 8 --max-new 16
"""

from __future__ import annotations

import argparse
import sys

import jax

from repro.configs.base import RunConfig
from repro.configs.registry import ALL, get_config, get_smoke
from repro.models import build
from repro.models.params import init
from repro.serve.engine import SERVABLE_FAMILIES, Engine
from repro.serve.workload import make_trace
from repro.checkpoint import checkpointer as ckpt
from repro.compile_cache import enable_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list(ALL))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--trace", default="poisson",
                    choices=("poisson", "bursty"))
    ap.add_argument("--rate", type=float, default=1.0)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--amp", default="O1", choices=("O0", "O1", "O2"),
                    help="precision policy; O2 keeps the weights in bf16")
    ap.add_argument("--ckpt", default=None, help="restore params from here")
    args = ap.parse_args(argv)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family not in SERVABLE_FAMILIES:
        print(f"[serve] engine serves token-prompt KV-cache families "
              f"{SERVABLE_FAMILIES}; {cfg.family} models decode via "
              "repro.models.api decode_fn")
        return 2
    enable_compile_cache()
    run = RunConfig(amp=args.amp)
    model = build(cfg)
    params = init(jax.random.PRNGKey(args.seed), model.spec, run.param_dtype)
    if args.ckpt:
        params, _ = ckpt.restore(args.ckpt, params)

    engine = Engine(cfg, run, params, n_slots=args.slots,
                    max_len=args.max_len, page_size=args.page_size,
                    prefill_chunk=args.prefill_chunk)
    reqs = make_trace(args.trace, args.requests, rate=args.rate,
                      seed=args.seed, vocab=cfg.vocab_size,
                      prompt_len=(4, min(16, args.max_len)),
                      max_new=(args.max_new, args.max_new))
    stats = engine.run_trace(reqs)
    print(stats.render())
    problems = stats.gate()
    for p in problems:
        print(f"[serve] GATE: {p}")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
