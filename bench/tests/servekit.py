"""A serving cell's context at minitron-4b's SMOKE widths on the CPU."""

from __future__ import annotations

import json
import os
import time

import benchkit
from benchlib import harness
from benchlib import traffic as T


def config() -> dict:
    with open(os.path.join(benchkit.FIXTURES, "minitron-tiny.json")) as f:
        cfg = json.load(f)
    # the reference beside the test's configuration is the real one's
    cfg["_file"] = os.path.join(benchkit.BENCH, "configs", "minitron-4b.json")
    return cfg


def ctx(traffic: str = "serve-tiny", seed: int = 2 ** 31 + 77,
        seconds: float = 0.8) -> harness.Ctx:
    import jax
    cfg = config()
    return harness.Ctx(
        seed=seed, seconds=seconds, trace=False, chips=1,
        devices=jax.devices()[:1], cfg=cfg,
        traffic=T.load(traffic, benchkit.FIXTURES),
        cell={"name": "test", "chips": 1},
        tracer=harness.Tracer(False, seconds),
        counter=harness.CompileCounter(), t_start=time.perf_counter(),
        limits=dict(cfg["limits"]))
