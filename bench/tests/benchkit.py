"""Runs a cell's runner on the CPU at a test's size: everything a run
does after the harness's look for a chip."""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "fixtures")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import harness  # noqa: E402
from benchlib import traffic as T  # noqa: E402


def config(name: str) -> dict:
    path = os.path.join(FIXTURES, f"{name}.json")
    with open(path) as f:
        cfg = json.load(f)
    # the reference beside the test's configuration is the real one's
    real = {"deepcam-tiny": "deepcam"}[name]
    cfg["_file"] = os.path.join(BENCH, "configs", f"{real}.json")
    return cfg


def ctx(cfg_name: str, traffic_name: str, seed: int = 7,
        seconds: float = 2.0, limits: dict | None = None) -> harness.Ctx:
    import jax
    cfg = config(cfg_name)
    spec = T.load(traffic_name, FIXTURES)
    return harness.Ctx(
        seed=seed, seconds=seconds, trace=False, chips=1,
        devices=jax.devices()[:1], cfg=cfg, traffic=spec,
        cell={"name": "test", "chips": 1},
        tracer=harness.Tracer(False, seconds),
        counter=harness.CompileCounter(), t_start=time.perf_counter(),
        limits=dict(limits if limits is not None else cfg["limits"]))


def run(c: harness.Ctx) -> harness.Outcome:
    return harness.runner_of(c.cfg).run(c)
