"""The traffic generator: it reads a mix's data file and makes its work.

A mix is a JSON file in ``bench/traffic/``.  Its ``kind`` names the
runner that reads it; a ``train`` mix gives the global batch and how many
batches of synthetic climate images set-up makes into a pool; a
``serve`` mix gives how requests arrive (``arrivals``: ``poisson`` at
``rate_per_s``, or a standing ``backlog``) and the lognormal lengths of
their prompts and outputs.  Every seed gets the same sizes; the seed
changes only the values of the inputs (and, for requests, their order),
and never reaches the program except as the inputs made here.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")


def load(name: str, directory: str = TRAFFIC_DIR) -> dict:
    path = os.path.join(directory, f"{name}.json")
    with open(path) as f:
        spec = json.load(f)
    if not isinstance(spec.get("kind"), str):
        raise ValueError(f"{path}: a mix names its kind")
    return spec


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one use of one seed; any non-negative whole
    number is a valid seed, however large."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    words = [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF,
             *(ord(c) for c in stream)]
    return np.random.default_rng(words)


def climate_batch(seed: int, index: int, batch: int, hw: tuple[int, int],
                  channels: int, classes: int) -> dict[str, np.ndarray]:
    """One batch of synthetic climate fields and segmentation labels.

    Smooth fields (noise at 1/8 resolution, repeated 8x8) with the rare
    classes where channel 0 is extreme, as the program's own
    ``ClimateStream`` makes them.
    """
    if classes != 3:
        raise ValueError("the label rule makes 3 classes")
    rng = rng_for(seed, f"images{index}")
    H, W = hw
    low = rng.standard_normal((batch, max(H // 8, 1), max(W // 8, 1),
                               channels), dtype=np.float32)
    img = np.repeat(np.repeat(low, 8, axis=1), 8, axis=2)[:, :H, :W, :]
    img = np.ascontiguousarray(img)
    a = img[..., 0]
    lab = np.zeros((batch, H, W), np.int32)
    lab[a > 1.2] = 1          # "tropical cyclone"
    lab[a < -1.2] = 2         # "atmospheric river"
    return {"images": img, "labels": lab}


# --------------------------------------------------------------------------
# requests
# --------------------------------------------------------------------------

def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths, lognormal about ``median`` with ``sigma``, rounded and
    clipped to [``min``, ``max``]."""
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


@dataclasses.dataclass
class ServePlan:
    """The requests of one run of a serving mix.

    ``due[i]`` is request ``i``'s arrival, in seconds after arrivals
    begin, or ``None`` for a backlog, whose requests are due when the
    runner tops the queue up.  The set of sizes, and of the gaps between
    arrivals, is the mix's own, the same for every seed; the seed orders
    them and draws the prompts' tokens."""

    seed: int
    vocab: int
    prompt_len: np.ndarray
    max_new: np.ndarray
    due: np.ndarray | None

    def __len__(self) -> int:
        return len(self.prompt_len)

    def prompt(self, i: int) -> np.ndarray:
        rng = rng_for(self.seed, f"prompt{i}")
        return rng.integers(0, self.vocab, int(self.prompt_len[i]),
                            dtype=np.int32)


def serve_plan(spec: dict, seed: int, seconds: float, vocab: int
               ) -> ServePlan:
    """The requests a serving mix makes for a run of ``seconds`` after its
    ``lead_seconds``.  Poisson arrivals: ``rate_per_s`` x (lead + window)
    requests, their exponential gaps scaled so that they fill that time
    exactly.  A backlog: a pool of ``pool`` requests, cycled if a run
    uses more."""
    fixed = rng_for(0, "sizes")            # the mix's sizes: no seed
    order = rng_for(seed, "order")
    if spec["arrivals"] == "poisson":
        span = float(spec["lead_seconds"]) + float(seconds)
        n = max(1, round(spec["rate_per_s"] * span))
        gaps = fixed.exponential(1.0, n)
        gaps *= span / gaps.sum()
        due = np.concatenate([[0.0], np.cumsum(order.permutation(gaps))[:-1]])
    elif spec["arrivals"] == "backlog":
        n, due = int(spec["pool"]), None
    else:
        raise ValueError(f"unknown arrivals {spec['arrivals']!r}")
    prompt = lengths(spec["prompt_len"], n, fixed)
    new = lengths(spec["output_len"], n, fixed)
    perm = order.permutation(n)
    return ServePlan(seed, vocab, prompt[perm], new[perm], due)
