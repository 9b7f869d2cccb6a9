"""The traffic generator: it reads a mix's data file and makes its work.

A mix is a JSON file in ``bench/traffic/``.  Its ``kind`` names the
runner that reads it; a ``train`` mix gives the global batch and how many
batches of synthetic climate images set-up makes into a pool.  Every
seed gets the same sizes; the seed changes only the values of the
inputs, and never reaches the program except as the inputs made here.
"""

from __future__ import annotations

import json
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
