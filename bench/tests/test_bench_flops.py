"""The pinned model sizes and FLOPs, recomputed from the references'
shapes at full size without running anything, and the HLO reader's
convolution FLOPs on a compiled program."""

import json
import os

import benchkit
from benchlib import harness, hlo


def _cfg(name):
    path = os.path.join(benchkit.BENCH, "configs", f"{name}.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["_file"] = path
    return cfg


def test_deepcam_flops_and_parameters_are_pinned():
    cfg = _cfg("deepcam")
    ref = harness.reference_of(cfg)
    assert ref.n_params(cfg) == cfg["parameters"] == 41_593_491
    assert ref.train_flops_per_example(cfg) == cfg["train_flops_per_image"]
    # fewer than 3x the forward: the stem's input needs no gradient
    assert 9.2e12 < cfg["train_flops_per_image"] < 9.4e12


def test_hlo_reader_counts_convolution_flops():
    import jax
    import jax.numpy as jnp
    x = jax.ShapeDtypeStruct((1, 16, 16, 8), jnp.float32)
    w = jax.ShapeDtypeStruct((3, 3, 8, 4), jnp.float32)
    text = jax.jit(lambda x, w: jax.lax.conv_general_dilated(
        x, w, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    ).lower(x, w).compile().as_text()
    convs = [c for c in hlo.costs(text).values()
             if "convolution" in c.opcodes]
    assert len(convs) == 1
    assert convs[0].flops == 2 * 14 * 14 * 4 * 8 * 9
    # operands and result, float32
    assert convs[0].hbm_bytes >= 4 * (16 * 16 * 8 + 9 * 8 * 4 + 14 * 14 * 4)
