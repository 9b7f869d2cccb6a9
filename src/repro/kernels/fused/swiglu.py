"""Fused gated-MLP activation epilogue: ``act(gate) · up`` in one pass.

The reference SwiGLU epilogue (``repro.models.layers._mlp_apply``) lowers
as separate silu, multiply and cast kernels between the two matmuls —
three streaming passes over the (rows, d_ff) activations at zero or near
zero arithmetic intensity.  This kernel reads gate and up once, applies
the activation in fp32, and writes the product once, cast to the compute
dtype at the write.  ``act`` covers both gate flavors the configs use:
``"silu"`` (SwiGLU) and ``"gelu"`` (GeGLU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import config as kc
from repro.kernels.fused.common import row_blocked_call

ACTS = ("silu", "gelu")


def _swiglu_kernel(g_ref, u_ref, o_ref, *, act: str):
    g = g_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    h = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
    o_ref[...] = (h * u).astype(o_ref.dtype)


def fused_swiglu(gate: jax.Array, up: jax.Array, *, act: str = "silu",
                 out_dtype=None, config: kc.KernelConfig | None = None,
                 block_rows: int | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """gate/up (rows, d_ff) → act(gate)·up as ``out_dtype``."""
    if act not in ACTS:
        raise ValueError(f"unknown activation {act!r}; known: {ACTS}")
    cfg = kc.resolve("fused_swiglu", config, block_rows=block_rows)
    (y,) = row_blocked_call(
        functools.partial(_swiglu_kernel, act=act), [gate, up], [],
        [out_dtype or gate.dtype], cfg, interpret=interpret)
    return y


def hbm_bytes(rows: int, d_ff: int, itemsize: int = 2) -> float:
    """Analytic fused traffic: gate + up in, product out."""
    return float(3 * rows * d_ff * itemsize)
