"""Fused RMSNorm / LayerNorm (+ residual add, + dtype-cast epilogue).

The reference norms (``repro.models.layers``) round-trip through fp32:
under AMP O1/O2 that lowers as convert (zero-AI) kernels around every
norm, and the preceding residual add is its own streaming kernel — the
exact Table-III pattern the census flags.  One Pallas pass does

    r = x + h                     (optional residual input)
    y = norm(r) · scale (+ bias)  (statistics in fp32 VMEM)
    out = y.astype(out_dtype)     (the cast epilogue, free at the write)

reading x (+ h) once from HBM and writing r/y once — the chain's traffic
drops to its unavoidable minimum and the convert launches disappear into
the fusion.  Math is bit-identical to the reference: same fp32 statistics,
same operation order (oracle parity in ``tests/test_fused.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import config as kc
from repro.kernels.fused.common import row_blocked_call


def _rms(xf: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rmsnorm_kernel(x_ref, s_ref, y_ref, *, eps: float):
    xf = x_ref[...].astype(jnp.float32)
    y_ref[...] = _rms(xf, s_ref[...], eps).astype(y_ref.dtype)


def _rmsnorm_res_kernel(x_ref, h_ref, s_ref, r_ref, y_ref, *, eps: float):
    r = x_ref[...] + h_ref[...]
    r_ref[...] = r.astype(r_ref.dtype)
    y_ref[...] = _rms(r.astype(jnp.float32), s_ref[...], eps
                      ).astype(y_ref.dtype)


def _layernorm_kernel(x_ref, s_ref, b_ref, y_ref, *, eps: float):
    xf = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y_ref[...] = (y * s_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(y_ref.dtype)


def fused_rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-5,
                  out_dtype=None, config: kc.KernelConfig | None = None,
                  block_rows: int | None = None,
                  interpret: bool | None = None) -> jax.Array:
    """x (rows, d), scale (d,) → rmsnorm(x)·scale as ``out_dtype``."""
    cfg = kc.resolve("fused_norm", config, block_rows=block_rows)
    (y,) = row_blocked_call(
        functools.partial(_rmsnorm_kernel, eps=eps), [x], [scale],
        [out_dtype or x.dtype], cfg, interpret=interpret)
    return y


def fused_rmsnorm_residual(x: jax.Array, h: jax.Array, scale: jax.Array, *,
                           eps: float = 1e-5, out_dtype=None,
                           config: kc.KernelConfig | None = None,
                           block_rows: int | None = None,
                           interpret: bool | None = None
                           ) -> tuple[jax.Array, jax.Array]:
    """(x + h, rmsnorm(x + h)·scale) in one pass; x/h (rows, d)."""
    cfg = kc.resolve("fused_norm", config, block_rows=block_rows)
    r, y = row_blocked_call(
        functools.partial(_rmsnorm_res_kernel, eps=eps), [x, h], [scale],
        [x.dtype, out_dtype or x.dtype], cfg, interpret=interpret)
    return r, y


def fused_layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array, *,
                    eps: float = 1e-5, out_dtype=None,
                    config: kc.KernelConfig | None = None,
                    block_rows: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """x (rows, d), scale/bias (d,) → layernorm(x)·scale + bias."""
    cfg = kc.resolve("fused_norm", config, block_rows=block_rows)
    (y,) = row_blocked_call(
        functools.partial(_layernorm_kernel, eps=eps), [x], [scale, bias],
        [out_dtype or x.dtype], cfg, interpret=interpret)
    return y


def hbm_bytes(rows: int, d: int, itemsize: int = 2,
              residual: bool = False) -> float:
    """Analytic fused traffic: x (+h) in, y (+r) out, scale once."""
    n_streams = 4 if residual else 2
    return float(n_streams * rows * d * itemsize + 4 * d)
