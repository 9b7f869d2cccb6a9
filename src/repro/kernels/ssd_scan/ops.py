"""Model-facing wrapper for the SSD kernel (layout of repro.models.ssm).

``pallas_call`` has no autodiff rule, so the wrapper is a ``custom_vjp``:
kernel forward, reference-math backward (recompute — the same policy the
chunk-remat XLA path uses; a dedicated backward kernel replaces it on
real TPU hardware).

The chunk size routes through ``repro.tune.best_config`` when the caller
passes ``chunk=None``: a persisted tuned winner for this
(shape, dtype, machine) wins, else the 128 default.  Model code that has
its own chunk policy (``repro.models.ssm``) keeps passing it explicitly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_scan


def _lookup_chunk(b: int, h: int, s: int, p: int, n: int, dtype) -> int:
    from repro.tune import best_config
    cfg = best_config("ssd_scan", (b, h, s, p, n),
                      dtype=jnp.dtype(dtype).name)
    return int(cfg.get("chunk"))


@functools.lru_cache(maxsize=8)
def _make(chunk: int | None, interpret: bool | None):
    def _ref(xh, a, B_, C_, q):
        from repro.models.ssm import ssd_chunked
        y, _ = ssd_chunked(xh, a, B_, C_, min(q, xh.shape[1]))
        return y

    @jax.custom_vjp
    def ssd(xh, a, B_, C_):
        B, S, H, P = xh.shape
        N = B_.shape[-1]
        q = chunk if chunk is not None else _lookup_chunk(
            B, H, S, P, N, xh.dtype)
        y = ssd_scan(xh.transpose(0, 2, 1, 3), a.transpose(0, 2, 1),
                     B_, C_, chunk=q, interpret=interpret)
        return y.transpose(0, 2, 1, 3)

    def fwd(xh, a, B_, C_):
        return ssd(xh, a, B_, C_), (xh, a, B_, C_)

    def bwd(res, g):
        xh, a, B_, C_ = res
        q = chunk if chunk is not None else _lookup_chunk(
            xh.shape[0], xh.shape[2], xh.shape[1], xh.shape[3],
            B_.shape[-1], xh.dtype)
        _, vjp = jax.vjp(lambda w, x, y, z: _ref(w, x, y, z, q),
                         xh, a, B_, C_)
        return vjp(g)

    ssd.defvjp(fwd, bwd)
    return ssd


def ssd_scan_model_layout(xh: jax.Array, a_log_dt: jax.Array,
                          B_: jax.Array, C_: jax.Array,
                          chunk: int | None = None,
                          interpret: bool | None = None) -> jax.Array:
    """xh (B, S, H, P), a_log_dt (B, S, H), B_/C_ (B, S, N) → (B, S, H, P).

    ``chunk=None`` → the tuned winner for this shape (default 128).
    """
    return _make(chunk, interpret)(xh, a_log_dt, B_, C_)
