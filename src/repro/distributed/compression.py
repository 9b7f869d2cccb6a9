"""Gradient compression for the slow (cross-pod / DCN) all-reduce leg.

At 512+ chips the gradient all-reduce crosses pods over DCN (~25 GB/s/chip
vs 200 GB/s aggregate ICI); compressing the cross-pod leg 4x (fp32→int8)
moves the collective roofline term down proportionally.

Scheme (1-bit-Adam-family, here 8-bit):

1. within-pod reduce stays full precision (ICI is fast),
2. the cross-pod exchange quantizes to int8 with a per-tensor fp32 scale
   (stochastic-rounding-free symmetric quant),
3. **error feedback**: the quantization residual is added to the *next*
   step's gradient, making the compression error O(1) over training rather
   than O(T).

``compress/decompress`` are pure and shard_map-safe; ``psum_compressed``
implements the cross-pod all-reduce as int8 all-gather + local fp32
reduction (wire bytes = 1/4 of fp32 ring all-reduce at pod counts ≤ 8).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp



class ErrorFeedback(NamedTuple):
    residual: Any      # same tree as grads, fp32

    @classmethod
    def init(cls, grads_like: Any) -> "ErrorFeedback":
        return cls(jax.tree.map(
            lambda g: jnp.zeros(g.shape, jnp.float32), grads_like))


def compress(g: jax.Array) -> tuple[jax.Array, jax.Array]:
    """fp32 → (int8 payload, fp32 scale). Symmetric linear quant."""
    amax = jnp.max(jnp.abs(g))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def decompress(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compress_with_feedback(g: jax.Array, residual: jax.Array
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (int8, scale, new_residual)."""
    corrected = g.astype(jnp.float32) + residual
    q, scale = compress(corrected)
    new_residual = corrected - decompress(q, scale)
    return q, scale, new_residual


def psum_compressed(g: jax.Array, residual: jax.Array, axis_name: str
                    ) -> tuple[jax.Array, jax.Array]:
    """Cross-pod mean-all-reduce with int8 wire format + error feedback.

    Must run inside shard_map with ``axis_name`` bound (the ``pod`` axis).
    Wire bytes: all_gather of int8 = (n-1)/n x N bytes vs fp32 ring
    all-reduce 2(n-1)/n x 4N — an 8x reduction.
    """
    n = jax.lax.axis_size(axis_name)
    q, scale, new_residual = compress_with_feedback(g, residual)
    qs = jax.lax.all_gather(q, axis_name)            # (n, ...), int8 on wire
    scales = jax.lax.all_gather(scale, axis_name)    # (n,), negligible
    summed = jnp.sum(
        qs.astype(jnp.float32)
        * scales.reshape((n,) + (1,) * (q.ndim)), axis=0)
    return summed / n, new_residual


def tree_psum_compressed(grads: Any, ef: ErrorFeedback, axis_name: str
                         ) -> tuple[Any, ErrorFeedback]:
    flat_g, treedef = jax.tree.flatten(grads)
    flat_r = jax.tree.leaves(ef.residual)
    out_g, out_r = [], []
    for g, r in zip(flat_g, flat_r):
        s, nr = psum_compressed(g, r, axis_name)
        out_g.append(s.astype(g.dtype))
        out_r.append(nr)
    return (treedef.unflatten(out_g),
            ErrorFeedback(treedef.unflatten(out_r)))
