"""Model-facing wrapper: GQA layout ↔ kernel layout, with custom VJP.

``attention_apply`` (repro.models.layers) calls this with
q (B, S, K, G, hd) and k/v (B, S, K, hd); the kernel works on flattened
(B·K·G, S, hd) rows, with each query head reading its shared KV head.

``pallas_call`` has no autodiff rule, so the wrapper is a ``custom_vjp``:
the forward runs the kernel; the backward recomputes attention with the
reference math and differentiates that (the flash recompute-not-store
policy — on real TPU hardware the backward is its own Pallas kernel with
the same signature; the jnp backward here is the CPU-validatable
stand-in and is exactly what the roofline's 2×-forward backward models).

Block sizes route through ``repro.tune.best_config``: if the autotuner
has a persisted winner for this (shape, dtype, machine) the kernel runs
it, otherwise the 512/512 default — callers can still pin blocks
explicitly.  The store lookup happens at trace time (one ``os.stat`` per
compile, zero per-step cost).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention


def _ref_gqa(q: jax.Array, k: jax.Array, v: jax.Array,
             causal: bool) -> jax.Array:
    """Reference GQA attention in the model layout (fp32 softmax)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqkgh,bskh->bkgqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _lookup_config(bh: int, sq: int, sk: int, hd: int, dtype) -> "object":
    from repro.tune import best_config
    return best_config("flash_attention", (bh, sq, sk, hd),
                       dtype=jnp.dtype(dtype).name)


def _kernel_gqa(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
                interpret: bool | None, block_q: int | None,
                block_k: int | None) -> jax.Array:
    B, Sq, K, G, hd = q.shape
    _, Sk, _, _ = k.shape
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B * K * G, Sq, hd)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(
        B * K * G, Sk, hd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(
        B * K * G, Sk, hd)
    cfg = (None if block_q is not None or block_k is not None
           else _lookup_config(B * K * G, Sq, Sk, hd, q.dtype))
    of = flash_attention(qf, kf, vf, causal=causal, config=cfg,
                         block_q=block_q, block_k=block_k,
                         interpret=interpret)
    return of.reshape(B, K, G, Sq, hd).transpose(0, 3, 1, 2, 4)


@functools.lru_cache(maxsize=16)
def _make(causal: bool, interpret: bool | None, block_q: int | None,
          block_k: int | None):
    @jax.custom_vjp
    def fa(q, k, v):
        return _kernel_gqa(q, k, v, causal, interpret, block_q, block_k)

    def fwd(q, k, v):
        return fa(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(lambda a, b, c: _ref_gqa(a, b, c, causal), q, k, v)
        return vjp(g)

    fa.defvjp(fwd, bwd)
    return fa


def flash_attention_gqa(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, interpret: bool | None = None,
                        block_q: int | None = None,
                        block_k: int | None = None) -> jax.Array:
    """q (B, Sq, K, G, hd), k/v (B, Sk, K, hd) → (B, Sq, K, G, hd).

    ``block_q``/``block_k`` default to the tuned winner for this shape
    (``repro.tune.best_config``), falling back to 512/512.
    """
    return _make(causal, interpret, block_q, block_k)(q, k, v)
