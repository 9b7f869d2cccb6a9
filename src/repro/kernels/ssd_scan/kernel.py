"""Chunked SSD (Mamba-2 state-space duality) as a Pallas TPU kernel.

The dual form splits the sequence into chunks of Q steps: inside a chunk
the recurrence is a masked (Q × Q) quadratic form — MXU-friendly matmuls —
and across chunks a (P × N) state carries.  This kernel fuses one chunk's
whole pipeline in VMEM (the XLA-native lowering streams the (Q, Q, H)
decay/score tensors through HBM):

* grid = (B, H, n_chunks), chunk minor-most → sequential on-core, so the
  (P, N) state lives in VMEM scratch across chunk steps and is
  re-initialized whenever the (b, h) row changes (``c == 0``);
* per step: cumsum, decay matrix, C·Bᵀ scores, two (Q×Q)·(Q×P) matmuls,
  state update — all in fp32 VMEM, none of it touching HBM;
* HBM traffic per chunk: x, a, B, C in + y out = O(Q·(P+N)) instead of
  O(Q²·H) — the same roofline move flash attention makes for softmax.

Inputs are pre-scaled by the wrapper exactly like ``repro.models.ssm``:
``xdt = x·dt`` and ``a = A·dt`` (negative log-decay per step).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import config as kc


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, o_ref, state_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)                  # (Q, P)
    a = a_ref[0, 0].astype(jnp.float32)                  # (1, Q)
    Bc = b_ref[0].astype(jnp.float32)                    # (Q, N)
    Cc = c_ref[0].astype(jnp.float32)                    # (Q, N)

    iq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iq >= jq
    # inclusive prefix sum as a column, cum_i = sum_{j<=i} a_j: a masked
    # lane reduction, since Mosaic lowers no cumsum
    cum = jnp.sum(jnp.where(causal, a, 0.0), axis=1, keepdims=True)  # (Q, 1)
    total = jnp.sum(a)

    # intra-chunk: M[i,j] = 1[i>=j] · exp(cum_i - cum_j) · (C_i · B_j)
    cum_sq = jnp.broadcast_to(cum, (chunk, chunk))
    seg = cum_sq - cum_sq.T
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))    # mask pre-exp
    scores = Cc @ Bc.T                                   # (Q, Q)
    y = (scores * decay) @ x                             # (Q, P)

    # inter-chunk: y_i += exp(cum_i) · C_i · state_prev
    y = y + jnp.exp(cum) * (Cc @ state_ref[...].T)

    # state update: s = exp(total)·s + Σ_j exp(total - cum_j) x_j B_jᵀ
    w = jnp.exp(total - cum)                             # (Q, 1)
    state_ref[...] = (jnp.exp(total) * state_ref[...]
                      + x.T @ (Bc * w))                  # (P, N)

    o_ref[0, 0] = y.astype(o_ref.dtype)


def ssd_scan(xdt: jax.Array, a: jax.Array, B_: jax.Array, C_: jax.Array, *,
             config: kc.KernelConfig | None = None,
             chunk: int | None = None,
             interpret: bool | None = None) -> jax.Array:
    """xdt (B, H, S, P), a (B, H, S), B_/C_ (B, S, N) → y (B, H, S, P).

    ``chunk`` resolves explicit kwarg → ``config`` → the 128 default; the
    chunk grid dim is ``arbitrary`` (sequential — the VMEM state scratch
    carries across chunks), B/H are ``parallel``.
    """
    cfg = kc.resolve("ssd_scan", config, chunk=chunk)
    Bsz, H, S, P = xdt.shape
    N = B_.shape[-1]
    chunk = min(int(cfg.get("chunk")), S)
    assert S % chunk == 0
    nc = S // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            # a rides as (B, H, 1, S): a (1, chunk) tile is a legal TPU
            # block (unit dim = the array's, chunk a multiple of 128);
            # (1, 1, chunk) over (B, H, S) would tile H by 1
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, H, S, P), xdt.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=kc.compiler_params(cfg),
        interpret=kc.interpret_mode(interpret),
    )(xdt, a[:, :, None, :], B_, C_)


def hbm_bytes(b: int, h: int, s: int, p: int, n: int,
              itemsize: int = 4) -> float:
    """Analytic traffic: x + y (B,H,S,P) + a + B/C once."""
    return float(b) * (2 * h * s * p + h * s + 2 * s * n) * itemsize


def flops(b: int, h: int, s: int, p: int, n: int, chunk: int) -> float:
    """Per-chunk: CBᵀ (2Q²N) + My (2Q²P) + state (2QPN + QP) + inter (2QPN)."""
    nc = s // chunk
    per_chunk = (2 * chunk * chunk * n + 2 * chunk * chunk * p
                 + 4 * chunk * p * n)
    return float(b * h * nc) * per_chunk
