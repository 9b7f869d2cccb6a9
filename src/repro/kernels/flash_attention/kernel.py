"""Flash attention (causal, GQA) as a Pallas TPU kernel.

The roofline analysis of the XLA-native lowering shows the attention
softmax chain streaming (chunk × S) score matrices through HBM ~12×
per layer — the dominant memory term of every full-attention train/prefill
cell.  This kernel keeps scores entirely in VMEM:

* grid = (B·H, Sq/bq): one core pass per query block;
* K/V for the whole sequence live in VMEM (bf16, 32k × 128 ≈ 8 MiB each —
  comfortably inside the ~128 MiB VMEM budget with double buffering);
* online-softmax accumulators (m, l, acc) in fp32 VMEM scratch;
* causal masking skips fully-masked K blocks (the `nb` bound), so the
  kernel does the same ½·Sq·Sk work the math requires.

HBM traffic per (b, h): read Q + K + V once, write O once — the memory
term of attention drops from O(S²) to O(S·hd), which is the whole point
(hardware adaptation of the GPU flash-attention insight: the VMEM
scratchpad plays the role of the SM shared memory, block sizes follow the
MXU 128-lane granularity instead of warp tiling).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import config as kc

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, block_q: int, block_k: int, sk: int, causal: bool):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                      # (bq, hd)
    scale = q.shape[-1] ** -0.5
    q = q * scale

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    q_offset = qi * block_q
    # number of k-blocks this q-block attends to (causal prefix)
    nb = (jax.lax.div(q_offset + block_q + block_k - 1, block_k)
          if causal else sk // block_k)
    nb = jnp.minimum(nb, sk // block_k)

    def body(ki, _):
        k_off = ki * block_k
        k = k_ref[0, pl.ds(k_off, block_k), :].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, pl.ds(k_off, block_k), :].astype(jnp.float32)
        s = q @ k.T                                       # (bq, bk)
        if causal:
            qpos = q_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = k_off + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
        m_ref[...] = m_new
        return ()

    jax.lax.fori_loop(0, nb, body, ())
    o_ref[...] = (acc_ref[...] / l_ref[...][:, None]
                  ).astype(o_ref.dtype)[None]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    config: kc.KernelConfig | None = None,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """q (BH, Sq, hd), k/v (BH, Sk, hd) → (BH, Sq, hd).

    Block sizes resolve explicit kwargs → ``config`` → the 512/512
    default; both grid dims (row, q-block) are independent → ``parallel``.
    """
    cfg = kc.resolve("flash_attention", config, block_q=block_q,
                     block_k=block_k)
    bh, sq, hd = q.shape
    _, sk, _ = k.shape
    block_q = min(int(cfg.get("block_q")), sq)
    block_k = min(int(cfg.get("block_k")), sk)
    assert sq % block_q == 0 and sk % block_k == 0
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, sk=sk,
        causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),          # m
            pltpu.VMEM((block_q,), jnp.float32),          # l
            pltpu.VMEM((block_q, hd), jnp.float32),       # acc
        ],
        compiler_params=kc.compiler_params(cfg),
        interpret=kc.interpret_mode(interpret),
    )(q, k, v)


def hbm_bytes(bh: int, sq: int, sk: int, hd: int, itemsize: int = 2) -> float:
    """Analytic kernel traffic: Q+O once, K+V once per (b, h)."""
    return float(bh) * (2 * sq * hd + 2 * sk * hd) * itemsize


def flops(bh: int, sq: int, sk: int, hd: int, causal: bool = True) -> float:
    """QK^T + PV matmul FLOPs (causal halves the score area)."""
    area = sq * sk / (2 if causal else 1)
    return float(bh) * 2 * 2 * area * hd
