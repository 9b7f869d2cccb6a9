"""ERT MXU GEMM kernel (paper §II-A Tensor Core + Fig 2 size sweep).

Blocked matmul with explicit VMEM tiling: grid (M/bm, N/bn, K/bk), fp32
accumulator scratch in VMEM, bf16 (or fp32) operand tiles sized to the MXU
(multiples of 128 on the matmul dims — the hardware-alignment rule the
paper's cuBLAS/WMMA comparison turns on).  FLOPs = 2·M·N·K.

On real TPU hardware this kernel measures the MXU ceiling as a function of
matrix size (Fig 2 analogue: ``benchmarks.gemm_sweep``); on CPU it is
validated against the jnp oracle in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import config as kc


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(a: jax.Array, b: jax.Array, *,
           config: kc.KernelConfig | None = None,
           block_m: int | None = None, block_n: int | None = None,
           block_k: int | None = None,
           out_dtype=None, interpret: bool | None = None) -> jax.Array:
    """C = A @ B with (bm, bn, bk) VMEM tiles; MXU-aligned blocks.

    Tile sizes resolve explicit kwargs → ``config`` → the tuner default
    (256³); the i/j grid dims are ``parallel``, the accumulating k dim
    ``arbitrary`` (sequential — the VMEM scratch carries across it).
    """
    cfg = kc.resolve("ert_gemm", config, block_m=block_m, block_n=block_n,
                     block_k=block_k)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    block_m, block_n, block_k = (min(int(cfg.get("block_m")), m),
                                 min(int(cfg.get("block_n")), n),
                                 min(int(cfg.get("block_k")), k))
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    k_steps = k // block_k
    out_dtype = out_dtype or a.dtype
    kernel = functools.partial(_matmul_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n, k_steps),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=kc.compiler_params(cfg),
        interpret=kc.interpret_mode(interpret),
    )(a, b)


def gemm_flops(m: int, n: int, k: int) -> float:
    return 2.0 * m * n * k
