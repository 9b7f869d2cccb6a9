"""ERT compute-ceiling micro-kernels (paper §II-A, Table I ladder).

The paper tunes an FMA-chain kernel from 15.4 → 29.2 TFLOP/s on V100 by
packing (``half2``), 32-bit indexing and inlining.  The TPU-native ladder:

* v1 ``fp32``      — dependent FMA chains on the VPU (fp32 lanes),
* v2 ``bf16``      — same chains in bf16 (2× lane packing on the VPU),
* v3 ``mxu``       — the GEMM kernel in ``gemm.py`` (the Tensor-Core
                     analogue; see also Fig 2 sweep).

Each kernel is a ``pl.pallas_call`` with an explicit VMEM BlockSpec: a
block of the array is loaded once, ``n_iters`` dependent FMAs run per
element (``ILP`` independent chains hide FMA latency), and the block is
written back — FLOPs = 2 · n_iters · ILP · N, bytes = 2 · N · itemsize, so
arithmetic intensity is dialed by ``n_iters`` exactly like ERT's kernel
generator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import config as kc

BLOCK = 4096  # default elements per grid step; multiple of the 8x128 VPU tile


def _fma_chain_kernel(x_ref, o_ref, *, n_iters: int, ilp: int):
    x = x_ref[...]
    dt = x.dtype
    a = jnp.asarray(1.0000001, dt)
    b = jnp.asarray(1e-7, dt)
    # `ilp` independent dependent-chains per element (latency hiding),
    # unrolled at trace time — the analogue of ERT's generated unroll.
    accs = [x + jnp.asarray(i, dt) for i in range(ilp)]
    for _ in range(n_iters):
        accs = [acc * a + b for acc in accs]
    out = accs[0]
    for acc in accs[1:]:
        out = out + acc
    o_ref[...] = out


def fma_chain(x: jax.Array, n_iters: int = 64, ilp: int = 4, *,
              config: kc.KernelConfig | None = None,
              block: int | None = None,
              interpret: bool | None = None) -> jax.Array:
    """Run the FLOP micro-kernel; FLOPs = (2·n_iters·ilp + ilp) · x.size.

    The block size comes from the config (tunable); any ``x.size`` works —
    the final block is padded and the pad sliced off after the call.
    """
    cfg = kc.resolve("fma_chain", config, block=block)
    blk = int(cfg.get("block"))
    n = x.size
    xf = x.reshape(-1)
    pad = (-n) % blk
    if pad:
        xf = jnp.pad(xf, (0, pad))
    kernel = functools.partial(_fma_chain_kernel, n_iters=n_iters, ilp=ilp)
    out = pl.pallas_call(
        kernel,
        grid=((n + pad) // blk,),
        in_specs=[pl.BlockSpec((blk,), lambda i: (i,))],
        out_specs=pl.BlockSpec((blk,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n + pad,), x.dtype),
        compiler_params=kc.compiler_params(cfg),
        interpret=kc.interpret_mode(interpret),
    )(xf)
    return out[:n].reshape(x.shape)


def fma_flops(n_elements: int, n_iters: int, ilp: int) -> float:
    return (2.0 * n_iters * ilp + ilp) * n_elements
