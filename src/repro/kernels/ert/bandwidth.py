"""ERT bandwidth micro-kernel (paper §II-A memory ceilings).

STREAM-triad through the memory hierarchy: ``o = a · s + b`` with one pass
over two input arrays and one output — 3·N·itemsize bytes of HBM traffic
and 2·N FLOPs, i.e. AI ≈ 0.17 (fp32): firmly on the bandwidth roof.  The
BlockSpec streams VMEM-sized tiles, which is exactly how the HBM roof is
reached on TPU (contiguous, double-buffered block DMA).

The block size is a :class:`~repro.kernels.config.KernelConfig` parameter
(default 16384, the former hardcoded constant) so ``repro.tune`` can
search it; ``double_buffer=True`` selects a two-stage software-pipelined
variant that loads both half-tiles before either FMA issues (a 2× window
for the Mosaic pipeliner; on the interpret host it halves grid-step
overhead).  Arbitrary N is supported: the final block is padded and the
padded lanes' stores masked off by the wrapper's slice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import config as kc

BLOCK = 16384    # default tile (kept as the tuner's search-space anchor)


def _triad_kernel(a_ref, b_ref, o_ref, *, scale: float):
    o_ref[...] = a_ref[...] * jnp.asarray(scale, a_ref.dtype) + b_ref[...]


def _triad_kernel_db(a_ref, b_ref, o_ref, *, scale: float, block: int):
    # two-stage pipeline: both half-tile loads issue before either FMA, so
    # the second load overlaps the first FMA once the compiler schedules it
    s = jnp.asarray(scale, a_ref.dtype)
    a0 = a_ref[pl.dslice(0, block)]
    b0 = b_ref[pl.dslice(0, block)]
    a1 = a_ref[pl.dslice(block, block)]
    b1 = b_ref[pl.dslice(block, block)]
    o_ref[pl.dslice(0, block)] = a0 * s + b0
    o_ref[pl.dslice(block, block)] = a1 * s + b1


def triad(a: jax.Array, b: jax.Array, scale: float = 3.0, *,
          config: kc.KernelConfig | None = None,
          block: int | None = None, double_buffer: bool | None = None,
          interpret: bool | None = None) -> jax.Array:
    """o = a·s + b; bytes = 3·N·itemsize, flops = 2·N.  Any N."""
    cfg = kc.resolve("triad", config, block=block,
                     double_buffer=double_buffer)
    blk = int(cfg.get("block"))
    db = bool(cfg.get("double_buffer"))
    n = a.size
    assert a.shape == b.shape
    step = 2 * blk if db else blk
    af, bf = a.reshape(-1), b.reshape(-1)
    pad = (-n) % step
    if pad:                       # padded final block, sliced off below
        af = jnp.pad(af, (0, pad))
        bf = jnp.pad(bf, (0, pad))
    if db:
        kernel = functools.partial(_triad_kernel_db, scale=scale, block=blk)
    else:
        kernel = functools.partial(_triad_kernel, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=((n + pad) // step,),
        in_specs=[pl.BlockSpec((step,), lambda i: (i,)),
                  pl.BlockSpec((step,), lambda i: (i,))],
        out_specs=pl.BlockSpec((step,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n + pad,), a.dtype),
        compiler_params=kc.compiler_params(cfg),
        interpret=kc.interpret_mode(interpret),
    )(af, bf)
    return out[:n].reshape(a.shape)


def triad_bytes(n_elements: int, itemsize: int) -> float:
    return 3.0 * n_elements * itemsize


def triad_flops(n_elements: int) -> float:
    return 2.0 * n_elements
