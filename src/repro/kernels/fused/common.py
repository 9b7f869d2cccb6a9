"""Shared launch plumbing for the row-blocked fused kernels.

Every fused forward kernel is row-parallel over a 2D ``(rows, d)`` view:
each row is normalized / activated independently, so the grid is a 1D
sweep over row blocks (``parallel`` — no state carries between blocks)
and arbitrary row counts are handled by padding the final block (the
triad/fma_chain convention from PR 3, instead of ``assert rows % block``).
Padding rows are all-zero, which every kernel body maps to a finite value
(rsqrt(0 + eps) stays finite), and are sliced off after the call.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import config as kc

#: VMEM one grid step may hold: half of the 16 MiB Mosaic scopes for a
#: kernel by default on TPU v5e, the rest left to the compiler's own
#: temporaries.  A fixed row count cannot honour it: 1024 rows of a
#: 9216-wide SwiGLU need 180 MB.
VMEM_BUDGET_BYTES = 8 * 2**20
#: fp32 row-wide intermediates a kernel body keeps live at once
_F32_TEMPS = 3
#: row granularity of a block: the (16, 128) tile of a 16-bit array
_ROW_TILE = 16


def vmem_rows(d: int, dtypes: Sequence[Any]) -> int:
    """Most rows of width ``d`` one grid step may take within
    :data:`VMEM_BUDGET_BYTES`: every blocked operand and output is
    double-buffered, plus the fp32 intermediates."""
    per_row = d * (2 * sum(jnp.dtype(dt).itemsize for dt in dtypes)
                   + 4 * _F32_TEMPS)
    rows = VMEM_BUDGET_BYTES // per_row
    return max(_ROW_TILE, rows // _ROW_TILE * _ROW_TILE)


def pad_rows(x: jax.Array, block: int) -> jax.Array:
    """Zero-pad dim 0 up to a multiple of ``block`` (no-op if aligned)."""
    pad = (-x.shape[0]) % block
    if not pad:
        return x
    width = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, width)


def row_blocked_call(kernel: Callable, row_args: Sequence[jax.Array],
                     shared_args: Sequence[jax.Array],
                     out_dtypes: Sequence[Any], cfg: kc.KernelConfig, *,
                     interpret: bool | None = None) -> tuple[jax.Array, ...]:
    """Launch ``kernel`` over row blocks of 2D ``(rows, d)`` operands.

    ``row_args`` are blocked over dim 0 in blocks of the config's
    ``block_rows``, capped by :func:`vmem_rows`; ``shared_args`` (1D,
    e.g. norm scale/bias) are broadcast to every block.  Outputs mirror
    the row layout, one per entry of ``out_dtypes``, and are sliced back
    to the unpadded row count.
    """
    rows, d = row_args[0].shape
    block = min(int(cfg.get("block_rows")), rows,
                vmem_rows(d, [a.dtype for a in row_args] + list(out_dtypes)))
    padded = [pad_rows(a, block) for a in row_args]
    n_blocks = padded[0].shape[0] // block

    in_specs = [pl.BlockSpec((block, d), lambda i: (i, 0)) for _ in padded]
    for s in shared_args:
        in_specs.append(pl.BlockSpec(s.shape, lambda i: (0,)))
    out_specs = [pl.BlockSpec((block, d), lambda i: (i, 0))
                 for _ in out_dtypes]
    out_shape = [jax.ShapeDtypeStruct((n_blocks * block, d), dt)
                 for dt in out_dtypes]
    if len(out_dtypes) == 1:
        out_specs, out_shape = out_specs[0], out_shape[0]

    out = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=kc.compiler_params(cfg),
        interpret=kc.interpret_mode(interpret),
    )(*padded, *shared_args)
    outs = (out,) if len(out_dtypes) == 1 else tuple(out)
    if outs[0].shape[0] != rows:
        outs = tuple(o[:rows] for o in outs)
    return outs
