"""Fused AdamW leaf update: moments + bias correction + decay + write.

The reference update (``repro.train.optim.adamw_update``) is the paper's
"optimizer phase" caricature: per leaf, XLA streams g/m/v/p through a
chain of elementwise kernels (fp32 upcasts, two moment updates, the
bias-corrected step, the decayed write, downcasts) — each pass re-reading
HBM at zero arithmetic intensity.  This kernel performs the whole update
in one pass per leaf block: four reads, three writes, all intermediate
values in VMEM/VREGs.

The math mirrors the reference expression-for-expression in fp32, so the
result is bitwise-close (``tests/test_fused.py`` asserts it on a real
train step).  Hyperparameters (lr, betas, eps, weight decay) are static;
the traced bias corrections ``1 - beta^t`` ride in as a tiny (2,) operand
broadcast to every block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import config as kc
from repro.kernels.fused.common import pad_rows


def _adamw_kernel(g_ref, m_ref, v_ref, p_ref, bc_ref,
                  p_out, m_out, v_out, *, lr: float, b1: float, b2: float,
                  eps: float, weight_decay: float):
    gf = g_ref[...].astype(jnp.float32)
    mf = m_ref[...].astype(jnp.float32)
    vf = v_ref[...].astype(jnp.float32)
    pf = p_ref[...].astype(jnp.float32)
    bc1, bc2 = bc_ref[0], bc_ref[1]
    m2 = b1 * mf + (1 - b1) * gf
    v2 = b2 * vf + (1 - b2) * gf * gf
    step = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
    newp = pf - lr * (step + weight_decay * pf)
    p_out[...] = newp.astype(p_out.dtype)
    m_out[...] = m2.astype(m_out.dtype)
    v_out[...] = v2.astype(v_out.dtype)


def fused_adamw(g: jax.Array, m: jax.Array, v: jax.Array, p: jax.Array,
                bc1: jax.Array, bc2: jax.Array, *, lr: float = 3e-4,
                b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                weight_decay: float = 0.1,
                config: kc.KernelConfig | None = None,
                block: int | None = None, interpret: bool | None = None
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One leaf's AdamW update in one pass → (new_p, new_m, new_v).

    Operands may have any (identical) shape — the kernel runs over the
    flattened view in blocks of ``block`` elements, padding the final
    block (zero inputs update to zeros, sliced off).  ``bc1``/``bc2`` are
    the traced bias corrections ``1 - beta^count``.
    """
    cfg = kc.resolve("fused_adamw", config, block=block)
    shape = p.shape
    n = p.size
    flat = [a.reshape(-1) for a in (g, m, v, p)]
    blk = min(int(cfg.get("block")), n)
    flat = [pad_rows(a, blk) for a in flat]
    n_blocks = flat[0].shape[0] // blk
    bc = jnp.stack([bc1.astype(jnp.float32), bc2.astype(jnp.float32)])

    kernel = functools.partial(
        _adamw_kernel, lr=lr, b1=b1, b2=b2, eps=eps,
        weight_decay=weight_decay)
    outs = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((blk,), lambda i: (i,))
                  for _ in flat] + [pl.BlockSpec((2,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((blk,), lambda i: (i,)) for _ in range(3)],
        out_shape=[jax.ShapeDtypeStruct((n_blocks * blk,), dt)
                   for dt in (p.dtype, m.dtype, v.dtype)],
        compiler_params=kc.compiler_params(cfg),
        interpret=kc.interpret_mode(interpret),
    )(*flat, bc)
    return tuple(o[:n].reshape(shape) for o in outs)


def hbm_bytes(n: int, itemsize: int = 4) -> float:
    """Analytic fused traffic: g/m/v/p in + p/m/v out, one pass each."""
    return float(7 * n * itemsize)
