"""GPipe-style pipeline parallelism over the ``pod`` mesh axis.

Layers are split into ``S = |pod|`` contiguous stages (the stacked layer
axis is sharded over ``pod``); microbatches stream through the classic
GPipe schedule — tick ``t`` runs microbatch ``t - stage`` on ``stage``,
activations hop stages via ``ppermute`` (ICI/DCN neighbor exchange, exactly
the collective the roofline's cross-pod term models).  ``jax.grad``
differentiates through ``ppermute`` (its transpose is the reversed
permutation), so the same schedule serves fwd+bwd (1F1B-equivalent wire
traffic; bubble fraction (S-1)/(M+S-1)).

Everything is shard_map-first: :func:`gpipe` must be called with ``pod``
bound as a manual axis and per-stage params already local.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp


def _pvary(x: jax.Array, axis: str) -> jax.Array:
    """Mark an unvarying value as device-varying over a manual mesh axis
    (scan carries inside shard_map must have matching varying types)."""
    return jax.lax.pcast(x, (axis,), to="varying")


def gpipe(stage_fn: Callable[[Any, jax.Array], jax.Array],
          local_params: Any, x_mbs: jax.Array, axis: str = "pod"
          ) -> jax.Array:
    """Run microbatches through pipeline stages.

    stage_fn: (local_params, x (b, s, d)) → (b, s, d)
    x_mbs: (M, b, s, d) microbatched hidden states (valid on stage 0).
    Returns (M, b, s, d) stage-S-1 outputs (valid on the last stage).
    """
    S = jax.lax.axis_size(axis)
    stage = jax.lax.axis_index(axis)
    M = x_mbs.shape[0]
    ticks = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]

    out_buf = _pvary(jnp.zeros_like(x_mbs), axis)
    carry_in = _pvary(jnp.zeros_like(x_mbs[0]), axis)

    def tick(state, t):
        recv, out_buf = state
        # stage 0 feeds fresh microbatches; others consume the neighbor's out
        mb_idx = jnp.clip(t, 0, M - 1)
        x_in = jnp.where(stage == 0, x_mbs[mb_idx], recv)
        y = stage_fn(local_params, x_in)
        # the last stage commits its result for microbatch t-(S-1)
        out_idx = jnp.clip(t - (S - 1), 0, M - 1)
        commit = (stage == S - 1) & (t >= S - 1)
        out_buf = jax.lax.dynamic_update_slice(
            out_buf,
            jnp.where(commit, y, out_buf[out_idx])[None],
            (out_idx,) + (0,) * (x_mbs.ndim - 1))
        # hop to the next stage (wraparound send from last is ignored)
        recv = jax.lax.ppermute(y, axis, perm)
        return (recv, out_buf), None

    (_, out_buf), _ = jax.lax.scan(tick, (carry_in, out_buf),
                                   jnp.arange(ticks))
    return out_buf


def stage_slice(n_layers: int, axis: str = "pod") -> tuple[jax.Array, int]:
    """(my first layer index, layers per stage) inside shard_map."""
    S = jax.lax.axis_size(axis)
    per = n_layers // S
    return jax.lax.axis_index(axis) * per, per


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
