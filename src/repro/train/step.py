"""Train-step factory: loss → grads → (scaled, accumulated) → optimizer.

One jitted function per (model, RunConfig): the unit the paper profiles
(fwd / bwd / optimizer are also exposed separately for the phase-wise
roofline, Figs 3-7) and the unit the dry-run lowers for every cell.

Features (task spec §large-scale):
* microbatch gradient accumulation (``run.microbatches``) via ``lax.scan``
  with fp32 accumulators — collectives on the grads happen once per step,
  not per microbatch (collective-deferred accumulation);
* dynamic loss scaling (paper §IV-C: AMP's loss-scaling schemes) with
  overflow-skip semantics;
* optimizer-state update (AdamW / Adafactor) with donated buffers.

``run.fusion = "auto"`` threads through every phase built here: the
forward/backward route their norm + residual, SwiGLU-epilogue and
embedding-backward chains through ``repro.kernels.fused``, and the
optimizer phase runs the fused one-pass AdamW leaf update — the same
``make_phases`` handles both lowerings, so a reference-vs-fused trace is
always the same program shape measured twice (docs/DESIGN.md §12).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import RunConfig
from repro.distributed import amp
from repro.models.api import Model
from repro.train import optim


class TrainState(NamedTuple):
    params: Any
    opt: Any
    loss_scale: amp.DynLossScale
    step: jax.Array


def init_state(model: Model, run: RunConfig, rng: jax.Array) -> TrainState:
    from repro.models.params import init
    params = init(rng, model.spec, run.param_dtype)
    return TrainState(
        params=params,
        opt=optim.optimizer_init(params, run),
        loss_scale=amp.DynLossScale.init(),
        step=jnp.zeros((), jnp.int32),
    )


def abstract_state(model: Model, run: RunConfig) -> TrainState:
    """TrainState of ShapeDtypeStructs (dry-run, no allocation)."""
    return jax.eval_shape(
        lambda: init_state(model, run, jax.random.PRNGKey(0)))


def _split_microbatches(batch: dict, m: int) -> dict:
    return {k: v.reshape(m, v.shape[0] // m, *v.shape[1:])
            for k, v in batch.items()}


def make_train_step(model: Model, run: RunConfig, lr: float = 3e-4
                    ) -> Callable[[TrainState, dict], tuple[TrainState, dict]]:
    use_scaling = run.amp == "O2"          # bf16 master weights need guarding

    def loss_of(params, mb, scale):
        loss, metrics = model.loss_fn(params, mb, run)
        if use_scaling:
            loss = amp.scale_loss(loss, scale)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def _grads(state: TrainState, batch: dict) -> tuple[Any, dict]:
        """The batch's gradients and metrics, over its microbatches."""
        m = run.microbatches
        if m > 1:
            mbs = _split_microbatches(batch, m)
            # O2 accumulates in the storage dtype (bf16): at ≥500B params a
            # separate fp32 accumulator alone would exceed HBM.
            acc_dt = run.param_dtype if run.amp == "O2" else jnp.float32

            def acc_body(carry, mb):
                g_acc, metric_acc = carry
                (_, metrics), grads = grad_fn(state.params, mb,
                                              state.loss_scale)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(acc_dt), g_acc, grads)
                metric_acc = jax.tree.map(lambda a, x: a + x,
                                          metric_acc, metrics)
                return (g_acc, metric_acc), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt),
                              state.params)
            (_, m0), _ = jax.eval_shape(
                lambda p, mb: grad_fn(p, mb, state.loss_scale),
                state.params, jax.tree.map(lambda x: x[0], mbs))
            m0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), m0)
            (grads, metrics), _ = jax.lax.scan(
                acc_body, (g0, m0), mbs)
            grads = jax.tree.map(lambda g: g / m, grads)
            metrics = jax.tree.map(lambda x: x / m, metrics)
        else:
            (_, metrics), grads = grad_fn(state.params, batch,
                                          state.loss_scale)
        return grads, metrics

    def train_step(state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        # phase scopes: the forward's ops read ``train.grad/jvp(...)`` in
        # their HLO ``op_name``, the backward's ``transpose(...)`` as well
        with jax.named_scope("train.grad"):
            grads, metrics = _grads(state, batch)

        with jax.named_scope("train.optimizer"):
            if use_scaling:
                grads, new_scale, finite = amp.unscale_and_update(
                    grads, state.loss_scale)
            else:
                new_scale, finite = state.loss_scale, jnp.array(True)

            new_params, new_opt = optim.optimizer_update(
                grads, state.opt, state.params, run, lr=lr)
            # overflow → skip the update (keep old params/opt), shrink the
            # scale
            new_params = jax.tree.map(
                lambda n, o: jnp.where(finite, n, o), new_params,
                state.params)
            new_opt = jax.tree.map(
                lambda n, o: jnp.where(finite, n, o), new_opt, state.opt)

        with jax.named_scope("train.metrics"):
            metrics = dict(metrics)
            metrics["grads_finite"] = finite.astype(jnp.float32)
            metrics["grad_norm"] = jnp.sqrt(sum(
                jnp.sum(g.astype(jnp.float32) ** 2)
                for g in jax.tree.leaves(grads)))
        return TrainState(new_params, new_opt, new_scale,
                          state.step + 1), metrics

    return train_step


# --------------------------------------------------------------------------
# Phase-split functions (paper Figs 3-7: fwd / bwd / optimizer separately)
# --------------------------------------------------------------------------

def make_phases(model: Model, run: RunConfig, lr: float = 3e-4
                ) -> dict[str, Callable]:
    """fwd / bwd / opt as separately-jittable functions for phase profiling."""

    def fwd(params, batch):
        return model.loss_fn(params, batch, run)[0]

    def bwd(params, batch):
        return jax.grad(fwd)(params, batch)

    def opt(params, grads, opt_state):
        return optim.optimizer_update(grads, opt_state, params, run, lr=lr)

    return {"fwd": fwd, "bwd": bwd, "opt": opt}
