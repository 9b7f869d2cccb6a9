#!/usr/bin/env python3
"""Bring-up check on a TPU: the repo's main paths at published sizes.

    python chip_smoke.py              # one chip: the five phases below
    python chip_smoke.py --chips 4    # four chips: data-parallel DeepCAM
                                      # against one chip with microbatches

Everything runs in this one process, which holds the chip; nothing is
spawned.  With one chip the phases run in this order:

1. device  -- JAX's first device must be a TPU; otherwise exit non-zero
              before anything runs (never a CPU fallback).
2. kernels -- every Pallas kernel of the main path through its
              model-facing wrapper with default arguments, at published
              widths, checked against its float32 jnp reference and for
              the Mosaic call in the compiled program.
3. train   -- DeepCAM at the paper's 768x1152x16 input, batch 1, AMP O1,
              a few steps through ``repro.launch.train``'s trainer.
4. record  -- ``Session.record("deepcam", smoke=False, batch=1)``: the
              HLO walk plus measured phases; the walker's FLOPs must agree
              with XLA's ``cost_analysis``.
5. serve   -- the whole minitron-4b (32 layers, vocabulary 256,000) in
              bf16 through ``Session.serve``; the latency gate must pass
              and one prompt's prefill logits must match the full forward.

Any failed check raises, so the script exits non-zero.  Only when every
phase passed does it print, as its last line, one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times printed on the way are host wall-clock times, compilation included.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

#: what a compiled program holds when a Pallas kernel lowered to Mosaic;
#: without it the kernel ran in the interpreter or fell back to jnp
KERNEL_MARKER = "tpu_custom_call"

#: tolerances of the kernel comparisons: max |kernel - reference| over
#: max |reference|, the reference in float32 at "highest" matmul precision
TOL_F32 = 1e-5      # f32 in and out, elementwise: only rounding order differs
TOL_BF16_OUT = 8e-3  # bf16 output: one rounding is 2^-8 of the value
TOL_MXU = 2e-2      # matmuls inside the kernel run on the MXU at its
                    # default precision, which rounds operands to bf16
#: walker FLOPs vs XLA's cost_analysis, relative to XLA's.  The walker
#: counts a transcendental as one FLOP and XLA reports them apart, so the
#: walker is held to XLA's flops + transcendentals.  Both count every dot
#: and convolution from its shapes (taps on padding excluded); they may
#: differ on the reductions and elementwise work around them
TOL_FLOPS = 0.05
#: prefill logits vs the full forward, max |diff| over max |forward|:
#: weights and activations are bf16 (AMP O2), and the chunked prefill
#: groups each layer's reductions differently from one full-length pass
TOL_LOGITS = 5e-2
#: the --chips 4 comparison of loss and gradient norm, relative: the
#: same mathematics, but the four per-image gradients are summed in a
#: different order (all-reduce vs sequential microbatch accumulation)
#: under bf16 compute
TOL_PARALLEL = 1e-3
#: the --chips 4 comparison of the first update p_new - p_init, as
#: |update_a - update_b| / |update_b| over every parameter.  AdamW's first
#: step is about lr x sign(gradient) per parameter, so a parameter whose
#: gradient is ~0 may flip sign between the two summation orders.  On
#: four TPU v5e chips this read 2.152e-02, with 54,553 of 41,593,491
#: parameters moved the other way; the limit is about twice that.  A
#: skipped update reads 1, a sign-flipped one 2, and full-size flips on
#: 1% of the parameters about 0.2.
TOL_UPDATE = 0.05


@dataclasses.dataclass(frozen=True)
class Sizes:
    """How big each phase runs.  :data:`FULL` is what a chip run uses."""

    smoke: bool                 # registry smoke configs instead of published
    triad_n: int
    fma_n: int
    gemm_n: int
    flash_s: int                # attention sequence length
    ssd_s: int                  # SSD sequence length
    rows: int                   # activation rows of the fused kernels
    train_steps: int
    requests: int
    slots: int
    max_len: int
    prefill_chunk: int
    prompt_len: tuple[int, int]
    max_new: tuple[int, int]
    check_len: int              # prompt length of the logits comparison


FULL = Sizes(smoke=False, triad_n=1 << 26, fma_n=1 << 24, gemm_n=4096,
             flash_s=2048, ssd_s=4096, rows=8192, train_steps=3,
             requests=8, slots=4, max_len=1024, prefill_chunk=128,
             prompt_len=(64, 384), max_new=(16, 32), check_len=300)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def device_info() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def require_tpu(chips: int) -> dict:
    """Phase 1: the device.  Exits non-zero on anything but a TPU."""
    info = device_info()
    say("device", json.dumps(info))
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{info['platform']!r}); nothing was run")
    if info["count"] < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, JAX found {info['count']}")
    return info


def peak_bytes(device=None) -> int:
    import jax
    device = device or jax.devices()[0]
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))


def rel_err(got, want) -> float:
    import jax.numpy as jnp
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# --------------------------------------------------------------------------
# 2. kernels
# --------------------------------------------------------------------------

def _kernel_cases(sz: Sizes, seed: int):
    """(name, kernel fn, args, float32 reference fn, tolerance) per kernel:
    minitron-4b attention and MLP widths, mamba2-1.3b SSD widths."""
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_config, get_smoke
    from repro.kernels.ert import bandwidth, flops, gemm, ref as ert_ref
    from repro.kernels.flash_attention.ops import (_ref_gqa,
                                                  flash_attention_gqa)
    from repro.kernels.fused import ops as fops
    from repro.kernels.ssd_scan.ops import ssd_scan_model_layout
    from repro.models.ssm import ssd_chunked

    get = get_smoke if sz.smoke else get_config
    lm, ssm = get("minitron-4b"), get("mamba2-1.3b")
    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def normal(shape, dtype=f32, scale=1.0):
        return (scale * jax.random.normal(next(keys), shape)).astype(dtype)

    K, hd = lm.n_kv_heads, lm.head_dim
    G = lm.n_heads // K
    S, H, P, N = sz.ssd_s, ssm.ssm_heads, ssm.ssm_head_dim, ssm.ssm_state
    D, F = lm.d_model, lm.d_ff

    def rms(x, s):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + 1e-5) * s

    def adamw_ref(g, m, v, p, bc1, bc2):
        m2 = 0.9 * m + 0.1 * g
        v2 = 0.95 * v + 0.05 * g * g
        step = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + 1e-8)
        return p - 3e-4 * (step + 0.1 * p), m2, v2

    return [
        ("ert_triad", bandwidth.triad,
         (normal((sz.triad_n,)), normal((sz.triad_n,))),
         ert_ref.triad_ref, TOL_F32),
        ("ert_fma_chain", flops.fma_chain,
         (jax.random.uniform(next(keys), (sz.fma_n,)),),
         ert_ref.fma_chain_ref, TOL_F32),
        ("ert_gemm", gemm.matmul,
         (normal((sz.gemm_n,) * 2, bf16), normal((sz.gemm_n,) * 2, bf16)),
         lambda a, b: a @ b, TOL_BF16_OUT),
        ("flash_attention_gqa", flash_attention_gqa,
         (normal((1, sz.flash_s, K, G, hd), bf16),
          normal((1, sz.flash_s, K, hd), bf16),
          normal((1, sz.flash_s, K, hd), bf16)),
         lambda q, k, v: _ref_gqa(q, k, v, True), TOL_MXU),
        ("ssd_scan_model_layout", ssd_scan_model_layout,
         (normal((1, S, H, P), scale=0.1),
          -jax.random.uniform(next(keys), (1, S, H), minval=1e-3,
                              maxval=0.1),
          normal((1, S, N), scale=N ** -0.5),
          normal((1, S, N), scale=N ** -0.5)),
         lambda x, a, b, c: ssd_chunked(x, a, b, c, min(128, S))[0],
         TOL_MXU),
        ("fused_rmsnorm", fops.rmsnorm,
         (normal((sz.rows, D), bf16), 1.0 + normal((D,), scale=0.1)),
         rms, TOL_BF16_OUT),
        ("fused_rmsnorm_residual", fops.rmsnorm_residual,
         (normal((sz.rows, D), bf16), normal((sz.rows, D), bf16),
          1.0 + normal((D,), scale=0.1)),
         lambda x, h, s: (x + h, rms(x + h, s)), TOL_BF16_OUT),
        ("fused_swiglu", fops.swiglu,
         (normal((sz.rows, F), bf16), normal((sz.rows, F), bf16)),
         lambda g, u: jax.nn.silu(g) * u, TOL_BF16_OUT),
        ("fused_adamw",
         lambda g, m, v, p, b1, b2: fops.adamw_leaf(
             g, m, v, p, b1, b2, lr=3e-4, b1=0.9, b2=0.95, eps=1e-8,
             weight_decay=0.1),
         (normal((D, F), scale=1e-3), normal((D, F), scale=1e-3),
          jnp.abs(normal((D, F), scale=1e-6)), normal((D, F), scale=0.02),
          jnp.float32(0.1), jnp.float32(0.05)),
         adamw_ref, TOL_F32),
    ]


def phase_kernels(sz: Sizes, seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp

    for name, fn, args, ref, tol in _kernel_cases(sz, seed):
        compiled = jax.jit(fn).lower(*args).compile()
        check(KERNEL_MARKER in compiled.as_text(),
              f"{name}: no {KERNEL_MARKER} in the compiled program (the "
              "kernel ran in the interpreter or fell back to jnp)")
        got = jax.tree.leaves(compiled(*args))
        f32_args = [a.astype(jnp.float32) for a in args]
        with jax.default_matmul_precision("highest"):
            want = jax.tree.leaves(jax.jit(ref)(*f32_args))
        err = max(rel_err(g, w) for g, w in zip(got, want))
        shapes = " ".join("x".join(map(str, a.shape)) or "()"
                          for a in args)
        say("kernels", f"{name:24s} {KERNEL_MARKER} | max|err|/max|ref| "
                       f"{err:.3e} (tol {tol:g}) | {shapes}")
        check(err <= tol, f"{name}: error {err:.3e} > {tol:g}")
    # the peak counts from process start: later phases' peaks include this
    say("kernels", f"peak_bytes_in_use {peak_bytes()}")


# --------------------------------------------------------------------------
# 3. train
# --------------------------------------------------------------------------

def phase_train(sz: Sizes) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import RunConfig
    from repro.launch.train import build_trainer

    trainer = build_trainer("deepcam", RunConfig(amp="O1"), batch=1,
                            smoke=sz.smoke)
    before = trainer.state.params
    rep = trainer.fit(sz.train_steps, log_every=1,
                      log=lambda m: say("train", m))
    moved = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree.leaves(trainer.state.params), jax.tree.leaves(before)))
    image = trainer.make_batch(0)["images"].shape
    say("train", f"deepcam images {image} | losses {rep.losses} | "
                 f"grad norms {rep.grad_norms}")
    say("train", "time between step completions (s, first is dispatch to "
                 "ready and includes compile) "
                 f"{[round(t, 3) for t in rep.step_times]}")
    say("train", f"max |param change| {moved:.3e} | peak_bytes_in_use "
                 f"{peak_bytes()}")
    # what the compiler planned for the step, beside what was allocated
    with trainer.mesh:
        mem = trainer.step_fn.lower(
            trainer.state, trainer._put_batch(trainer.make_batch(0))
        ).compile().memory_analysis()
    say("train", f"compiled step bytes: arguments "
                 f"{mem.argument_size_in_bytes} | outputs "
                 f"{mem.output_size_in_bytes} | temporaries "
                 f"{mem.temp_size_in_bytes}")
    check(rep.steps == sz.train_steps, f"ran {rep.steps} steps")
    check(all(math.isfinite(x) for x in rep.losses + rep.grad_norms),
          "non-finite loss or gradient norm")
    check(moved > 0, "the parameters did not change")


# --------------------------------------------------------------------------
# 4. record
# --------------------------------------------------------------------------

def phase_record(sz: Sizes, out_dir: str) -> None:
    from repro.session import Session

    s = Session(workspace=os.path.join(out_dir, "workspace"))
    res = s.record("deepcam", smoke=sz.smoke, batch=1, iters=3, warmup=1)
    say("record", f"machine {s.machine.name} | run {res.data.run_id}")
    for ph, pay in res.phases.items():
        walk = pay["flops"]
        xla = pay["xla_flops"] + pay["xla_transcendentals"]
        diff = abs(walk - xla) / xla if xla else math.inf
        say("record", f"{ph}: walker {walk:.6e} FLOP | xla "
                      f"{pay['xla_flops']:.6e} FLOP + "
                      f"{pay['xla_transcendentals']:.6e} transcendentals | "
                      f"diff {diff:.3%} (tol {TOL_FLOPS:.0%}) | host wall "
                      f"{pay['wall_s'] * 1e3:.3f} ms | {pay['launches']} "
                      "launches")
        check(diff <= TOL_FLOPS, f"{ph}: walker and XLA FLOPs differ by "
                                 f"{diff:.3%}")
    say("record", f"peak_bytes_in_use {peak_bytes()}")


# --------------------------------------------------------------------------
# 5. serve
# --------------------------------------------------------------------------

def phase_serve(sz: Sizes, out_dir: str, seed: int = 0) -> None:
    import jax
    import numpy as np

    from repro.session import Session

    s = Session(workspace=os.path.join(out_dir, "workspace"))
    res = s.serve("minitron-4b", smoke=sz.smoke, amp="O2", fusion="static",
                  n_requests=sz.requests, n_slots=sz.slots,
                  max_len=sz.max_len, prefill_chunk=sz.prefill_chunk,
                  prompt_len=sz.prompt_len, max_new=sz.max_new, seed=seed)
    _rec, stats, engine = res.data
    cfg = engine.cfg
    n_params = sum(x.size for x in jax.tree.leaves(engine.params))
    say("serve", f"{cfg.name}: {cfg.n_layers} layers, vocab "
                 f"{cfg.vocab_size}, {n_params} params in "
                 f"{jax.tree.leaves(engine.params)[0].dtype}")
    for line in res.text.splitlines():
        say("serve", line)
    check(res.exit_code == 0, "latency gate failed")

    prompt = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, sz.check_len).astype(np.int32)
    got = engine.prompt_logits(prompt)[:cfg.vocab_size]
    forward = jax.jit(lambda p, t: engine.model.forward_fn(
        p, {"tokens": t}, engine.run))
    want = forward(engine.params, prompt[None])[0, -1, :cfg.vocab_size]
    err = rel_err(got, want)
    say("serve", f"prefill logits vs full forward ({sz.check_len}-token "
                 f"prompt, {-(-sz.check_len // engine.chunk)} chunks): "
                 f"max|diff|/max|forward| {err:.3e} (tol {TOL_LOGITS:g})")
    say("serve", f"peak_bytes_in_use {peak_bytes()}")
    check(err <= TOL_LOGITS, f"prefill logits differ by {err:.3e}")


# --------------------------------------------------------------------------
# --chips 4: data-parallel DeepCAM vs the same batch on one chip
# --------------------------------------------------------------------------

def phase_parallel(sz: Sizes, chips: int = 4, seed: int = 0) -> None:
    """Global batch ``chips`` (one image per chip) over ``chips`` devices
    against the same batch on one of them with ``microbatches=chips``:
    DeepCAM's norm uses learned statistics, so both are one computation.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import RunConfig
    from repro.launch.train import build_trainer

    devices = jax.devices()[:chips]
    out = {}
    for label, devs, run in (
            (f"{chips} chips x 1 image", devices, RunConfig(amp="O1")),
            (f"1 chip x {chips} microbatches", devices[:1],
             RunConfig(amp="O1", microbatches=chips))):
        trainer = build_trainer("deepcam", run, batch=chips, smoke=sz.smoke,
                                devices=devs, seed=seed)
        init = jax.tree.leaves(trainer.state.params)
        rep = trainer.fit(1, log=lambda m: say("parallel", m))
        # every parameter's first update, gathered on the host
        update = np.concatenate([
            np.asarray(new.astype(jnp.float32) - old.astype(jnp.float32)
                       ).ravel()
            for new, old in zip(jax.tree.leaves(trainer.state.params), init)])
        norm = float(np.linalg.norm(update))
        out[label] = (rep.losses[0], rep.grad_norms[0], update)
        say("parallel", f"{label}: loss {rep.losses[0]!r} | grad norm "
                        f"{rep.grad_norms[0]!r} | update norm {norm!r} "
                        f"over {update.size} parameters")
        check(norm > 0, f"{label}: the parameters did not change")
        say("parallel", f"{label}: peak_bytes_in_use per device "
                        f"{[peak_bytes(d) for d in jax.devices()]}")
        del trainer
        gc.collect()
    (a, b) = out.values()
    for what, x, y in zip(("loss", "grad norm"), a, b):
        diff = abs(x - y) / abs(y)
        say("parallel", f"{what}: relative diff {diff:.3e} "
                        f"(tol {TOL_PARALLEL:g})")
        check(diff <= TOL_PARALLEL, f"{what} differs by {diff:.3e}")
    ua, ub = a[2], b[2]
    diff = float(np.linalg.norm(ua - ub) / np.linalg.norm(ub))
    flipped = int(np.count_nonzero(np.sign(ua) != np.sign(ub)))
    say("parallel", f"update: relative diff {diff:.3e} (tol {TOL_UPDATE:g})"
                    f" | {flipped} of {ub.size} parameters moved the other "
                    "way")
    check(diff <= TOL_UPDATE, f"update differs by {diff:.3e}")


# --------------------------------------------------------------------------

def run(sz: Sizes, chips: int, out_dir: str, seed: int = 0) -> dict:
    """Every phase in order; returns the device record of the last line."""
    info = require_tpu(chips)
    if chips > 1:
        phases = [("parallel", lambda: phase_parallel(sz, chips, seed))]
    else:
        phases = [("kernels", lambda: phase_kernels(sz, seed)),
                  ("train", lambda: phase_train(sz)),
                  ("record", lambda: phase_record(sz, out_dir)),
                  ("serve", lambda: phase_serve(sz, out_dir, seed))]
    for name, phase in phases:
        t0 = time.perf_counter()
        phase()
        gc.collect()
        say(name, f"passed in {time.perf_counter() - t0:.1f} s host wall")
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the data-parallel DeepCAM comparison")
    ap.add_argument("--out", default=os.path.join(ROOT, ".repro-workspace",
                                                  "chip_smoke"),
                    help="directory for the workspace the phases record "
                         "into")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    say("cache", f"compilation cache {cache}")
    info = run(FULL, args.chips, args.out, args.seed)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
