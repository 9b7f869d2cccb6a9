import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the exact production step function (train_step
for ``train_*``, forward for ``prefill_*``, serve_step for ``decode_*`` /
``long_*``), attaches the production shardings to ShapeDtypeStruct inputs
(no allocation), lowers and compiles it against the 16×16 single-pod mesh
and the 2×16×16 multi-pod mesh, and extracts:

* ``memory_analysis()``  — per-device bytes (proves the cell fits),
* ``cost_analysis()``    — XLA's own FLOPs/bytes (cross-check),
* the hierarchical-roofline terms from the HLO walk (paper methodology,
  ``repro.core``): compute / memory / collective seconds, dominant term,
  MODEL_FLOPS ratio, zero-AI census.

Results go to JSON (one record per cell) consumed by
``benchmarks/roofline_table.py`` and EXPERIMENTS.md.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch minitron-4b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out benchmarks/results/dryrun.jsonl
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SHAPES, ModelConfig, RunConfig, ShapeSpec
from repro.configs.registry import ARCHS, cells, get_config
from repro.core import get_machine
from repro.core.profiler import profile_compiled
from repro.core.roofline import model_flops_ratio
from repro.distributed import sharding as shd
from repro.launch.mesh import devices_per_pod, make_production_mesh
from repro.models import api as M
from repro.train import step as TS


# --------------------------------------------------------------------------
# Per-cell run policy (the BASELINE the hillclimbs start from)
# --------------------------------------------------------------------------

def default_run(cfg: ModelConfig, shape: ShapeSpec) -> RunConfig:
    n = cfg.param_count()
    if shape.kind == "train":
        # remat=full is the fit-first baseline: with scanned layers the live
        # set is one layer's carry, not L layers of activations.  The §Perf
        # hillclimbs relax this (dots / none) where memory headroom allows.
        return RunConfig(
            amp="O2" if n >= 500e9 else "O1",
            remat="full" if n >= 1e9 else "none",
            tp=True,
            fsdp=n >= 8e9,
            sp=n >= 500e9,    # sequence-shard activations at 1T scale
            optimizer="adafactor" if n >= 500e9 else "adamw",
            # microbatching bounds the live activation stack (one microbatch
            # at a time through fwd+bwd); under O2 (≥500B) the accumulator
            # stays bf16, so even 1T-param grads accumulate in storage dtype.
            microbatches=max(1, min(8, shape.global_batch // 32)),
            # chunked attention bounds live score memory to
            # (B, H, chunk, S) — the XLA-native stand-in for the flash
            # kernel (which replaces it on real TPU hardware)
            attn_impl="chunked" if shape.seq_len >= 4096 else "einsum",
            attn_chunk=512,
        )
    if shape.kind == "prefill":
        return RunConfig(amp="O1", tp=True, fsdp=n >= 50e9,
                         attn_impl="chunked", attn_chunk=512)
    # decode
    return RunConfig(amp="O1", tp=True, fsdp=n >= 50e9)


# --------------------------------------------------------------------------
# Cell → (fn, sharded input specs)
# --------------------------------------------------------------------------

def build_cell(arch: str, shape: ShapeSpec, mesh: jax.sharding.Mesh,
               run: RunConfig | None = None):
    """Returns (name, fn, args_specs, donate) ready to lower under mesh."""
    cfg = get_config(arch)
    run = run or default_run(cfg, shape)
    model = M.build(cfg)

    batch_abs = M.input_specs(cfg, shape)
    batch_sh = shd.shard_batch_dim(batch_abs, mesh, run)
    batch_specs = shd.with_sharding(batch_abs, batch_sh)

    if shape.kind == "train":
        state_abs = TS.abstract_state(model, run)
        pshard = shd.param_shardings(model.spec, mesh, run)
        oshard = shd.opt_state_shardings(state_abs.opt, pshard, mesh)
        rep = shd.replicated(mesh)
        state_sh = TS.TrainState(
            params=pshard, opt=oshard,
            loss_scale=jax.tree.map(lambda _: rep, state_abs.loss_scale),
            step=rep)
        state_specs = shd.with_sharding(state_abs, state_sh)
        fn = TS.make_train_step(model, run)
        return cfg, run, fn, (state_specs, batch_specs), (0,)

    # inference holds weights in the serving dtype (bf16 under O1/O2):
    # checkpoints are cast once at load, exactly like production serving
    params_abs = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, run.compute_dtype),
        model.spec, is_leaf=lambda x: hasattr(x, "axes"))
    pshard = shd.param_shardings(model.spec, mesh, run)
    params_specs = shd.with_sharding(params_abs, pshard)

    if shape.kind == "prefill":
        def fwd(params, batch):
            return model.forward_fn(params, batch, run)
        return cfg, run, fwd, (params_specs, batch_specs), ()

    # decode: serve_step — one token against a cache of size seq_len
    state_abs = M.decode_state_specs(cfg, shape)
    state_sh = shd.decode_state_shardings(state_abs, mesh, run)
    state_specs = shd.with_sharding(state_abs, state_sh)

    def serve_step(params, batch, state):
        return model.decode_fn(params, batch, state, run)

    return cfg, run, serve_step, (params_specs, batch_specs, state_specs), (2,)


def model_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """Headline MODEL_FLOPS: 6·N_active·tokens (train), 2·N_active (infer)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch          # one token per sequence


# --------------------------------------------------------------------------
# Runner
# --------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_kind: str,
             machine_name: str = "tpu-v5e",
             run_overrides: dict | None = None,
             return_profile: bool = False):
    """Lower one (arch, shape, mesh) cell and analyze it.

    Returns the summary dict; ``return_profile=True`` additionally hands
    back the underlying :class:`ProfileResult` as ``(rec, prof)`` so
    callers (``benchmarks.decode_batch_study``) can re-serialize the cell
    through the trace-store phase schema instead of this dict.
    """
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    cfg = get_config(arch)
    run = default_run(cfg, shape)
    if run_overrides:
        import dataclasses
        run = dataclasses.replace(run, **run_overrides)

    t0 = time.time()
    cfg, run, fn, arg_specs, donate = build_cell(arch, shape, mesh, run)
    jitted = jax.jit(fn, donate_argnums=donate)
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*arg_specs)
        compiled = lowered.compile()
    t_compile = time.time() - t0

    machine = get_machine(machine_name)
    n_dev = int(mesh.devices.size)
    # dot/conv FLOPs classify onto the AMP policy's compute-dtype ceiling
    # (CPU bf16 legalization hides bf16 in the compiled module;
    # docs/DESIGN.md §9)
    from repro.core.hlo_analysis import dtype_class
    mm_class = dtype_class(
        "bf16" if run.compute_dtype == jnp.bfloat16 else "f32")
    prof = profile_compiled(f"{arch}/{shape_name}/{mesh_kind}", compiled,
                            machine, devices_per_pod(mesh), n_dev,
                            matmul_class=mm_class)
    mf = model_flops(cfg, shape)
    ratio = model_flops_ratio(mf, prof.analysis, n_dev)
    mem = prof.memory_stats
    census = prof.analysis.zero_ai_census()

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "n_devices": n_dev,
        "run": {k: getattr(run, k) for k in
                ("amp", "remat", "tp", "fsdp", "sp", "attn_impl",
                 "attn_chunk", "optimizer", "microbatches",
                 "sharded_logits")},
        "compile_s": round(t_compile, 2),
        # roofline terms (seconds, per device)
        "compute_s": prof.terms.compute_s,
        "memory_s": prof.terms.memory_s,
        "collective_ici_s": prof.terms.collective_ici_s,
        "collective_dcn_s": prof.terms.collective_dcn_s,
        "dominant": prof.terms.dominant,
        "bound_overlap_s": prof.terms.bound_overlap_s,
        "roofline_fraction": prof.terms.roofline_fraction,
        # raw quantities
        "hlo_flops_per_dev": prof.analysis.total_flops,
        "flops_by_class": prof.terms.flops_by_class,
        "hbm_bytes_per_dev": prof.analysis.total_hbm_bytes,
        "ici_wire_bytes": prof.terms.ici_wire_bytes,
        "dcn_wire_bytes": prof.terms.dcn_wire_bytes,
        "model_flops_global": mf,
        "model_flops_ratio": ratio,
        # memory fit
        "peak_device_bytes": prof.peak_device_bytes,
        "fits_hbm": prof.fits_hbm(machine),
        "memory": None if mem is None else {
            "args": int(mem.argument_size_in_bytes),
            "out": int(mem.output_size_in_bytes),
            "temp": int(mem.temp_size_in_bytes),
            "alias": int(mem.alias_size_in_bytes),
        },
        # XLA cross-check (loop bodies counted once by XLA)
        "xla_flops": prof.xla_flops,
        "xla_bytes": prof.xla_bytes,
        "n_kernels": len(prof.analysis.kernels),
        "zero_ai": {k: v[0] for k, v in census.items()},
    }

    # kernel-adjusted terms: the modeled effect of swapping the Pallas
    # flash-attention / SSD kernels in for the XLA-native lowerings
    # (see repro.core.kernel_adjust; TPU-target, clearly labeled modeled)
    from repro.core.kernel_adjust import adjusted_terms
    dp = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                      if a in mesh.shape]))
    tp = mesh.shape.get("model", 1)
    adj, removed = adjusted_terms(prof.analysis, machine, cfg, shape, run,
                                  dp, tp)
    rec["adj_memory_s"] = adj.memory_s
    rec["adj_dominant"] = adj.dominant
    rec["adj_roofline_fraction"] = adj.roofline_fraction
    rec["adj_bytes_removed"] = removed
    if return_profile:
        return rec, prof
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, choices=list(ARCHS))
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=("single", "multi", "both"))
    ap.add_argument("--all", action="store_true",
                    help="every applicable (arch x shape) cell")
    ap.add_argument("--out", default=None, help="append JSONL here")
    ap.add_argument("--machine", default="tpu-v5e")
    ap.add_argument("--set", action="append", default=[],
                    help="RunConfig override, e.g. --set remat=dots")
    args = ap.parse_args(argv)

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = (v if not v.replace("-", "").isdigit() else int(v))
        if v in ("True", "False"):
            overrides[k] = v == "True"

    if args.all:
        todo = [(a, s.name) for a in ARCHS for s in cells(a)]
    else:
        if not (args.arch and args.shape):
            ap.error("need --arch and --shape (or --all)")
        todo = [(args.arch, args.shape)]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = 0
    out_f = open(args.out, "a") if args.out else None
    for arch, shape_name in todo:
        for mesh_kind in meshes:
            tag = f"{arch} × {shape_name} × {mesh_kind}"
            try:
                rec = run_cell(arch, shape_name, mesh_kind, args.machine,
                               overrides or None)
            except Exception:
                failures += 1
                print(f"[FAIL] {tag}")
                traceback.print_exc()
                if out_f:
                    out_f.write(json.dumps(
                        {"arch": arch, "shape": shape_name,
                         "mesh": mesh_kind, "error": True}) + "\n")
                    out_f.flush()
                continue
            print(f"[ok] {tag}: compile {rec['compile_s']}s | "
                  f"compute {rec['compute_s']*1e3:.2f}ms "
                  f"memory {rec['memory_s']*1e3:.2f}ms "
                  f"coll {(rec['collective_ici_s']+rec['collective_dcn_s'])*1e3:.2f}ms | "
                  f"dominant={rec['dominant']} "
                  f"frac={rec['roofline_fraction']:.3f} | "
                  f"peak {rec['peak_device_bytes']/2**30:.2f} GiB/dev "
                  f"fits={rec['fits_hbm']}")
            if out_f:
                out_f.write(json.dumps(rec) + "\n")
                out_f.flush()
    if out_f:
        out_f.close()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
