"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Runs a REAL training loop on whatever devices this process has (CPU smoke /
single TPU host / full pod under jaxdist) with the same model, step function
and sharding rules the dry-run lowers for the production mesh.  Features
exercised here: sharded TrainState, host-prefetched deterministic data,
async checkpointing + restart, straggler logging (see repro.train.trainer).

Examples::

    PYTHONPATH=src python -m repro.launch.train --arch minitron-4b \
        --smoke --steps 50 --batch 8 --seq 128 --ckpt /tmp/ckpt
    PYTHONPATH=src python -m repro.launch.train --arch deepcam --smoke \
        --steps 20 --batch 4
"""

from __future__ import annotations

import argparse
import sys

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs.base import RunConfig, ShapeSpec
from repro.configs.registry import ALL, get_config, get_smoke
from repro.data.pipeline import ClimateStream, TokenStream
from repro.distributed import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.models import api as M
from repro.train import step as TS
from repro.train.trainer import Trainer


def build_trainer(arch: str, run: RunConfig, *, batch: int, seq: int = 128,
                  smoke: bool = False, lr: float = 3e-4,
                  ckpt: str | None = None, ckpt_every: int = 50,
                  devices=None, seed: int = 0) -> Trainer:
    """A :class:`Trainer` for a registry config, data-parallel over
    ``devices`` (default: every device of this process) with the repo's
    parameter, optimizer-state and batch shardings."""
    cfg = get_smoke(arch) if smoke else get_config(arch)
    model = M.build(cfg)
    shape = ShapeSpec("cli", seq, batch, "train")

    mesh = make_host_mesh(devices)
    state_abs = TS.abstract_state(model, run)
    pshard = shd.param_shardings(model.spec, mesh, run)
    oshard = shd.opt_state_shardings(state_abs.opt, pshard, mesh)
    rep = shd.replicated(mesh)
    state_sh = TS.TrainState(
        params=pshard, opt=oshard,
        loss_scale=jax.tree.map(lambda _: rep, state_abs.loss_scale),
        step=rep)
    batch_sh = shd.shard_batch_dim(M.input_specs(cfg, shape), mesh, run)

    if cfg.family == "cnn":
        from repro.configs.deepcam import IMAGE_HW, SMOKE_HW
        stream = ClimateStream(SMOKE_HW if smoke else IMAGE_HW, batch)
    else:
        stream = TokenStream(cfg, shape, batch)

    return Trainer(model, run, stream, ckpt_dir=ckpt, ckpt_every=ckpt_every,
                   lr=lr, mesh=mesh, state_shardings=state_sh,
                   batch_shardings=batch_sh, seed=seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list(ALL))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--amp", default="O1", choices=("O0", "O1", "O2"))
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none",
                    choices=("none", "dots", "full"))
    args = ap.parse_args(argv)

    enable_compile_cache()
    run = RunConfig(amp=args.amp, remat=args.remat,
                    microbatches=args.microbatches)
    trainer = build_trainer(args.arch, run, batch=args.batch, seq=args.seq,
                            smoke=args.smoke, lr=args.lr, ckpt=args.ckpt,
                            ckpt_every=args.ckpt_every)
    report = trainer.fit(args.steps)
    print(f"[train] {report.steps} steps, final loss "
          f"{report.losses[-1]:.4f}, mean time between step completions "
          f"{1e3 * sum(report.step_times[1:]) / max(len(report.step_times) - 1, 1):.1f} ms, "
          f"overlapped {report.overlapped}, "
          f"stragglers {len(report.stragglers)}, "
          f"resumed_from={report.resumed_from}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
