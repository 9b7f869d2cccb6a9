"""Training cells: the program's ``Trainer`` driven step after step.

Set-up builds one trainer through ``repro.launch.train.build_trainer``,
gives it weights made by the configuration's reference from the seed and
a feed of pre-made batches, and runs its first ``setup_steps`` steps
through ``Trainer.fit``, the call the window then repeats.  Those steps
are what the reference follows afterwards: each step's loss, the first
gradient as AdamW received it (its first moment after one step, over
1 - b1) and the change of the parameters after the last of them, each
read per leaf.  The window runs ``Trainer.fit`` one step at a time until
``--seconds`` have passed; each step still pays its batch's
``device_put``.  After it, the run checks that every step went through
the benchmark's feed and that the window's steps moved the state.
"""

from __future__ import annotations

import gc
import math

import numpy as np

from benchlib import harness
from benchlib import traffic as T

#: the trainer's calls the benchmark wraps, each made once per step
FEED_CALLS = ("make_batch", "device_put", "step")

#: leaves whose reference gradient is under this share of the median
#: leaf's move by rounding alone under AdamW; their change is not compared
ROUNDING_LEAF = 1e-3


def _norms_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(tree):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                          for x in jax.tree.leaves(tree)])

    @jax.jit
    def diff_norms(a, b):
        return norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))
    return norms, diff_norms


# --------------------------------------------------------------------------
# the program
# --------------------------------------------------------------------------

class Program:
    """One trainer, its feed and the readings of its first steps."""

    def __init__(self, ctx: harness.Ctx):
        import jax
        with harness.program_on_path():
            from repro.configs.base import RunConfig
            from repro.launch.train import build_trainer
        cfg, spec = ctx.cfg, ctx.traffic
        self.ctx = ctx
        self.ref = harness.reference_of(cfg)
        self.batch = int(spec["global_batch"])
        if self.batch % ctx.chips:
            raise harness.BenchError(f"a global batch of {self.batch} does "
                                     f"not divide over {ctx.chips} chips")
        self.trainer = build_trainer(
            cfg["program"]["registry"], RunConfig(**cfg["program"]["run"]),
            batch=self.batch, lr=cfg["optimizer"]["lr"],
            devices=ctx.devices, smoke=bool(cfg["program"].get("smoke")))
        self.init = jax.jit(lambda k: self.ref.init_params(cfg, k),
                            out_shardings=self.trainer.state_shardings.params)
        self.norms, self.diff_norms = _norms_fn()
        self._wrap()

    def _wrap(self) -> None:
        """Host spans around the calls the trainer makes into the feed,
        the transfer and the step, each counted: :meth:`check_feed` sees
        that the trainer still makes them."""
        tr, span = self.trainer, self.ctx.tracer.span
        put, step = tr._put_batch, tr.step_fn
        self.put_batch, self.step_fn = put, step
        self.calls = dict.fromkeys(FEED_CALLS, 0)

        def make_batch(i):
            self.calls["make_batch"] += 1
            with span("bench.train.make_batch"):
                return self.pool[i % len(self.pool)]

        def put_batch(b):
            self.calls["device_put"] += 1
            with span("bench.train.device_put"):
                return put(b)

        def step_fn(state, b):
            self.calls["step"] += 1
            with span("bench.train.step_dispatch"):
                return step(state, b)

        tr.make_batch, tr._put_batch, tr.step_fn = make_batch, put_batch, \
            step_fn

    def check_feed(self) -> None:
        """Each step the trainer reports made the benchmark's feed,
        transfer and step calls once; otherwise the trainer has stopped
        calling them and the work is no longer the cell's."""
        steps = self.trainer.report.steps
        wrong = {k: n for k, n in self.calls.items() if n != steps}
        if wrong:
            raise harness.BenchError(
                f"the trainer made {wrong} calls into the benchmark's feed "
                f"in {steps} steps; it no longer runs the cell's work")

    def start(self, seed: int) -> dict:
        """Weights and feed from ``seed``, then the set-up steps; returns
        the program's readings of them."""
        import jax
        cfg, spec = self.ctx.cfg, self.ctx.traffic
        tr = self.trainer
        self.pool = [T.climate_batch(seed, i, self.batch,
                                     tuple(cfg["image_hw"]),
                                     cfg["in_channels"], cfg["classes"])
                     for i in range(int(spec["pool_batches"]))]
        n = int(cfg["setup_steps"])
        if len(self.pool) < n:
            raise harness.BenchError("the pool must hold a batch for each "
                                     "set-up step, so that their rows differ")
        params0 = self.init(harness.seed_key(seed))
        harness.check_layout(params0, tr.state.params)
        zeros = jax.tree.map(lambda x: x * 0, (tr.state.opt, tr.state.step))
        tr.state = jax.device_put(
            tr.state._replace(params=params0, opt=zeros[0], step=zeros[1]),
            tr.state_shardings)
        tr.report = type(tr.report)()
        self.calls = dict.fromkeys(FEED_CALLS, 0)
        b1 = cfg["optimizer"]["b1"]
        tr.fit(1, log_every=0)
        grad = np.asarray(self.norms(tr.state.opt.mu)) / (1.0 - b1)
        tr.fit(n, log_every=0)
        change = np.asarray(self.diff_norms(tr.state.params, params0))
        return {"losses": list(tr.report.losses[:n]), "grad": grad,
                "change": change}

    def hlo_text(self) -> str:
        tr = self.trainer
        with tr.mesh:
            return self.step_fn.lower(
                tr.state, self.put_batch(self.pool[0])).compile().as_text()


# --------------------------------------------------------------------------
# the reference
# --------------------------------------------------------------------------

class Reference:
    """The configuration's reference following the set-up steps: the same
    weights (made again from the seed) and batches, plain AdamW, float32
    at the highest precision; ``quant`` gives the control's convolutions.
    The compiled steps are kept for the next seed."""

    def __init__(self, ctx: harness.Ctx):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as Ps
        self.ctx, self.cfg = ctx, ctx.cfg
        self.ref = harness.reference_of(ctx.cfg)
        mesh = Mesh(np.array(ctx.devices), ("b",))
        self.rep = NamedSharding(mesh, Ps())
        self.rows = NamedSharding(mesh, Ps("b"))
        self.init = jax.jit(lambda k: self.ref.init_params(self.cfg, k),
                            out_shardings=self.rep)
        self.norms, self.diff_norms = _norms_fn()
        self._steps: dict = {}

    def _step(self, quant):
        import jax
        import jax.numpy as jnp
        if quant in self._steps:
            return self._steps[quant]
        cfg, ref, o = self.cfg, self.ref, self.cfg["optimizer"]
        kw = {} if quant is None else {"quant": quant}

        def step(params, mu, nu, count, batch):
            loss, g = jax.value_and_grad(ref.loss)(params, batch, cfg, **kw)
            c = count + 1
            bc1, bc2 = 1 - o["b1"] ** c, 1 - o["b2"] ** c
            mu = jax.tree.map(lambda m, x: o["b1"] * m + (1 - o["b1"]) * x,
                              mu, g)
            nu = jax.tree.map(lambda v, x: o["b2"] * v + (1 - o["b2"]) * x * x,
                              nu, g)
            params = jax.tree.map(
                lambda p, m, v: p - o["lr"] * (
                    (m / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
                    + o["weight_decay"] * p),
                params, mu, nu)
            return params, mu, nu, c, loss, self.norms(g)

        r = self.rep
        self._steps[quant] = jax.jit(step, in_shardings=(r, r, r, r,
                                                         self.rows),
                                     donate_argnums=(0, 1, 2))
        return self._steps[quant]

    def readings(self, seed: int, pool: list, quant=None) -> dict:
        import jax
        import jax.numpy as jnp
        step = self._step(quant)
        params = self.init(harness.seed_key(seed))
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        count = jnp.int32(0)
        losses, grad = [], None
        for i in range(int(self.cfg["setup_steps"])):
            batch = jax.device_put(pool[i], self.rows)
            params, mu, nu, count, loss, g = step(params, mu, nu, count,
                                                  batch)
            losses.append(float(loss))
            if grad is None:
                grad = np.asarray(g)
        change = np.asarray(self.diff_norms(
            params, self.init(harness.seed_key(seed))))
        return {"losses": losses, "grad": grad, "change": change}


def compare(prog: dict, ref: dict) -> dict[str, float]:
    """The numbers compared, each the worst over leaves:
    ``loss_gap``  |loss - ref| / |ref| of the first set-up step, the
                  forward of the same weights and images;
    ``grad_gap``  per leaf, the gap between the norms of the program's
                  first gradient and the reference's, over the larger of
                  the reference leaf's norm and the median leaf's;
    ``change_gap`` the same for the change of the parameters over the
                  set-up steps, leaving out leaves whose reference
                  gradient is rounding (:data:`ROUNDING_LEAF`).
    The median and 90th percentile over leaves of the last two are
    returned too, for the readings behind the limits; only the numbers
    named in the configuration's ``limits`` are checked.
    The later steps' losses are not compared: AdamW's first steps move
    each weight by about lr x sign(gradient), so where a gradient is near
    zero its rounding picks the sign, and their losses drift apart by up
    to a few percent on sound runs (see PERF.md)."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    out = {"loss_gap": float(abs(lp[0] - lr[0]) / abs(lr[0]))}
    g, d = leaf_gaps(prog, ref)
    out["grad_gap"], out["change_gap"] = float(np.max(g)), float(np.max(d))
    out["grad_gap_median"] = float(np.median(g))
    out["change_gap_median"] = float(np.median(d))
    out["grad_gap_p90"] = float(np.percentile(g, 90))
    out["change_gap_p90"] = float(np.percentile(d, 90))
    return {k: (v if math.isfinite(v) else float("inf"))
            for k, v in out.items()}


def leaf_gaps(prog: dict, ref: dict) -> tuple[np.ndarray, np.ndarray]:
    """Per leaf: the first gradient's norm gap, and the change's over the
    leaves whose reference gradient is not rounding; each over the larger
    of the reference leaf's norm and the median leaf's."""
    g_med = float(np.median(ref["grad"]))
    g = np.abs(prog["grad"] - ref["grad"]) / np.maximum(ref["grad"], g_med)
    keep = ref["grad"] >= ROUNDING_LEAF * g_med
    dr, dp = ref["change"][keep], prog["change"][keep]
    return g, np.abs(dp - dr) / np.maximum(dr, np.median(dr))


def later_loss_gaps(prog: dict, ref: dict) -> list[float]:
    """|loss - ref| / |ref| of every set-up step: printed, not compared."""
    return [abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                 ref["losses"])]


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def run(ctx: harness.Ctx, prog: Program | None = None,
        reference: Reference | None = None) -> harness.Outcome:
    """One run; a test may hand in a program and a reference it built."""
    clock, tracer = ctx.tracer.clock, ctx.tracer
    harness.say(f"set-up: imports and device {clock() - ctx.t_start:.3f} s")
    prog = prog or Program(ctx)
    harness.say(f"set-up: trainer built {clock() - ctx.t_start:.3f} s")
    readings = prog.start(ctx.seed)
    tr = prog.trainer
    k = n = int(ctx.cfg["setup_steps"])
    prog.check_feed()
    p_setup = tr.state.params      # the step returns new arrays: kept as is
    setup_s = clock() - ctx.t_start

    ctx.counter.on = True
    t0 = clock()
    tracer.open_window(t0)
    deadline = t0 + ctx.seconds
    # steps and seconds before, in and after the traced sub-window
    phases = [[0, 0.0], [0, 0.0], [0, 0.0]]
    while (now := clock()) < deadline:
        tracing = tracer.poll(now)
        phase = 1 if tracing else 0 if tracer.started is None else 2
        with tracer.span("bench.train.fit"):
            tr.fit(k + 1, log_every=0)
        k += 1
        phases[phase][0] += 1
        phases[phase][1] += clock() - now
    t_end = clock()
    tracer.close()
    ctx.counter.on = False
    steps = k - n
    if steps == 0:
        raise harness.BenchError("no step finished in the window")
    prog.check_feed()
    window_losses = tr.report.losses[-steps:]
    bad = sum(not math.isfinite(x) for x in window_losses)
    harness.say(f"compilations in the window: {ctx.counter.n}")
    harness.say(f"window: {steps} steps of {prog.batch} images in "
                f"{t_end - t0!r} s")
    if ctx.trace:
        harness.say("steps and seconds before, in and after the traced "
                    f"sub-window: {phases!r}")
    # the window's steps moved the state: its step count and every leaf
    # that the set-up steps moved
    moved = np.asarray(prog.diff_norms(tr.state.params, p_setup))
    guards = {"steps_lost": float(abs(k - int(tr.state.step))),
              "leaves_unmoved": float(np.sum((readings["change"] > 0)
                                             & (moved == 0)))}
    mem = harness.peak_bytes(ctx.devices)

    reading = {"examples_per_chip": prog.batch // ctx.chips,
               "flops_per_example": float(ctx.cfg["train_flops_per_image"]),
               "program": "jit_train_step"}
    if ctx.trace:
        from benchlib import hlo
        reading["costs"] = hlo.costs(prog.hlo_text())
    pool = prog.pool
    del prog, tr, p_setup
    gc.collect()

    ref = (reference or Reference(ctx)).readings(ctx.seed, pool)
    numbers = compare(readings, ref)
    harness.say(f"losses: program {readings['losses']!r} reference "
                f"{ref['losses']!r}")
    harness.say(f"compared: {numbers!r}; loss gap of each set-up step "
                f"{later_loss_gaps(readings, ref)!r}")
    checks = {**harness.fmt_checks(numbers, ctx.limits),
              **{g: (v, 0.0) for g, v in guards.items()}}
    ok = harness.all_within(checks) and bad == 0 and ctx.counter.n == 0
    return harness.Outcome(
        correct=ok, attempted=steps * len(pool[0]["images"]),
        failed=bad * len(pool[0]["images"]),
        end_to_end={"images_per_s": steps * len(pool[0]["images"])
                    / (t_end - t0), "setup_s": setup_s},
        checks=checks, memory_peak_bytes=mem, reading=reading)
