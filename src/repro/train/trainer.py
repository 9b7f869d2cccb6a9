"""Training loop: checkpoint/restart, straggler mitigation, elastic re-mesh.

The loop is deliberately boring — all the cleverness lives in the jitted
step — but it carries the operational features a 1000-node deployment
needs (task spec §large-scale runnability):

* **restart** — on construction the trainer looks for the latest checkpoint
  and resumes (step counter ⇒ exact data-stream position, because the data
  pipeline is a pure function of the step);
* **async checkpointing** every ``ckpt_every`` steps (I/O off the step path);
* **straggler detection** — an EWMA of step wall-times; a step slower than
  ``straggler_factor``× the EWMA is logged with its host id (on real
  multi-host this feeds the scheduler's replace-node decision; here it is
  surfaced via ``TrainReport.stragglers``);
* **elastic re-mesh** — ``restore`` re-shards onto whatever mesh the
  restarted job got (checkpoints are mesh-independent), so scaling the pod
  count up or down between runs needs no conversion step;
* **preemption safety** — SIGTERM sets a flag; the loop checkpoints and
  exits cleanly at the next step boundary;
* **fault tolerance** — restore walks checkpoints newest→oldest and skips
  any that fail their integrity digest (a truncated newest checkpoint
  falls back to the previous one, never to garbage); transient step
  faults are retried with exponential backoff; the async checkpointer's
  ``healthy()`` probe is polled each log interval so a dead writer fails
  the run promptly, not at the next save.

**One step in flight.**  ``fit`` dispatches step i+1 before it waits on
and reads back step i's metrics, so the host's call of the jitted step
(tens of ms for a model of a thousand state arrays) runs while the
device works on step i.  ``fit`` may therefore return with its last step
still on the device.  That step is resolved — waited on, read back,
timed, logged and counted in the report — the next time anything needs
it: the next ``fit`` call (right after it dispatches its own first
step), any read of ``Trainer.report`` or of a field of the report ``fit``
returned, an assignment to ``Trainer.state`` or ``Trainer.report``, a
checkpoint save, SIGTERM, or an exception leaving ``fit`` (the report
then counts exactly the steps that landed).  Reading ``Trainer.state``
does not wait: its arrays are the in-flight step's outputs.  In the
profiler's trace, the ``wait`` and ``readback`` spans inside step i+1's
``repro.train.step`` belong to step i.  A step's ``step_times`` entry is
the time from the previous step's completion to its own, or from its
dispatch when nothing was in flight before it.
"""

from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable

import jax
import numpy as np

from repro.checkpoint import checkpointer as ckpt
from repro.configs.base import RunConfig
from repro.models.api import Model
from repro.resilience import faults
from repro.trace import spans
from repro.train.step import TrainState, init_state, make_train_step


@dataclasses.dataclass
class TrainReport:
    steps: int = 0
    losses: list = dataclasses.field(default_factory=list)
    grad_norms: list = dataclasses.field(default_factory=list)
    step_times: list = dataclasses.field(default_factory=list)
    stragglers: list = dataclasses.field(default_factory=list)
    restarts: int = 0
    resumed_from: int | None = None
    retries: int = 0                 # transient step faults retried past
    skipped_ckpts: list = dataclasses.field(default_factory=list)
    overlapped: int = 0              # steps dispatched while the previous
                                     # step's metrics were still unread
    # the trainer's resolve of its step in flight, while ``fit`` has
    # returned with one; a read of any field calls it first
    _drain: Callable[[], None] | None = dataclasses.field(
        default=None, repr=False, compare=False)

    def __getattribute__(self, name):
        if not name.startswith("_"):
            drain = object.__getattribute__(self, "_drain")
            if drain is not None:
                drain()
        return object.__getattribute__(self, name)


@dataclasses.dataclass
class _InFlight:
    """A dispatched step whose metrics are not read yet."""
    step: int
    metrics: dict
    t_start: float                   # its dispatch, or the completion of
                                     # the step in flight before it
    log: Callable[[str], None]
    log_every: int


class Trainer:
    def __init__(self, model: Model, run: RunConfig,
                 make_batch: Callable[[int], dict],
                 ckpt_dir: str | None = None,
                 ckpt_every: int = 50,
                 lr: float = 3e-4,
                 mesh: jax.sharding.Mesh | None = None,
                 state_shardings: Any = None,
                 batch_shardings: Any = None,
                 straggler_factor: float = 2.0,
                 seed: int = 0,
                 ckpt_keep: int = 3,
                 step_retries: int = 2,
                 retry_backoff_s: float = 0.05):
        self.model, self.run = model, run
        self.make_batch = make_batch
        self.ckpt_dir, self.ckpt_every = ckpt_dir, ckpt_every
        self.mesh = mesh
        self.state_shardings = state_shardings
        self.batch_shardings = batch_shardings
        self.straggler_factor = straggler_factor
        self.step_retries = step_retries
        self.retry_backoff_s = retry_backoff_s
        self._pending: _InFlight | None = None
        self._next_step: int | None = None   # None: read ``state.step``
        self._ewma: float | None = None
        self._report = TrainReport()
        self._stop = False
        self._async_ckpt = ckpt.AsyncCheckpointer(keep=ckpt_keep)

        step_fn = make_train_step(model, run, lr=lr)
        jit_kwargs: dict[str, Any] = {}
        if state_shardings is not None:
            jit_kwargs["in_shardings"] = (state_shardings, batch_shardings)
            jit_kwargs["out_shardings"] = (state_shardings, None)
        self.step_fn = jax.jit(step_fn, **jit_kwargs)

        # ----- init or resume -------------------------------------------
        self.state = self._init_or_resume(seed)

    # -------------------------------------------------------------------
    @property
    def state(self) -> TrainState:
        return self._state

    @state.setter
    def state(self, value: TrainState) -> None:
        self._drain()
        self._state = value
        self._next_step = None

    @property
    def report(self) -> TrainReport:
        self._drain()
        return self._report

    @report.setter
    def report(self, value: TrainReport) -> None:
        self._drain()
        self._report = value

    def _drain(self) -> None:
        """Resolves the step in flight, if there is one, outside any
        step's spans."""
        rec, self._pending = self._pending, None
        self._report._drain = None
        if rec is not None:
            jax.block_until_ready(rec.metrics["loss"])
            self._record(rec)

    def _record(self, rec: _InFlight) -> float:
        """The report's bookkeeping of a step whose loss is ready; returns
        the time it was seen complete."""
        t = time.perf_counter()
        dt = t - rec.t_start
        report, i, ewma = self._report, rec.step, self._ewma
        # straggler detection (per-step heartbeat timing)
        if ewma is not None and dt > self.straggler_factor * ewma:
            report.stragglers.append((i, dt, ewma))
        self._ewma = dt if ewma is None else 0.9 * ewma + 0.1 * dt

        loss = float(rec.metrics["loss"])
        report.steps += 1
        report.losses.append(loss)
        report.grad_norms.append(float(rec.metrics["grad_norm"]))
        report.step_times.append(dt)
        if rec.log_every and i % rec.log_every == 0:
            rec.log(f"[trainer] step {i:5d} loss {loss:.4f} "
                    f"({dt*1e3:.1f} ms, grad_norm "
                    f"{report.grad_norms[-1]:.3f})")
            if not self._async_ckpt.healthy():
                rec.log(f"[trainer] checkpoint writer failed; "
                        f"surfacing at step {i}")
                self._async_ckpt.wait()          # raises the stored error
        return t

    def _save(self, step: int) -> None:
        """Saves the state after ``step`` steps; the step in flight, which
        made it, is resolved first."""
        self._drain()
        self._async_ckpt.save(self.ckpt_dir, step, self._state,
                              {"step": step})

    # -------------------------------------------------------------------
    def _init_or_resume(self, seed: int) -> TrainState:
        if self.ckpt_dir is not None:
            steps = ckpt.available_steps(self.ckpt_dir)
            if steps:
                like = jax.eval_shape(
                    lambda: init_state(self.model, self.run,
                                       jax.random.PRNGKey(seed)))
                # newest first; skip anything corrupt or half-written —
                # resuming from an older verified checkpoint beats dying
                for step in reversed(steps):
                    try:
                        state, meta = ckpt.restore(
                            self.ckpt_dir, like, step=step,
                            shardings=self.state_shardings)
                    except (ckpt.CheckpointCorrupt, OSError, KeyError,
                            ValueError) as e:
                        self._report.skipped_ckpts.append((step, repr(e)))
                        continue
                    self._report.resumed_from = int(meta.get("step", step))
                    self._report.restarts += 1
                    return state
        with_mesh = self.mesh if self.mesh is not None else _null_ctx()
        with with_mesh:
            state = init_state(self.model, self.run, jax.random.PRNGKey(seed))
            if self.state_shardings is not None:
                state = jax.device_put(state, self.state_shardings)
        return state

    # -------------------------------------------------------------------
    def _install_sigterm(self) -> None:
        def handler(_sig, _frm):
            self._stop = True
        try:
            signal.signal(signal.SIGTERM, handler)
        except ValueError:                       # not on the main thread
            pass

    def _put_batch(self, batch: dict) -> dict:
        if self.batch_shardings is not None:
            return jax.device_put(batch, self.batch_shardings)
        return batch

    def _step_resilient(self, i: int, batch: dict,
                        log: Callable[[str], None],
                        plan: "faults.FaultPlan"):
        """One train step with fault hooks and transient-fault retry.

        The step function is a pure function of (state, batch), so a
        retry recomputes bit-identical results — losses after a retried
        step match an uninterrupted run exactly.
        """
        attempts = self.step_retries + 1
        for attempt in range(attempts):
            try:
                plan.maybe_crash("crash_step", target=i)
                plan.maybe_raise("step_fault", target=i)
                with jax.profiler.TraceAnnotation(spans.DISPATCH):
                    return self.step_fn(self._state, batch)
            except faults.TransientFault as e:
                if attempt + 1 >= attempts:
                    raise
                self._report.retries += 1
                delay = self.retry_backoff_s * (2 ** attempt)
                log(f"[trainer] transient fault at step {i} "
                    f"(attempt {attempt + 1}/{attempts}): {e}; "
                    f"retrying in {delay:g}s")
                time.sleep(delay)
        raise AssertionError("unreachable")

    def fit(self, n_steps: int, log_every: int = 10,
            log: Callable[[str], None] = print) -> TrainReport:
        self._install_sigterm()
        self._report._drain = None       # this call resolves its own steps
        annotate = jax.profiler.TraceAnnotation
        if self._next_step is None:      # nothing is in flight
            self._next_step = int(self._state.step)
        ctx = self.mesh if self.mesh is not None else _null_ctx()
        try:
            with ctx:
                plan = faults.active_plan()
                while (i := self._next_step) < n_steps:
                    if self._stop:
                        log(f"[trainer] SIGTERM at step {i}; checkpointing")
                        self._drain()
                        break
                    # each part of the step in its host span
                    # (repro.trace.spans); wait and readback are step i-1's
                    with jax.profiler.StepTraceAnnotation(spans.STEP,
                                                          step_num=i):
                        with annotate(spans.BATCH):
                            host_batch = self.make_batch(i)
                        with annotate(spans.PUT):
                            batch = self._put_batch(host_batch)
                        t0 = time.perf_counter()
                        self._state, metrics = self._step_resilient(
                            i, batch, log, plan)
                        self._next_step = i + 1
                        prev, self._pending = self._pending, _InFlight(
                            i, metrics, t0, log, log_every)
                        if prev is not None:
                            self._report.overlapped += 1
                        with annotate(spans.WAIT):
                            if prev is not None:
                                jax.block_until_ready(prev.metrics["loss"])
                        with annotate(spans.READBACK):
                            if prev is not None:
                                self._pending.t_start = self._record(prev)
                        if (self.ckpt_dir is not None and self.ckpt_every
                                and (i + 1) % self.ckpt_every == 0):
                            with annotate(spans.CHECKPOINT):
                                self._save(i + 1)
            if self.ckpt_dir is not None:
                with annotate(spans.CHECKPOINT):
                    self._save(int(self._state.step))
                    self._async_ckpt.wait()
        except BaseException as err:
            try:
                self._drain()                # count the steps that landed
            except Exception as e:           # the step in flight did not
                err.add_note(f"the step in flight failed too: {e!r}")
            raise
        if self._pending is not None:
            self._report._drain = self._drain
        return self._report


class _null_ctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False
