"""Campaign execution: expand → (pool of workers) → trace store.

Every sweep point runs the analytical pipeline (lower + compile + HLO walk
→ three-term bounds) and, when ``measured``, executes the *same* compiled
executable to fold wall time back in (``repro.trace``).  One
schema-versioned :class:`~repro.trace.store.TraceRecord` per point lands in
the store, stamped with the sweep name and the point's content hash — the
report side groups on those.

Two mechanics matter here:

* **Process-pool workers.**  XLA's host-platform device count is fixed at
  jax import, so a point whose mesh needs N > 1 (virtual) devices cannot
  run in a process that already imported jax.  The engine groups points by
  device count and runs one spawn-context pool per group; each worker's
  initializer sets ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
  *before* the first job triggers the lazy jax import.  This module
  therefore imports neither jax nor ``repro.trace`` at module scope.

* **Per-point analysis caching.**  Analytical (bound-only) points are pure
  functions of (point, jax version, machine model): their phase payloads
  are cached under the point key, so re-running a campaign recompiles only
  points whose spec actually changed.  Measured points always execute —
  a wall-clock sample is not cacheable — but still share the store schema.

Campaign resilience (docs/DESIGN.md §17) is layered on top: workers run
under a :class:`~repro.resilience.watchdog.SupervisedPool` so a point that
hangs past ``deadline_s`` is killed and its worker replaced; failed points
retry with exponential backoff and are quarantined after ``retries + 1``
attempts; every lifecycle event lands fsync'd in the campaign journal
(``sweep_journal.jsonl`` beside the store) so ``--resume`` can skip every
point whose record already landed — across any number of crashes.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
import traceback
from typing import Any, Callable, Mapping

from repro.resilience import faults
from repro.resilience.journal import CampaignJournal, journal_path_for
from repro.resilience.watchdog import SupervisedPool, holds_accelerator
from repro.session.workspace import (LEGACY_SWEEP_CACHE, LEGACY_SWEEP_STORE,
                                     resolve_sweep_cache,
                                     resolve_sweep_store)
from repro.sweep.spec import SweepPoint, SweepSpec

# legacy constants (pre-workspace callers import them); the engine itself
# resolves through repro.session.workspace so REPRO_WORKSPACE governs it
DEFAULT_STORE = LEGACY_SWEEP_STORE
DEFAULT_CACHE_DIR = LEGACY_SWEEP_CACHE


@dataclasses.dataclass
class PointResult:
    """Outcome of one point: a stored record id, or a failure."""

    point: SweepPoint
    run_id: str | None = None
    error: str | None = None
    cached: bool = False
    wall_s: float = 0.0             # total measured step time (0 = analytical)
    attempts: int = 1
    quarantined: bool = False       # exhausted its attempts this campaign
    resumed: bool = False           # skipped: record landed in a prior run

    @property
    def ok(self) -> bool:
        return self.error is None


@dataclasses.dataclass
class SweepResult:
    results: list[PointResult]
    skipped: list[tuple[SweepPoint, str]]

    @property
    def n_ok(self) -> int:
        return sum(1 for r in self.results if r.ok)

    @property
    def n_failed(self) -> int:
        return sum(1 for r in self.results if not r.ok)

    @property
    def n_cached(self) -> int:
        return sum(1 for r in self.results if r.cached)

    @property
    def n_quarantined(self) -> int:
        return sum(1 for r in self.results if r.quarantined)

    @property
    def n_resumed(self) -> int:
        return sum(1 for r in self.results if r.resumed)

    def failure_summary(self) -> list[str]:
        """One line per failed point: label, attempts, last error line —
        the operator-facing digest (full tracebacks stay in the journal)."""
        out = []
        for r in self.results:
            if r.ok:
                continue
            lines = [ln for ln in (r.error or "").splitlines() if ln.strip()]
            last = lines[-1].strip() if lines else "unknown error"
            tag = "quarantined" if r.quarantined else "failed"
            out.append(f"{r.point.label}: {tag} after {r.attempts} "
                       f"attempt(s) — {last}")
        return out


# --------------------------------------------------------------------------
# One point, in-process (lazy jax import — workers set XLA_FLAGS first)
# --------------------------------------------------------------------------

def _point_run(point: SweepPoint):
    from repro.configs.base import RunConfig
    return RunConfig(amp=point.amp, fusion=point.fusion)


def _build_point(point: SweepPoint):
    """(model, run, phases, shardings | None, mesh | None) for one point."""
    import jax

    from repro.configs.registry import get_config, get_smoke
    from repro.models import api as M
    from repro.trace.cli import build_phase_args

    cfg = get_smoke(point.config) if point.smoke else get_config(point.config)
    run = _point_run(point)
    model = M.build(cfg)
    phases = build_phase_args(model, run, seq=point.seq, batch=point.batch,
                              concrete=point.measured)
    if point.n_devices == 1:
        return model, run, phases, None, None

    if jax.device_count() < point.n_devices:
        raise RuntimeError(
            f"{point.label}: needs {point.n_devices} devices but this "
            f"process has {jax.device_count()} — run through the sweep "
            "engine's worker pool (it sets the XLA host-device count), "
            "not inline")
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_mesh
    mesh = make_mesh(point.mesh, ("data", "model"))
    pshard = shd.param_shardings(model.spec, mesh, run)
    shardings: dict[str, tuple] = {}
    for name, (_fn, args) in phases.items():
        if name == "opt":
            params, grads, opt_state = args
            oshard = shd.opt_state_shardings(opt_state, pshard, mesh)
            shardings[name] = (pshard, pshard, oshard)
        else:
            _params, batch = args
            shardings[name] = (pshard,
                               shd.shard_batch_dim(batch, mesh, run))
    return model, run, phases, shardings, mesh


def _matmul_class(run) -> str | None:
    """AMP policy → assumed dot/conv ceiling class (docs/DESIGN.md §9)."""
    import jax.numpy as jnp
    return "bf16" if run.compute_dtype == jnp.bfloat16 else None


def _resolve_machine(name: str):
    """The machine model every bound in this point is computed against:
    the registry spec with stored empirical interconnect ceilings folded
    in when ``repro.net characterize`` has run for this machine key
    (docs/DESIGN.md §18) — datasheet interconnect otherwise."""
    from repro.net.characterize import machine_with_net
    return machine_with_net(name)


def _analytical_payload(res, machine) -> dict[str, Any]:
    """Phase payload for a bound-only point: a zero-wall measurement, so
    the schema (and serializer) is exactly ``trace.store.phase_payload``
    — measured fields come out zero, the envelope/bound fields are the
    record's substance."""
    from repro.trace.collector import measurement_from_profile
    from repro.trace.store import phase_payload
    res.wall_s = 0.0
    m = measurement_from_profile(res, machine)
    # zero wall attributes zero time everywhere: rank by bound instead so
    # the persisted top-kernel slice is the analytically hottest ones
    m.kernels.sort(key=lambda k: -k.bound_s)
    return phase_payload(m)


def _cache_path(cache_dir: str, point: SweepPoint) -> str:
    import jax

    from repro.configs.registry import get_config, get_smoke
    from repro.core.machine import MACHINES
    # an analytical payload is a pure function of the point spec, the
    # resolved config constants, the machine-model constants, and the jax
    # version (lowering changes move the bounds) — hash all four so an
    # edited config or MachineSpec invalidates instead of serving stale
    # bounds
    cfg = get_smoke(point.config) if point.smoke else get_config(point.config)
    machine = MACHINES.get(point.machine)
    if machine is not None:
        # hash the *resolved* model (empirical net ceilings folded in):
        # a fresh `repro net characterize` moves the collective bounds,
        # so it must invalidate analytical payloads too
        machine = _resolve_machine(point.machine)
    env = json.dumps({
        "config": dataclasses.asdict(cfg),
        "machine": dataclasses.asdict(machine) if machine else point.machine,
        "jax": jax.__version__,
    }, sort_keys=True, default=str)
    tag = f"{point.key}-{hashlib.sha256(env.encode()).hexdigest()[:16]}"
    return os.path.join(cache_dir, f"{tag}.json")


def _cache_load(cache_dir: str | None,
                point: SweepPoint) -> dict[str, Any] | None:
    if not cache_dir:
        return None
    path = _cache_path(cache_dir, point)
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def _cache_save(cache_dir: str | None, point: SweepPoint,
                phases: Mapping[str, Any]) -> None:
    if not cache_dir:
        return
    os.makedirs(cache_dir, exist_ok=True)
    path = _cache_path(cache_dir, point)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(dict(phases), f)
    os.replace(tmp, path)


def run_point(point: SweepPoint, *, iters: int = 3, warmup: int = 1,
              cache_dir: str | None = None,
              sweep_name: str | None = None):
    """Execute one sweep point in this process → ``(TraceRecord, cached)``.

    Measured points compile once, analyze + execute that executable
    (``repro.trace.collector``); analytical points only lower/compile/
    analyze — and skip even that on a cache hit.
    """
    from repro.trace.store import record_from_payloads, record_from_phases

    mesh_dict = {"data": point.mesh[0], "model": point.mesh[1]}
    meta = {"sweep_point": point.key, "sweep": sweep_name or "adhoc",
            "label": point.label, **point.to_dict()}
    # interconnect-ceiling provenance: which measured roofs (if any) the
    # collective bounds in this record were computed against
    from repro.net.characterize import net_ceilings
    nc = net_ceilings(point.machine)
    if nc:
        meta["net_ceilings"] = nc
    if point.measured:
        # which kernel configs this measurement will run with (tuned
        # winners vs hardcoded defaults) — the report side flags points
        # measured with defaults after a tuned winner exists
        from repro.tune import active_dispatch_table, active_kernel_configs
        meta["kernel_configs"] = active_kernel_configs()
        meta["dispatch_table"] = active_dispatch_table(machine=point.machine)

    if not point.measured:
        cached = _cache_load(cache_dir, point)
        if cached is not None:
            return record_from_payloads(
                point.config, cached, machine=point.machine, mesh=mesh_dict,
                meta={**meta, "cached": True}), True

        from repro.core.profiler import profile_fn
        model, run, phases, shardings, mesh = _build_point(point)
        machine = _resolve_machine(point.machine)
        payloads = {}
        for name, (fn, args) in phases.items():
            res = profile_fn(
                fn, args=args, name=name, machine=machine, mesh=mesh,
                in_shardings=shardings[name] if shardings else None,
                matmul_class=_matmul_class(run))
            payloads[name] = _analytical_payload(res, machine)
        _cache_save(cache_dir, point, payloads)
        return record_from_payloads(
            point.config, payloads, machine=point.machine, mesh=mesh_dict,
            meta={**meta, "cached": False}), False

    # measured: compile once, analyze + execute the same executable
    import jax

    from repro.trace.collector import collect_phase
    model, run, phases, shardings, mesh = _build_point(point)
    ms = {}
    for name, (fn, args) in phases.items():
        in_sh = shardings[name] if shardings else None
        concrete = args
        if mesh is not None:
            concrete = jax.device_put(args, in_sh)
        ms[name] = collect_phase(
            name, fn, args, machine=_resolve_machine(point.machine),
            iters=iters,
            warmup=warmup, concrete_args=concrete, mesh=mesh,
            in_shardings=in_sh, matmul_class=_matmul_class(run))
    return record_from_phases(
        point.config, ms, machine=point.machine, mesh=mesh_dict,
        meta=meta), False


# --------------------------------------------------------------------------
# Worker pool (one pool per device-count group)
# --------------------------------------------------------------------------

def _worker_init(n_devices: int) -> None:
    """Runs in the spawned worker before any job — and before the lazy jax
    import — so the forced host-platform device count can still take."""
    if n_devices > 1:
        # appended, not prepended: XLA flag parsing is last-occurrence-wins,
        # so this must beat any forced count inherited from the environment
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_devices}")


def _point_job(point_dict: dict, iters: int, warmup: int,
               cache_dir: str | None, sweep_name: str | None,
               index: int = 0, attempt: int = 0,
               in_worker: bool = False) -> dict:
    """Worker entry: run one point, return a picklable outcome.

    ``index`` is the point's campaign ordinal and ``attempt`` its retry
    count — the fault-injection site identity (``crash_point:INDEX``,
    ``hang_point:INDEX:SECS``), passed explicitly because counters do not
    survive the process boundary.  ``in_worker=False`` (the inline path)
    skips crash/hang injection: an inline hang cannot be killed and an
    inline ``os._exit`` would take the campaign driver down with it.
    """
    point = SweepPoint.from_dict(point_dict)
    if in_worker:
        plan = faults.active_plan()
        plan.maybe_crash("crash_point", target=index, attempt=attempt)
        plan.maybe_hang("hang_point", target=index, attempt=attempt)
    try:
        rec, cached = run_point(point, iters=iters, warmup=warmup,
                                cache_dir=cache_dir, sweep_name=sweep_name)
    except Exception:
        return {"point": point_dict, "error": traceback.format_exc()}
    return {"point": point_dict, "record": json.loads(rec.to_json()),
            "cached": cached}


def _append_outcome(store, point: SweepPoint, outcome: dict) -> PointResult:
    from repro.trace.store import TraceRecord
    if outcome.get("error"):
        return PointResult(point, error=outcome["error"])
    rec = TraceRecord.from_dict(outcome["record"])
    store.append(rec)
    wall = sum(float(p.get("wall_s", 0.0)) for p in rec.phases.values())
    return PointResult(point, run_id=rec.run_id,
                       cached=bool(outcome.get("cached")), wall_s=wall)


def _resume_run_ids(store, journal: CampaignJournal | None,
                    sweep_name: str) -> dict[str, str]:
    """Point key → run_id for every point already completed in a prior
    invocation of this campaign.  Union of the journal's ``done`` events
    and a store scan (covers the crash window between the store append
    and the journal ``done`` line — the store is the source of truth)."""
    done: dict[str, str] = {}
    if journal is not None:
        done.update(journal.replay(sweep_name).done)
    try:
        for rec in store.records_where(
                lambda r: r.meta.get("sweep") == sweep_name):
            key = rec.meta.get("sweep_point")
            if key:
                done[str(key)] = rec.run_id
    except OSError:
        pass
    return done


def run_sweep(sweep: SweepSpec, *, store_path: str | None = None,
              workers: int | None = None,
              cache_dir: "str | None | type(Ellipsis)" = ...,
              progress: Callable[[str], None] | None = None,
              deadline_s: float | None = None,
              retries: int = 1,
              backoff_s: float = 0.25,
              resume: bool = False,
              journal_path: "str | None | type(Ellipsis)" = ...,
              ) -> SweepResult:
    """Run a whole campaign: expand, execute, persist one record per point.

    ``store_path=None`` resolves through the workspace rules
    (``$REPRO_WORKSPACE/sweep.jsonl``, else the legacy default); the
    ``cache_dir`` default resolves the same way (``None`` means *no*
    cache, so the sentinel is ``...``) — one root for both.

    ``workers``: pool size; ``0`` runs every point inline in this process
    (single-device points only — useful under pytest and for debugging).
    A process on an accelerator always runs inline: it holds the chip.
    ``None`` picks ``min(4, cpu_count)`` for analytical sweeps but ``1``
    for measured ones: concurrent wall-clock samples contend for the same
    CPUs and skew each other, so parallel measurement is opt-in.

    Resilience knobs: ``deadline_s`` kills (and replaces) a worker whose
    point runs longer — mind that a worker's *first* point pays the jax
    import, so deadlines under ~30 s are asking for false kills;
    ``retries`` bounds extra attempts per point (backoff doubles from
    ``backoff_s`` each round) before the point is **quarantined**;
    ``resume=True`` skips points whose record already landed (journal ∪
    store scan, keyed by the point content hash — zero duplicates);
    ``journal_path`` defaults to ``sweep_journal.jsonl`` beside the store
    (``None`` disables journalling, and with it ``--resume``'s journal
    half).
    """
    from repro.trace.store import TraceStore

    store_path = resolve_sweep_store(store_path)
    if cache_dir is ...:
        cache_dir = resolve_sweep_cache(None)
    if journal_path is ...:
        journal_path = journal_path_for(store_path)
    journal = CampaignJournal(journal_path) if journal_path else None
    say = progress or (lambda s: None)
    points, skipped = sweep.expand()
    for p, reason in skipped:
        say(f"[skip] {p.label}: {reason}")
    store = TraceStore(store_path)
    results: list[PointResult] = []

    if workers is None:
        workers = 1 if sweep.measure else min(4, os.cpu_count() or 1)
    if workers and holds_accelerator():
        # the chip is this process's: a worker could never reach it
        say("[sweep] this process holds the accelerator; running every "
            "point in it")
        workers = 0

    done_ids = (_resume_run_ids(store, journal, sweep.name)
                if resume else {})
    todo: list[tuple[int, SweepPoint]] = []
    for i, point in enumerate(points):
        run_id = done_ids.get(point.key)
        if run_id is not None:
            res = PointResult(point, run_id=run_id or None, resumed=True,
                              attempts=0)
            results.append(res)
            say(_ok_line(res))
        else:
            todo.append((i, point))

    attempts: dict[str, int] = {p.key: 0 for _, p in todo}
    errors: dict[str, str] = {}

    def record_attempt(point: SweepPoint) -> int:
        a = attempts[point.key]
        attempts[point.key] = a + 1
        if journal is not None:
            journal.log("attempt", sweep=sweep.name, point=point.key,
                        label=point.label, attempt=a)
        return a

    def settle(point: SweepPoint, outcome: dict) -> PointResult | None:
        """Store + journal one attempt's outcome.  Returns the final
        PointResult, or None if the point should be retried."""
        n = attempts[point.key]
        if not outcome.get("error"):
            res = _append_outcome(store, point, outcome)
            res.attempts = n
            if journal is not None:
                journal.log("done", sweep=sweep.name, point=point.key,
                            label=point.label, attempt=n - 1,
                            run_id=res.run_id)
            return res
        err = outcome["error"]
        errors[point.key] = err
        reason = err.strip().splitlines()[-1] if err.strip() else "unknown"
        if journal is not None:
            journal.log("fail", sweep=sweep.name, point=point.key,
                        label=point.label, attempt=n - 1, reason=reason)
        if n <= retries:
            return None                               # retry next round
        if journal is not None:
            journal.log("quarantine", sweep=sweep.name, point=point.key,
                        label=point.label, attempt=n - 1, reason=reason)
        return PointResult(point, error=err, attempts=n, quarantined=True)

    opts = (sweep.iters, sweep.warmup, cache_dir, sweep.name)

    if workers == 0:
        for i, point in todo:
            while True:
                a = record_attempt(point)
                outcome = _point_job(point.to_dict(), *opts,
                                     index=i, attempt=a, in_worker=False)
                res = settle(point, outcome)
                if res is not None:
                    break
                time.sleep(backoff_s * (2 ** a))
            results.append(res)
            say(_ok_line(res) if res.ok else f"[FAIL] {point.label}")
    else:
        by_dev: dict[int, list[tuple[int, SweepPoint]]] = {}
        for i, point in todo:
            by_dev.setdefault(point.n_devices, []).append((i, point))
        for n_devices, group in sorted(by_dev.items()):
            n_workers = min(workers, len(group))
            label_of = {p.key: p.label for _, p in group}
            with SupervisedPool(_point_job, n_workers,
                                init=_worker_init, initargs=(n_devices,),
                                deadline_s=deadline_s) as pool:
                pending = list(group)
                rnd = 0
                while pending:
                    tasks = []
                    for i, point in pending:
                        a = record_attempt(point)
                        tasks.append((point.key,
                                      (point.to_dict(), *opts, i, a, True)))
                    outcomes = pool.run(
                        tasks,
                        on_event=lambda kind, key: say(
                            f"[watchdog] {label_of[key]}: {kind}"))
                    retry = []
                    for i, point in pending:
                        out = outcomes[point.key]
                        if out.kind == "ok" and out.error is None:
                            outcome = out.value or {"error": "empty worker "
                                                             "reply"}
                        else:
                            outcome = {"error": out.error or out.kind}
                        res = settle(point, outcome)
                        if res is None:
                            retry.append((i, point))
                            continue
                        results.append(res)
                        say(_ok_line(res) if res.ok
                            else f"[FAIL] {point.label}")
                    if retry:
                        time.sleep(backoff_s * (2 ** min(rnd, 6)))
                    pending = retry
                    rnd += 1
    # keep campaign order (configs outermost), not completion order
    order = {p.key: i for i, p in enumerate(points)}
    results.sort(key=lambda r: order[r.point.key])
    return SweepResult(results, skipped)


def _ok_line(res: PointResult) -> str:
    if res.resumed:
        return f"[ok] {res.point.label} -> run {res.run_id} (resumed)"
    tag = " (cached)" if res.cached else ""
    wall = (f" wall {res.wall_s*1e3:.3f} ms" if res.wall_s else " bound-only")
    return f"[ok] {res.point.label} -> run {res.run_id}{wall}{tag}"
