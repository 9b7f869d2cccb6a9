"""Session: the paper's workflow as one object.

The hierarchical-roofline methodology is a pipeline — characterize the
machine (ERT, §II-A), characterize the application (compiled-HLO walk,
§II-B), fold measured wall time in (the time-based companion paper),
then compare runs over time.  Before this class, each step lived behind
a different entry point with its own store.  A :class:`Session` binds
them: one machine model, one :class:`~repro.session.workspace.Workspace`
(one root for all three stores), and the workflow as first-class methods

    characterize → profile → record → report → sweep / tune → compare
                → trend / advise / merge          (repro.obs, fleet view)

every one returning a :class:`~repro.session.result.RooflineResult`.
Callers never touch ``compile_fn`` / ``profile_fn`` / store classes
directly; ``python -m repro`` is this class as a CLI.

Importing this module is cheap and jax-free; constructing a Session
resolves the machine model (which loads ``repro.core``), and the heavy
subsystems (jax compilation, the model registry, the engines) load
inside the methods that need them.
"""

from __future__ import annotations

from typing import Any, Callable, Mapping, Sequence, TYPE_CHECKING

from repro.session.result import (RooflineResult, payload_from_profile,
                                  phases_from_record, provenance)
from repro.session.workspace import Workspace

if TYPE_CHECKING:                                    # pragma: no cover
    from repro.core.machine import MachineSpec

#: phases of one training step, in execution order (the paper's split)
TRAIN_PHASES = ("fwd", "bwd", "opt")


def _matmul_class(run: Any) -> str | None:
    """dot/conv ceiling class for an AMP policy (docs/DESIGN.md §9)."""
    import jax.numpy as jnp
    return "bf16" if run.compute_dtype == jnp.bfloat16 else None


class Session:
    """One analysis session: a machine model + a workspace + the workflow.

    ``machine`` is a :class:`MachineSpec`, a registry name
    (``cpu-host``, ``tpu-v5e``, ...) or ``None`` for the model of the
    device this process computes on; ``workspace`` is a
    :class:`Workspace`, a root path, or ``None`` for the default root
    (``REPRO_WORKSPACE`` > ``./.repro-workspace`` in a checkout >
    ``~/.repro``).
    """

    def __init__(self, machine: "MachineSpec | str | None" = None,
                 workspace: Workspace | str | None = None):
        from repro.core.machine import (MachineSpec, get_machine,
                                        machine_for_device)
        if machine is None:
            machine = machine_for_device()
        self.machine = (machine if isinstance(machine, MachineSpec)
                        else get_machine(machine))
        self.workspace = (workspace if isinstance(workspace, Workspace)
                          else Workspace(workspace))

    def __repr__(self) -> str:
        return (f"Session(machine={self.machine.name!r}, "
                f"workspace={self.workspace.root!r})")

    def _provenance(self, **extra: Any) -> dict[str, Any]:
        return provenance(self.workspace, machine=self.machine.name, **extra)

    # -- 1. machine characterization (paper §II-A) -----------------------
    def characterize(self, empirical: bool = False, tuned: bool = True,
                     smoke: bool = False) -> RooflineResult:
        """Machine model: datasheet, or measured ERT ceilings of this host.

        ``empirical=True`` runs the ERT micro-kernel suite against this
        host (``tuned=True`` = best-of-tuned winners through the
        *workspace's* tune store, the honest mode; searches persist, so a
        second characterization is a pure store hit).  Either way the
        resulting machine becomes the session's, every later bound is
        against it, and the workspace header records it.
        """
        if empirical:
            from repro.core.machine import empirical_cpu_spec
            self.machine = empirical_cpu_spec(
                tuned=tuned, store=self.workspace.tune_store if tuned
                else None, smoke=smoke)
        self.workspace.write_header(self.machine.name)
        from repro.core.report import machine_table
        return RooflineResult(
            kind="characterize", name=self.machine.name,
            machine=self.machine,
            provenance=self._provenance(
                empirical=empirical,
                tune_store=self.workspace.tune_path if empirical and tuned
                else None),
            text=machine_table(self.machine))

    # -- 2. application characterization (paper §II-B) -------------------
    def profile(self, target: str | Callable, args: Sequence[Any] = (),
                *, name: str | None = None,
                phases: Sequence[str] = TRAIN_PHASES,
                seq: int = 32, batch: int = 4, amp: str = "O1",
                fusion: str = "off", smoke: bool = True,
                measure: bool = False, iters: int = 5, warmup: int = 2,
                **profile_kw: Any) -> RooflineResult:
        """Analytical HLO walk of a registry config — or of *your* jax
        function (pass a callable + ``args``; ShapeDtypeStructs fine).

        ``measure=True`` additionally executes the same compiled
        executables and attributes wall time (``repro.trace``); the
        result then carries achieved/%-of-roofline per phase and is
        ready for :meth:`record`-style rendering, but nothing is stored
        — :meth:`record` is the persisting variant.
        """
        from repro.core.profiler import profile_fn

        if callable(target):
            label = name or getattr(target, "__name__", "fn")
            phase_args: Mapping[str, tuple] = {label: (target, tuple(args))}
            mm = profile_kw.pop("matmul_class", None)
        else:
            label = name or target
            phase_args, run = self._build_phases(
                target, seq=seq, batch=batch, amp=amp, fusion=fusion,
                smoke=smoke, concrete=measure)
            phase_args = {ph: pa for ph, pa in phase_args.items()
                          if ph in phases}
            mm = _matmul_class(run)

        results = {ph: profile_fn(fn, args=a, name=ph, machine=self.machine,
                                  measure=measure, measure_iters=iters,
                                  measure_warmup=warmup, matmul_class=mm,
                                  **profile_kw)
                   for ph, (fn, a) in phase_args.items()}
        if measure:
            from repro.trace.collector import measurement_from_profile
            from repro.trace.store import phase_payload
            payloads = {ph: phase_payload(
                measurement_from_profile(res, self.machine))
                for ph, res in results.items()}
        else:
            payloads = {ph: payload_from_profile(res)
                        for ph, res in results.items()}
        return RooflineResult(
            kind="profile", name=label, machine=self.machine,
            provenance=self._provenance(measured=measure),
            phases=payloads,
            analyses={ph: res.analysis for ph, res in results.items()},
            data=results)

    # -- 3. measured trace into the store (time-based roofline) ----------
    def record(self, config: str, *, seq: int = 32, batch: int = 4,
               amp: str = "O1", fusion: str = "off", smoke: bool = True,
               iters: int = 5, warmup: int = 2, scale_wall: float = 1.0,
               meta: Mapping[str, Any] | None = None) -> RooflineResult:
        """Measure one config's train phases and append a provenance-
        stamped record to the workspace trace store.  ``scale_wall``
        multiplies measured wall times before storing (regression
        drills — the trend gate's acceptance test)."""
        from repro.trace.collector import collect_phases
        from repro.trace.store import record_from_phases

        from repro.tune import active_kernel_configs

        phase_args, run = self._build_phases(
            config, seq=seq, batch=batch, amp=amp, fusion=fusion,
            smoke=smoke, concrete=True)
        # bounds against the net-augmented machine: measured interconnect
        # ceilings (when `net characterize` stored them for this machine
        # key) replace the datasheet roofs in every collective bound
        from repro.net.characterize import machine_with_net, net_ceilings
        machine = machine_with_net(self.machine, self.workspace.tune_store)
        nc = net_ceilings(self.machine.name, self.workspace.tune_store)
        ms = collect_phases(phase_args, machine=machine, iters=iters,
                            warmup=warmup, matmul_class=_matmul_class(run))
        if scale_wall != 1.0:
            from repro.trace.cli import scale_measurement
            ms = {k: scale_measurement(m, scale_wall)
                  for k, m in ms.items()}
        # what the tune store offered at measurement time — the advisor's
        # tune-mismatch rule diffs this stamp against the store later
        kcfg = active_kernel_configs(machine=self.machine.name,
                                     store=self.workspace.tune_store)
        from repro.tune import active_dispatch_table
        dtab = active_dispatch_table(machine=self.machine.name,
                                     store=self.workspace.tune_store)
        rec = record_from_phases(
            config, ms, machine=self.machine.name,
            meta={"smoke": smoke, "seq": seq, "batch": batch, "amp": amp,
                  "fusion": fusion, "scale_wall": scale_wall,
                  "kernel_configs": kcfg, "dispatch_table": dtab,
                  **({"net_ceilings": nc} if nc else {}),
                  **dict(meta or {})})
        self.workspace.trace_store.append(rec)
        self.workspace.write_header(self.machine.name)
        from repro.trace.timeline import ascii_timeline, build_timeline
        return RooflineResult(
            kind="record", name=config, machine=machine,
            provenance=self._provenance(run_id=rec.run_id,
                                        store=self.workspace.trace_path),
            phases=phases_from_record(rec),
            text=ascii_timeline(build_timeline(ms)),
            data=rec)

    # -- 3b. serving under load (continuous batching, repro.serve) -------
    def serve(self, config: str, *, n_requests: int = 16,
              trace: str = "poisson", rate: float = 1.0, burst: int = 4,
              seed: int = 0, n_slots: int = 4, max_len: int = 64,
              prefill_chunk: int = 16, page_size: int = 16,
              prompt_len: tuple[int, int] = (4, 16),
              max_new: tuple[int, int] = (4, 16),
              amp: str = "O1", fusion: str = "off", smoke: bool = True,
              max_ticks: int = 4096,
              meta: Mapping[str, Any] | None = None) -> RooflineResult:
        """Serve a seeded synthetic arrival trace through the continuous-
        batching engine and record prefill/decode as *separate* phase
        payloads in the trace store (config key ``serve/<name>``).

        The compiled executables the engine drove under the wall clock
        are re-analyzed (never re-jitted) and their envelopes scaled by
        call counts, so the stored record answers the paper's question
        per serving phase: decode is bandwidth-dominated at small batch,
        chunked prefill sits far closer to the compute ceiling.
        ``exit_code`` is 1 when the latency gate fails (a wedged
        scheduler, an admitted request that never finished).  ``data``
        is ``(record, stats, engine)``; the engine still holds the
        weights and its compiled executables.
        """
        import jax

        from repro.configs.base import RunConfig
        from repro.configs.registry import get_config, get_smoke
        from repro.models import api as M
        from repro.models.params import init
        from repro.serve.engine import Engine
        from repro.serve.trace import serve_record
        from repro.serve.workload import make_trace
        from repro.tune import active_kernel_configs

        cfg = get_smoke(config) if smoke else get_config(config)
        run = RunConfig(amp=amp, fusion=fusion)
        params = init(jax.random.PRNGKey(seed), M.build(cfg).spec,
                      run.param_dtype)
        engine = Engine(cfg, run, params, n_slots=n_slots, max_len=max_len,
                        page_size=page_size, prefill_chunk=prefill_chunk)
        pl = (min(prompt_len[0], max_len), min(prompt_len[1], max_len))
        kw = {"burst": burst} if trace == "bursty" else {}
        reqs = make_trace(trace, n_requests, rate=rate, seed=seed,
                          vocab=cfg.vocab_size, prompt_len=pl,
                          max_new=max_new, **kw)
        stats = engine.run_trace(reqs, max_ticks=max_ticks)
        kcfg = active_kernel_configs(machine=self.machine.name,
                                     store=self.workspace.tune_store)
        from repro.tune import active_dispatch_table
        dtab = active_dispatch_table(machine=self.machine.name,
                                     store=self.workspace.tune_store)
        rec = serve_record(
            config, engine, stats, self.machine,
            matmul_class=_matmul_class(run),
            meta={"smoke": smoke, "amp": amp, "fusion": fusion,
                  "trace": trace, "n_requests": n_requests,
                  "n_slots": n_slots, "max_len": max_len,
                  "prefill_chunk": engine.chunk, "page_size": page_size,
                  "seed": seed, "kernel_configs": kcfg,
                  "dispatch_table": dtab, **dict(meta or {})})
        self.workspace.trace_store.append(rec)
        self.workspace.write_header(self.machine.name)
        problems = stats.gate()
        text = stats.render()
        if problems:
            text += "\n" + "\n".join(f"GATE: {p}" for p in problems)
        return RooflineResult(
            kind="record", name=f"serve/{config}", machine=self.machine,
            provenance=self._provenance(run_id=rec.run_id,
                                        store=self.workspace.trace_path),
            phases=phases_from_record(rec),
            text=text, data=(rec, stats, engine),
            exit_code=1 if problems else 0)

    # -- 4. read back without re-running ---------------------------------
    def report(self, config: str | None = None) -> RooflineResult:
        """Newest stored record for ``config`` (or the newest record of
        any config) from the workspace trace store."""
        store = self.workspace.trace_store
        recs = store.last(config, n=1)
        if not recs:
            which = f"config {config!r}" if config else "any config"
            raise LookupError(
                f"no records for {which} in {self.workspace.trace_path} — "
                "run Session.record() (or `python -m repro record`) first")
        rec = recs[0]
        from repro.core.machine import get_machine
        machine = (self.machine if rec.machine == self.machine.name
                   else get_machine(rec.machine))
        from repro.trace.timeline import ascii_timeline, timeline_from_record
        return RooflineResult(
            kind="report", name=rec.config, machine=machine,
            provenance=self._provenance(run_id=rec.run_id,
                                        git_sha=rec.git_sha,
                                        store=self.workspace.trace_path),
            phases=phases_from_record(rec),
            text=ascii_timeline(timeline_from_record(rec)),
            data=rec)

    # -- 5. cross-config campaigns ---------------------------------------
    def sweep(self, spec: Any = None, *, smoke: bool = False,
              workers: int | None = None,
              progress: Callable[[str], None] | None = None,
              resume: bool = False, deadline_s: float | None = None,
              retries: int = 1,
              **axes: Any) -> RooflineResult:
        """Run a campaign into the workspace sweep store and summarize.

        Pass a ready :class:`~repro.sweep.spec.SweepSpec`, ``smoke=True``
        for the CI preset, or axes as keywords
        (``configs=("minitron-4b",), seqs=(16,), amps=("O0", "O1")``...).
        ``resume``/``deadline_s``/``retries`` forward to
        :func:`repro.sweep.engine.run_sweep` (campaign resilience knobs;
        the journal lives beside the workspace sweep store).
        """
        from repro.sweep.aggregate import (latest_per_point, render_summary,
                                           sweep_records)
        from repro.sweep.engine import run_sweep
        from repro.sweep.spec import SweepSpec, normalize_axes, smoke_spec

        if spec is None:
            if smoke:
                # the preset hardcodes cpu-host; the session's machine is
                # what the result and workspace header will claim, so the
                # stored records must be bounded against the same model
                import dataclasses
                spec = dataclasses.replace(smoke_spec(),
                                           machine=self.machine.name)
            else:
                # mesh_shapes is the mesh-scale alias for meshes (repro.net)
                spec = SweepSpec(machine=self.machine.name,
                                 **normalize_axes(dict(axes)))
        elif axes:
            raise TypeError(f"pass axes ({sorted(axes)}) or a spec, "
                            "not both")
        result = run_sweep(spec, store_path=self.workspace.sweep_path,
                           cache_dir=self.workspace.sweep_cache_dir,
                           workers=workers, progress=progress,
                           resume=resume, deadline_s=deadline_s,
                           retries=retries)
        self.workspace.write_header(self.machine.name)
        recs = latest_per_point(sweep_records(self.workspace.sweep_store,
                                              spec.name))
        return RooflineResult(
            kind="sweep", name=spec.name, machine=self.machine,
            provenance=self._provenance(store=self.workspace.sweep_path,
                                        n_ok=result.n_ok,
                                        n_failed=result.n_failed),
            text=render_summary(recs) if recs else "(no points stored)",
            data=result,
            exit_code=1 if result.n_failed else 0)

    # -- 6. kernel autotuning (feeds the empirical ceilings) -------------
    def tune(self, kernels: Sequence[str] | None = None, *,
             backend: str = "pallas", smoke: bool = False,
             ceilings: bool = False, force: bool = False,
             iters: int = 3, warmup: int = 1, dispatch: bool = False,
             config: str = "minitron-4b", seq: int = 16, batch: int = 2,
             amp: str = "O1", full: bool = False) -> RooflineResult:
        """Search kernel configs into the workspace tune store (a point
        already stored is a pure hit — no re-timing).

        ``dispatch=True`` instead populates the site-keyed
        fused-vs-reference dispatch table (docs/DESIGN.md §16): trace
        ``config``'s train phases under ``fusion="auto"`` and measure
        every dispatch site encountered — a second call over the same
        workspace is a 100% store hit (zero re-timings).  The smoke
        variant of ``config`` is traced unless ``full=True`` (the CLI's
        ``--full``); the kernel-autotuner path keeps its own ``smoke``
        flag (tiny shapes + spaces) with the opposite default.
        """
        if dispatch:
            from repro.tune.dispatch import search_sites
            store = self.workspace.tune_store
            outcome = search_sites(
                config, seq=seq, batch=batch, amp=amp,
                machine=self.machine.name, store=store, iters=iters,
                warmup=warmup, smoke=not full, force=force)
            self.workspace.write_header(self.machine.name)
            return RooflineResult(
                kind="tune", name=f"dispatch/{config}",
                machine=self.machine,
                provenance=self._provenance(
                    store=self.workspace.tune_path,
                    n_sites=outcome.n_sites,
                    n_measured=outcome.n_measured),
                text=outcome.describe(),
                data=outcome)
        from repro.tune import search, tune_ceilings
        from repro.tune import space as sp

        known = sp.XLA_KERNELS if backend == "xla" else sp.PALLAS_KERNELS
        kernels = list(kernels) if kernels else list(known)
        bad = sorted(set(kernels) - set(known))
        if bad:
            raise KeyError(f"no {backend} search space for {bad}; "
                           f"valid: {sorted(known)}")
        store = self.workspace.tune_store
        outcomes = {k: search(k, machine=self.machine.name, backend=backend,
                              store=store, iters=iters, warmup=warmup,
                              smoke=smoke, force=force)
                    for k in kernels}
        if ceilings or smoke:
            outcomes.update(tune_ceilings(
                machine=self.machine.name, store=store, iters=iters,
                warmup=warmup, smoke=smoke, force=force))
        self.workspace.write_header(self.machine.name)
        return RooflineResult(
            kind="tune", name=",".join(kernels), machine=self.machine,
            provenance=self._provenance(store=self.workspace.tune_path,
                                        n_winners=len(list(store.keys()))),
            text="\n".join(o.describe() for o in outcomes.values()),
            data=outcomes)

    # -- 7. regression comparison across runs ----------------------------
    def compare(self, config: str | None = None, *, base: str | None = None,
                new: str | None = None, threshold: float = 0.10,
                window: int = 2) -> RooflineResult:
        """Diff stored runs (newest-vs-previous per config, or two
        explicit run ids); ``exit_code`` is 1 when any cell regressed."""
        from repro.trace.compare import (compare_last, compare_records,
                                         format_deltas, has_regressions)
        store = self.workspace.trace_store
        if base or new:
            if not (base and new):
                raise ValueError("base and new run ids go together")
            b, n = store.run(base), store.run(new)
            if b is None or n is None:
                raise LookupError(
                    f"run id not found in {self.workspace.trace_path}")
            deltas = compare_records(b, n, threshold)
        else:
            deltas = compare_last(store, config, threshold, window=window)
        return RooflineResult(
            kind="compare", name=config or "all", machine=self.machine,
            provenance=self._provenance(store=self.workspace.trace_path,
                                        threshold=threshold),
            text=format_deltas(deltas),
            data=deltas,
            exit_code=1 if has_regressions(deltas) else 0)

    # -- 8. observability: trend / advise / merge (repro.obs) ------------
    def trend(self, config: str | None = None, *, gate: bool = False,
              tolerance: float | None = None,
              baseline: str | None = None,
              bench_dirs: Sequence[str] | None = None,
              max_rows: int = 40) -> RooflineResult:
        """Perf-trend series over the workspace's stored history (trace
        + sweep records + harvested ``BENCH_*.json``), sparkline report;
        ``gate=True`` sets ``exit_code`` 1 when any lower-is-better
        series regressed past the tolerance.  ``baseline`` pins the gate
        to a tagged known-good run (tag name or run id — see
        :meth:`trend_tag`) instead of the rolling median."""
        from repro.obs.trend import (DEFAULT_TOLERANCE, collect_series,
                                     gate_series, render_trend)
        series = collect_series(self.workspace, config,
                                bench_dirs=bench_dirs)
        baseline_run = (self.workspace.resolve_tag(baseline)
                        if baseline else None)
        regressions = gate_series(
            series, tolerance if tolerance is not None
            else DEFAULT_TOLERANCE,
            baseline_run=baseline_run) if gate else None
        return RooflineResult(
            kind="trend", name=config or "all", machine=self.machine,
            provenance=self._provenance(n_series=len(series),
                                        gated=gate,
                                        baseline=baseline_run),
            text=render_trend(series, regressions, max_rows=max_rows),
            data=(series, regressions or []),
            exit_code=1 if regressions else 0)

    def trend_tag(self, name: str, run_id: str | None = None
                  ) -> RooflineResult:
        """Pin a run id under a human tag in the workspace header so
        ``trend(gate=True, baseline=name)`` anchors to it.  ``run_id``
        defaults to the newest stored trace record; prefixes are
        resolved against the trace then sweep stores."""
        rec = None
        if run_id is None:
            recs = self.workspace.trace_store.last(n=1)
            if not recs:
                raise LookupError(
                    f"no records in {self.workspace.trace_path} to tag — "
                    "run `python -m repro record` first")
            rec = recs[0]
        else:
            rec = (self.workspace.trace_store.run(run_id)
                   or self.workspace.sweep_store.run(run_id))
            if rec is None:
                raise LookupError(
                    f"run {run_id!r} not found in the workspace trace or "
                    "sweep stores")
        self.workspace.tag_run(name, rec.run_id)
        return RooflineResult(
            kind="trend", name=f"tag/{name}", machine=self.machine,
            provenance=self._provenance(run_id=rec.run_id),
            text=f"tagged run {rec.run_id} ({rec.config}) as {name!r} — "
                 f"gate against it with `python -m repro trend --gate "
                 f"--baseline {name}`",
            data={"tag": name, "run_id": rec.run_id})

    def advise(self, config: str | None = None, *, top: int = 0
               ) -> RooflineResult:
        """Mine the stored records for known bottleneck patterns; ranked
        evidence-cited findings (``repro.obs.advisor``)."""
        from repro.obs.advisor import advise, render_findings
        findings = advise(self.workspace, config,
                          machine=self.machine.name)
        return RooflineResult(
            kind="advise", name=config or "all", machine=self.machine,
            provenance=self._provenance(n_findings=len(findings)),
            text=render_findings(findings, top=top),
            data=findings)

    def merge(self, remote_root: str) -> RooflineResult:
        """Union a remote workspace's stores into this one (run_id /
        tune-key / harvest-file dedupe, skip-and-report conflicts,
        provenance appended to ``workspace.json``)."""
        from repro.obs.merge import merge_workspace, render_merge
        reports = merge_workspace(self.workspace, remote_root)
        return RooflineResult(
            kind="merge", name=remote_root, machine=self.machine,
            provenance=self._provenance(
                added={r.store: r.n_added for r in reports}),
            text=render_merge(reports, self.workspace.root,
                              remote_root),
            data=reports)

    # -- 9. interconnect roofline level (repro.net) -----------------------
    def net_characterize(self, *, n_devices: int = 8,
                         sizes: Sequence[int] | None = None,
                         iters: int = 3, warmup: int = 1,
                         force: bool = False, smoke: bool = False,
                         deadline_s: float = 900.0,
                         inline: bool = False) -> RooflineResult:
        """Measure (or fetch) this host's collective ceilings into the
        workspace tune store and fold them into the session's machine —
        every later bound runs against the measured ICI/DCN roofs.  A
        second call under the same machine key is a pure store hit."""
        from repro.net.characterize import characterize_net, machine_with_net
        out = characterize_net(
            self.machine.name, n_devices=n_devices,
            sizes=tuple(sizes) if sizes else None, iters=iters,
            warmup=warmup, store=self.workspace.tune_store, force=force,
            smoke=smoke, deadline_s=deadline_s, inline=inline)
        self.machine = machine_with_net(self.machine,
                                        self.workspace.tune_store)
        self.workspace.write_header(self.machine.name)
        from repro.core.report import machine_table
        tag = ("store hit — nothing re-timed" if out["cached"] else
               f"measured over {out['n_devices']} forced host device(s)")
        return RooflineResult(
            kind="net", name=f"net/{self.machine.name}",
            machine=self.machine,
            provenance=self._provenance(store=self.workspace.tune_path,
                                        cached=out["cached"],
                                        n_devices=out["n_devices"]),
            text=f"net characterize: {tag}\n\n"
                 + machine_table(self.machine),
            data=out)

    def net_report(self, sweep: str | None = None,
                   config: str | None = None) -> RooflineResult:
        """Stored interconnect ceilings + the mesh-scale ranking over
        persisted sweep records: which points are network-bound, and
        the mesh shape where each config flips (store-only)."""
        from repro.net.report import net_rows, render_net_report
        from repro.sweep.aggregate import latest_per_point, sweep_records
        recs = latest_per_point(
            sweep_records(self.workspace.sweep_store, sweep))
        recs = {k: r for k, r in recs.items()
                if config is None or r.config == config}
        rows = net_rows(recs)
        return RooflineResult(
            kind="net", name=sweep or "all", machine=self.machine,
            provenance=self._provenance(store=self.workspace.sweep_path,
                                        n_points=len(rows)),
            text=render_net_report(recs, machine=self.machine.name,
                                   store=self.workspace.tune_store),
            data=rows,
            exit_code=0 if rows else 1)

    # -- shared phase construction (the one registry path) ---------------
    def _build_phases(self, config: str, *, seq: int, batch: int, amp: str,
                      fusion: str, smoke: bool, concrete: bool):
        """(phase args, run) for a registry config — concrete buffers for
        the measured path, ShapeDtypeStructs for the analytical one."""
        from repro.configs.base import RunConfig
        from repro.configs.registry import get_config, get_smoke
        from repro.models import api as M
        from repro.trace.cli import build_phase_args

        cfg = get_smoke(config) if smoke else get_config(config)
        run = RunConfig(amp=amp, fusion=fusion)
        model = M.build(cfg)
        return build_phase_args(model, run, seq=seq, batch=batch,
                                concrete=concrete), run
