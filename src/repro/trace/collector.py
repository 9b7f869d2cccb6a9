"""Measured-time collection: fold wall time into the analytical roofline.

The analytical pipeline (``repro.core``) bounds each kernel's time from
below (FLOPs/ceiling, bytes/bandwidth).  This module runs the *same
compiled executable* — ``profile_fn(measure=True)``, never a re-jit — and
spreads the measured wall time across kernels proportionally to their
analytical bound times.  That profile-weighted attribution is the standard
move of the time-based roofline (arXiv 2009.04598): it turns one wall-time
number plus the per-kernel characterization into

* per-kernel *achieved* FLOP/s  = FLOPs / attributed time,
* per-kernel %-of-roofline      = bound time / attributed time,
* per-phase  achieved FLOP/s and %-of-roofline against the three-term
  ``max(T_compute, T_memory, T_collective)`` envelope.

On real TPU hardware the wall time is device time; in the CPU container it
is host time against the ``cpu-host`` machine model — the full
measure→characterize→compare loop is exercised either way.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Sequence

from repro.core.hlo_analysis import KernelRecord, ModuleAnalysis
from repro.core.machine import MachineSpec, get_machine
from repro.core.profiler import ProfileResult, profile_fn
from repro.core.roofline import RooflineTerms, kernel_points


@dataclasses.dataclass(frozen=True)
class KernelMeasurement:
    """One kernel with measured time attributed onto its analytical bound."""

    name: str
    category: str
    exec_count: int
    flops: float                    # total FLOPs (x exec_count)
    hbm_bytes: float                # total fusion-boundary traffic
    ai_hbm: float                   # arithmetic intensity at HBM
    bound_s: float                  # analytical lower bound on time
    attributed_s: float             # share of the measured wall time
    achieved_flops_per_s: float     # flops / attributed_s
    pct_of_roofline: float          # bound_s / attributed_s  (1.0 = at bound)
    vmem_bytes: float = 0.0         # total internal (VMEM-level) traffic


@dataclasses.dataclass
class PhaseMeasurement:
    """One profiled-and-measured phase (fwd / bwd / opt / step)."""

    name: str
    wall_s: float                   # measured median step time
    iters: int
    machine: str
    terms: RooflineTerms            # the analytical three-term envelope
    kernels: list[KernelMeasurement]
    flops: float                    # per-device HLO FLOPs
    hbm_bytes: float
    vmem_bytes: float = 0.0         # per-device internal (VMEM-level) bytes
    xla_flops: float = 0.0          # XLA's cost_analysis FLOPs (cross-check)
    xla_transcendentals: float = 0.0    # ... and transcendentals, apart

    @property
    def achieved_flops_per_s(self) -> float:
        return self.flops / self.wall_s if self.wall_s else 0.0

    @property
    def pct_of_roofline(self) -> float:
        """Measured efficiency vs the perfect-overlap bound (<=1 in theory;
        >1 means the machine model under-estimates this host)."""
        return self.terms.bound_overlap_s / self.wall_s if self.wall_s else 0.0

    @property
    def bound_overlap_s(self) -> float:
        return self.terms.bound_overlap_s

    @property
    def bound_serial_s(self) -> float:
        return self.terms.bound_serial_s

    @property
    def dominant(self) -> str:
        return self.terms.dominant

    def summary(self) -> str:
        return (f"[{self.name}] wall {self.wall_s*1e3:.3f} ms | "
                f"achieved {self.achieved_flops_per_s/1e9:.2f} GFLOP/s | "
                f"{100*self.pct_of_roofline:.1f}% of roofline | "
                f"bound [{self.bound_overlap_s*1e3:.3f}, "
                f"{self.bound_serial_s*1e3:.3f}] ms | "
                f"dominant={self.dominant}")


def kernel_bound_s(rec: KernelRecord, machine: MachineSpec) -> float:
    """Analytical time bound for one kernel: the larger of its HBM-roofline
    bound and its pure memory-streaming time (the weighting
    ``repro.core.report.kernel_table`` ranks by)."""
    pts = kernel_points(rec, machine)
    hbm = next(p for p in pts if p.level == "hbm")
    t = hbm.time_bound_s * rec.exec_count
    t_mem = rec.total_hbm_bytes / machine.hbm.bytes_per_s
    return max(t, t_mem)


def attribute_time(analysis: ModuleAnalysis, machine: MachineSpec,
                   wall_s: float) -> list[KernelMeasurement]:
    """Spread measured wall time over kernels by bound-time weight.

    Kernels with zero analytical bound (empty fusions) get zero attributed
    time; if *every* bound is zero the time is split evenly so nothing is
    silently dropped.  Returned sorted by attributed time, descending.
    """
    recs = list(analysis.kernels)
    if not recs:
        return []
    bounds = [kernel_bound_s(r, machine) for r in recs]
    total = sum(bounds)
    out = []
    for rec, bound in zip(recs, bounds):
        weight = bound / total if total else 1.0 / len(recs)
        t_attr = wall_s * weight
        out.append(KernelMeasurement(
            name=rec.name, category=rec.category,
            exec_count=rec.exec_count,
            flops=rec.total_flops, hbm_bytes=rec.total_hbm_bytes,
            ai_hbm=rec.total_flops / rec.total_hbm_bytes
            if rec.total_hbm_bytes else 0.0,
            bound_s=bound, attributed_s=t_attr,
            achieved_flops_per_s=rec.total_flops / t_attr if t_attr else 0.0,
            pct_of_roofline=bound / t_attr if t_attr else 0.0,
            vmem_bytes=rec.total_vmem_bytes))
    out.sort(key=lambda k: -k.attributed_s)
    return out


def achieved_points(kernels: Sequence[KernelMeasurement]
                    ) -> list[tuple[float, float]]:
    """(AI, achieved FLOP/s) scatter for the measured roofline chart."""
    return [(k.ai_hbm, k.achieved_flops_per_s) for k in kernels
            if k.ai_hbm > 0 and k.achieved_flops_per_s > 0]


def measurement_from_profile(res: ProfileResult,
                             machine: MachineSpec | str
                             ) -> PhaseMeasurement:
    """Build a PhaseMeasurement from an already-measured ProfileResult."""
    if isinstance(machine, str):
        machine = get_machine(machine)
    if res.wall_s is None:
        raise ValueError(
            f"{res.name}: ProfileResult has no wall_s — profile with "
            "measure=True (or time_compiled the same executable) first")
    return PhaseMeasurement(
        name=res.name, wall_s=res.wall_s, iters=res.measure_iters,
        machine=machine.name, terms=res.terms,
        kernels=attribute_time(res.analysis, machine, res.wall_s),
        flops=res.analysis.total_flops,
        hbm_bytes=res.analysis.total_hbm_bytes,
        vmem_bytes=res.analysis.total_vmem_bytes,
        xla_flops=res.xla_flops,
        xla_transcendentals=res.xla_transcendentals)


def collect_phase(name: str, fn: Callable, args: Sequence[Any], *,
                  machine: MachineSpec | str = "cpu-host",
                  iters: int = 10, warmup: int = 3,
                  concrete_args: Sequence[Any] | None = None,
                  **profile_kw) -> PhaseMeasurement:
    """Compile once, analyze + execute that executable, attribute the time."""
    if isinstance(machine, str):
        machine = get_machine(machine)
    res = profile_fn(fn, args=args, name=name, machine=machine,
                     measure=True, measure_iters=iters,
                     measure_warmup=warmup, concrete_args=concrete_args,
                     **profile_kw)
    return measurement_from_profile(res, machine)


def collect_phases(phases: Mapping[str, tuple[Callable, Sequence[Any]]], *,
                   machine: MachineSpec | str = "cpu-host",
                   iters: int = 10, warmup: int = 3,
                   concrete_args: Mapping[str, Sequence[Any]] | None = None,
                   **profile_kw) -> dict[str, PhaseMeasurement]:
    """Measure fwd / bwd / optimizer separately (paper Figs 3-7, measured)."""
    out = {}
    for name, (fn, args) in phases.items():
        conc = concrete_args.get(name) if concrete_args else None
        out[name] = collect_phase(name, fn, args, machine=machine,
                                  iters=iters, warmup=warmup,
                                  concrete_args=conc, **profile_kw)
    return out
