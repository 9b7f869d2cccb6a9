"""Application characterization driver (paper §II-B + §III-B workflow).

The paper profiles DeepCAM by scoping Nsight Compute to the iteration loop
and collecting one metric set per phase (forward / backward / optimizer).
Here a *phase* is a jitted function; profiling it means lowering + compiling
it (optionally under a sharded mesh) and running the HLO analyzer over the
partitioned module.  The result bundles:

* the per-kernel :class:`KernelRecord` list (Table II analogue),
* XLA's own ``cost_analysis`` / ``memory_analysis`` (cross-check + HBM fit),
* the three roofline terms (compute / memory / collective),
* optional wall-clock timing (``measure=True``): the *same* compiled
  executable the analyzer characterized is executed — never a re-jit, so
  the measured program and the analyzed program are one object.  On real
  TPU hardware the same call times the real device; in a CPU container it
  times the host (the empirical path, paper Eq. 5).
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp

from repro.core import hlo_analysis
from repro.core.hlo_analysis import ModuleAnalysis
from repro.core.machine import MachineSpec, get_machine
from repro.core.roofline import RooflineTerms, roofline_terms


@dataclasses.dataclass
class ProfileResult:
    name: str
    analysis: ModuleAnalysis
    terms: RooflineTerms
    xla_flops: float                 # cost_analysis (per device, loop bodies 1x)
    xla_bytes: float
    memory_stats: Any                # CompiledMemoryStats
    n_devices: int
    wall_s: float | None = None      # measured median step time, if executed
    measure_iters: int = 0           # timed iterations behind wall_s
    # cost_analysis counts transcendentals apart from flops; the walker
    # counts each as one FLOP
    xla_transcendentals: float = 0.0

    @property
    def peak_device_bytes(self) -> int:
        ms = self.memory_stats
        if ms is None:
            return 0
        return int(ms.argument_size_in_bytes + ms.output_size_in_bytes
                   + ms.temp_size_in_bytes - ms.alias_size_in_bytes)

    def fits_hbm(self, machine: MachineSpec) -> bool:
        cap = machine.hbm.capacity_bytes
        return cap is None or self.peak_device_bytes <= cap

    def summary(self) -> str:
        mb = self.peak_device_bytes / 2**20
        wall = (f" | wall {self.wall_s*1e3:.3f} ms"
                if self.wall_s is not None else "")
        return (f"[{self.name}] {len(self.analysis.kernels)} kernels | "
                f"{self.analysis.total_flops/1e9:.2f} GFLOP/dev | "
                f"{self.analysis.total_hbm_bytes/1e9:.3f} GB HBM/dev | "
                f"{mb:.0f} MiB peak/dev | {self.terms.describe()}{wall}")


def _cost_analysis_dict(compiled) -> dict[str, float]:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca) if ca else {}


def profile_compiled(name: str, compiled, machine: MachineSpec,
                     devices_per_pod: int = 0,
                     n_devices: int = 1,
                     matmul_class: str | None = None) -> ProfileResult:
    analysis = hlo_analysis.analyze_compiled(compiled, devices_per_pod,
                                             matmul_class)
    ca = _cost_analysis_dict(compiled)
    try:
        mem = compiled.memory_analysis()
    except Exception:           # pragma: no cover - backend-dependent
        mem = None
    return ProfileResult(
        name=name,
        analysis=analysis,
        terms=roofline_terms(analysis, machine),
        xla_flops=float(ca.get("flops", 0.0)),
        xla_bytes=float(ca.get("bytes accessed", 0.0)),
        memory_stats=mem,
        n_devices=n_devices,
        xla_transcendentals=float(ca.get("transcendentals", 0.0)),
    )


# --------------------------------------------------------------------------
# Compile once, analyze AND execute the same object
# --------------------------------------------------------------------------

def compile_fn(fn: Callable, *, args: Sequence[Any],
               in_shardings: Any = None, out_shardings: Any = None,
               mesh: jax.sharding.Mesh | None = None,
               donate_argnums: tuple[int, ...] = (),
               static_argnums: tuple[int, ...] = ()):
    """Lower + compile ``fn`` on ``args`` (ShapeDtypeStructs ok).

    The single compile path shared by analysis (:func:`profile_fn`) and
    timing (:func:`time_fn`), so both always drive the same executable
    with the same shardings / static / donation configuration.
    """
    kwargs: dict[str, Any] = {}
    if in_shardings is not None:
        kwargs["in_shardings"] = in_shardings
    if out_shardings is not None:
        kwargs["out_shardings"] = out_shardings
    if donate_argnums:
        kwargs["donate_argnums"] = donate_argnums
    if static_argnums:
        kwargs["static_argnums"] = static_argnums
    jitted = jax.jit(fn, **kwargs)
    if mesh is not None:
        with jax.set_mesh(mesh):
            return jitted.lower(*args).compile()
    return jitted.lower(*args).compile()


def materialize_args(args: Sequence[Any]) -> tuple:
    """Concrete (zero-filled) arrays for any ShapeDtypeStruct leaves.

    Turns the dry-run's abstract argument specs into something an
    executable can actually run on; leaves that are already concrete pass
    through untouched.
    """
    def one(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return jnp.zeros(x.shape, x.dtype)
        return x
    return tuple(jax.tree.map(one, a,
                              is_leaf=lambda l: isinstance(
                                  l, jax.ShapeDtypeStruct))
                 for a in args)


def time_samples(compiled, args: Sequence[Any], *, iters: int = 10,
                 warmup: int = 3,
                 donate_argnums: tuple[int, ...] = ()) -> list[float]:
    """Per-iteration wall-clock seconds of a compiled executable.

    The raw-sample view behind :func:`time_compiled`; callers that want a
    different reducer (the autotuner ranks candidates on min-of-samples,
    the standard best-case discipline — system noise only ever adds time)
    take the list and fold it themselves.

    Donated arguments are consumed by each call, so they are re-copied
    *outside* the timed region every iteration (the copy is synced before
    the clock starts).
    """
    donate = set(donate_argnums)

    def call_args() -> tuple:
        if not donate:
            return tuple(args)
        return tuple(
            jax.tree.map(lambda x: jnp.array(x, copy=True), a)
            if i in donate else a
            for i, a in enumerate(args))

    out = None
    for _ in range(max(warmup, 1)):
        out = compiled(*call_args())
        jax.block_until_ready(out)
    times = []
    for _ in range(max(iters, 1)):
        a = call_args()
        jax.block_until_ready(a)
        t0 = time.perf_counter()
        out = compiled(*a)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return times


def time_compiled(compiled, args: Sequence[Any], *, iters: int = 10,
                  warmup: int = 3,
                  donate_argnums: tuple[int, ...] = ()) -> float:
    """Median wall-clock seconds per call of a compiled executable."""
    return statistics.median(time_samples(
        compiled, args, iters=iters, warmup=warmup,
        donate_argnums=donate_argnums))


def profile_fn(fn: Callable, *, args: Sequence[Any],
               name: str | None = None,
               in_shardings: Any = None, out_shardings: Any = None,
               mesh: jax.sharding.Mesh | None = None,
               machine: MachineSpec | str = "tpu-v5e",
               devices_per_pod: int = 0,
               donate_argnums: tuple[int, ...] = (),
               static_argnums: tuple[int, ...] = (),
               measure: bool = False,
               measure_iters: int = 10,
               measure_warmup: int = 3,
               concrete_args: Sequence[Any] | None = None,
               matmul_class: str | None = None) -> ProfileResult:
    """Lower + compile ``fn`` on ``args`` (ShapeDtypeStructs ok) and analyze it.

    ``measure=True`` additionally *executes* the very same compiled object
    (``concrete_args`` if given, else zero-filled materializations of
    ``args``) and records the median wall time in ``ProfileResult.wall_s``
    — the measured half of the time-based roofline.

    ``matmul_class``: ceiling class for dot/conv FLOPs whose operand chains
    show no reduced-precision hop (the CPU bf16-legalization workaround,
    docs/DESIGN.md §9) — pass the AMP policy's compute dtype class.
    """
    if isinstance(machine, str):
        machine = get_machine(machine)
    compiled = compile_fn(fn, args=args, in_shardings=in_shardings,
                          out_shardings=out_shardings, mesh=mesh,
                          donate_argnums=donate_argnums,
                          static_argnums=static_argnums)
    n_dev = len(mesh.devices.flat) if mesh is not None else 1
    res = profile_compiled(name or getattr(fn, "__name__", "fn"), compiled,
                           machine, devices_per_pod, n_dev,
                           matmul_class=matmul_class)
    if measure:
        concrete = (tuple(concrete_args) if concrete_args is not None
                    else materialize_args(args))
        res.wall_s = time_compiled(compiled, concrete, iters=measure_iters,
                                   warmup=measure_warmup,
                                   donate_argnums=donate_argnums)
        res.measure_iters = measure_iters
    return res


def time_fn(fn: Callable, *, args: Sequence[Any], iters: int = 10,
            warmup: int = 3, compiled=None, **compile_kw) -> float:
    """Wall-clock one callable (the empirical path; paper Eq. 5).

    Compiles through :func:`compile_fn` with exactly the kwargs
    :func:`profile_fn` accepts (``in_shardings`` / ``mesh`` /
    ``donate_argnums`` / ``static_argnums`` ...), so the timed program is
    the same program the analyzer would characterize — pass ``compiled``
    to skip even that single compile and time an existing executable.
    """
    if compiled is None:
        compiled = compile_fn(fn, args=args, **compile_kw)
    return time_compiled(compiled, materialize_args(args), iters=iters,
                         warmup=warmup,
                         donate_argnums=compile_kw.get("donate_argnums", ()))


def profile_phases(phases: Mapping[str, tuple[Callable, Sequence[Any]]],
                   **kw) -> dict[str, ProfileResult]:
    """Profile fwd / bwd / optimizer separately (paper Figs 3-7)."""
    return {name: profile_fn(fn, args=args, name=name, **kw)
            for name, (fn, args) in phases.items()}
