"""Machine characterization: the ERT-analogue machine model (paper §II-A).

The paper extends the Empirical Roofline Toolkit to produce multi-precision
compute ceilings (FP64/FP32/FP16/TensorCore on V100).  On TPU the equivalent
ceiling set is {fp32 (VPU), bf16 (MXU), int8 (MXU)} plus per-level memory
bandwidths (HBM / VMEM) and the interconnect (ICI / DCN).

Two sources feed a :class:`MachineSpec`:

* **datasheet** constants (the numbers below, from the task spec + public
  TPU v5e documentation) — the "marketing numbers" the paper warns about;
* **empirical** measurements from the ERT micro-kernels in
  ``repro.kernels.ert`` — on real hardware these overwrite the datasheet
  ceilings (``MachineSpec.with_empirical``); in this CPU container the
  empirical path runs against the host CPU (see ``empirical_cpu_spec``)
  so the full measure→characterize→plot loop is exercised end-to-end.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping


@dataclasses.dataclass(frozen=True)
class MemLevel:
    """One level of the memory hierarchy (paper: L1/L2/HBM; here VMEM/HBM)."""

    name: str
    bytes_per_s: float          # sustained bandwidth, bytes/s per chip
    capacity_bytes: int | None  # None = not capacity-limited at this granularity


@dataclasses.dataclass(frozen=True)
class NetLevel:
    """One level of the interconnect hierarchy (ICI within a pod, DCN across).

    The third roofline hierarchy level: collectives bound step time by
    ``wire_bytes / bytes_per_s + latency_s x n_collectives`` the same way
    memory traffic is bounded by ``bytes / bandwidth``.  ``bytes_per_s``
    is the *aggregate* per-chip wire bandwidth (per-link x usable links
    for ICI), so it divides algorithm-corrected wire bytes directly.
    """

    name: str                   # "ici" | "dcn"
    bytes_per_s: float          # aggregate wire bandwidth, bytes/s per chip
    latency_s: float = 0.0      # per-collective launch/sync latency


@dataclasses.dataclass(frozen=True)
class MachineSpec:
    """Per-chip machine model with multi-precision ceilings (paper Fig 1)."""

    name: str
    # precision → peak FLOP/s per chip.  The MXU (systolic matmul unit) is the
    # Tensor-Core analogue; the VPU handles non-matmul vector work.
    peak_flops: Mapping[str, float]
    # ordered fastest→slowest (VMEM before HBM), paper's L1→L2→HBM ordering.
    mem_levels: tuple[MemLevel, ...]
    ici_bytes_per_s: float       # per-link ICI bandwidth
    ici_links: int               # usable links per chip (2D torus: 4)
    dcn_bytes_per_s: float       # per-chip cross-pod (data-center network) bw
    empirical: bool = False      # True once ERT measurements overwrite datasheet
    # interconnect levels, fastest→slowest (ICI before DCN).  Empty means
    # "derive from the datasheet scalars above" (``interconnect`` property);
    # ``with_empirical_net`` fills them from measured collective ceilings.
    net_levels: tuple[NetLevel, ...] = ()

    # -- convenience -------------------------------------------------------
    @property
    def hbm(self) -> MemLevel:
        return self.mem_levels[-1]

    @property
    def vmem(self) -> MemLevel:
        return self.mem_levels[0]

    @property
    def interconnect(self) -> tuple[NetLevel, ...]:
        """Interconnect roofline levels (third hierarchy level).

        Falls back to datasheet-derived levels (zero launch latency) when
        no empirical collective characterization has been applied.
        """
        if self.net_levels:
            return self.net_levels
        return (NetLevel("ici", self.ici_bytes_per_s * self.ici_links),
                NetLevel("dcn", self.dcn_bytes_per_s))

    def net_level(self, name: str) -> NetLevel:
        for lv in self.interconnect:
            if lv.name == name:
                return lv
        raise KeyError(f"no interconnect level {name!r} in {self.name}")

    def peak_for(self, dtype_class: str) -> float:
        """Ceiling for a dtype class, defaulting to the bf16 MXU ceiling."""
        return self.peak_flops.get(dtype_class, self.peak_flops["bf16"])

    def ridge_point(self, dtype_class: str = "bf16", level: str = "hbm") -> float:
        """AI (FLOPs/byte) where the machine transitions memory→compute bound."""
        bw = self.hbm.bytes_per_s if level == "hbm" else self.level(level).bytes_per_s
        return self.peak_for(dtype_class) / bw

    def level(self, name: str) -> MemLevel:
        for lv in self.mem_levels:
            if lv.name == name:
                return lv
        raise KeyError(f"no memory level {name!r} in {self.name}")

    def with_empirical(self, peaks: Mapping[str, float] | None = None,
                       bandwidths: Mapping[str, float] | None = None) -> "MachineSpec":
        """Overwrite datasheet ceilings with ERT measurements (paper §II-A)."""
        flops = dict(self.peak_flops)
        if peaks:
            flops.update(peaks)
        levels = tuple(
            MemLevel(lv.name, (bandwidths or {}).get(lv.name, lv.bytes_per_s),
                     lv.capacity_bytes)
            for lv in self.mem_levels
        )
        return dataclasses.replace(self, peak_flops=flops, mem_levels=levels,
                                   empirical=True)

    def with_empirical_net(self, bandwidths: Mapping[str, float],
                           latencies: Mapping[str, float] | None = None
                           ) -> "MachineSpec":
        """Overwrite interconnect ceilings with measured collective ceilings.

        ``bandwidths``/``latencies`` are keyed by level name ("ici"/"dcn");
        levels not mentioned keep their current (datasheet or previously
        measured) values.  Mirrors :meth:`with_empirical` for the network.
        """
        lat = latencies or {}
        levels = tuple(
            NetLevel(lv.name, bandwidths.get(lv.name, lv.bytes_per_s),
                     lat.get(lv.name, lv.latency_s))
            for lv in self.interconnect)
        return dataclasses.replace(self, net_levels=levels)


# --------------------------------------------------------------------------
# Datasheet machine models
# --------------------------------------------------------------------------

# TPU v5e — the primary target (constants per task spec).
# fp32 has no dedicated MXU path; the modeled ceiling is 1/4 of bf16
# (documented assumption, see docs/DESIGN.md §4).  VMEM bandwidth is a modeled
# constant used only to spread the hierarchical-AI triplets (paper's L1/L2
# vs HBM distinction); it is clearly labeled modeled, not measured.
TPU_V5E = MachineSpec(
    name="tpu-v5e",
    peak_flops={
        "bf16": 197e12,
        "f32": 49.2e12,
        "int8": 394e12,
    },
    mem_levels=(
        MemLevel("vmem", 8.0e12, 128 * 2**20),   # modeled ~10x HBM
        MemLevel("hbm", 819e9, 16 * 2**30),
    ),
    ici_bytes_per_s=50e9,
    ici_links=4,
    dcn_bytes_per_s=25e9,
)

# TPU v5p — for sensitivity checks in benchmarks (not the graded target).
TPU_V5P = MachineSpec(
    name="tpu-v5p",
    peak_flops={"bf16": 459e12, "f32": 114.75e12, "int8": 918e12},
    mem_levels=(
        MemLevel("vmem", 16.0e12, 128 * 2**20),
        MemLevel("hbm", 2765e9, 95 * 2**30),
    ),
    ici_bytes_per_s=100e9,
    ici_links=6,
    dcn_bytes_per_s=25e9,
)

# Host CPU — placeholder; ``empirical_cpu_spec`` measures the real numbers.
CPU_HOST = MachineSpec(
    name="cpu-host",
    peak_flops={"bf16": 100e9, "f32": 100e9, "int8": 100e9},
    mem_levels=(
        MemLevel("vmem", 200e9, 32 * 2**20),     # stands in for LLC
        MemLevel("hbm", 20e9, None),             # stands in for DRAM
    ),
    ici_bytes_per_s=10e9,
    ici_links=1,
    dcn_bytes_per_s=10e9,
)

MACHINES: dict[str, MachineSpec] = {
    m.name: m for m in (TPU_V5E, TPU_V5P, CPU_HOST)
}


def get_machine(name: str = "tpu-v5e") -> MachineSpec:
    return MACHINES[name]


#: ``device_kind`` as JAX reports it -> machine model (the CPU backend
#: reports its kind as "cpu")
DEVICE_KINDS: dict[str, str] = {
    "TPU v5 lite": "tpu-v5e",
    "cpu": "cpu-host",
}


def machine_for_device(device=None) -> MachineSpec:
    """Machine model of ``device`` (default: this process's first device).

    Keyed on ``device_kind``; a device with no model is an error, never
    a guessed peak.  Asking JAX for its devices initialises the backend,
    and on an accelerator that claims the chip for this process.
    """
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = device.device_kind
    try:
        return MACHINES[DEVICE_KINDS[kind]]
    except KeyError:
        raise KeyError(f"no machine model for device kind {kind!r}; known: "
                       f"{sorted(DEVICE_KINDS)}") from None


def empirical_cpu_spec(tuned: bool = True, store=None, smoke: bool = False,
                       backend: str = "xla") -> MachineSpec:
    """Measured machine model of *this* host (the real ERT loop).

    ``tuned=True`` (default) derives every ceiling from the best-of-tuned
    winners persisted in the ``repro.tune`` store — the paper's §II-A
    discipline: a ceiling nobody tuned for understates the roof and
    inflates every achieved-vs-bound verdict downstream.  The first call
    runs the searches; later calls are pure store hits.  ``tuned=False``
    reproduces the old single-default-sample behavior.

    Lazy import: the measurement code lives in ``repro.kernels.ert.ops``
    and pulls in jax; this module stays importable without it.
    """
    from repro.kernels.ert.ops import characterize
    return characterize(backend=backend, tuned=tuned, store=store,
                        smoke=smoke)
