"""Kernel launch configuration shared by every Pallas kernel.

The paper's machine characterization is only honest when the micro-kernels
are *tuned*: a hardcoded tile size measures what one arbitrary default
achieves, not what the machine can do (§II-A — the ERT loop tunes its
kernel 15.4 → 29.2 TFLOP/s before calling the number a ceiling).  This
module is the single place kernel launch parameters live:

* :class:`KernelConfig` — a frozen, hashable (kernel, params) pair with
  optional ``dimension_semantics`` pipelining hints for the Mosaic
  compiler (``parallel`` grid dims may be partitioned across cores;
  ``arbitrary`` dims are sequential — accumulator / state-carry dims);
* :data:`DEFAULTS` — the per-kernel default configs (the former scattered
  module constants: ``BLOCK = 16384`` etc.), still the fallback when no
  tuned winner exists in the :class:`repro.tune.TuneStore`;
* :func:`compiler_params` — the pallas_call ``compiler_params`` payload
  for a config (None when the config carries no semantics hints).

Kernels take ``config=None`` and resolve through :func:`resolve`; they
never read the tune store themselves — store lookups live in the ops
wrappers and ``repro.tune.best_config`` so the kernel functions stay pure.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

KERNELS = ("triad", "fma_chain", "ert_gemm", "flash_attention", "ssd_scan",
           "fused_norm", "fused_swiglu", "fused_adamw")


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One kernel's launch parameters (hashable: params as sorted items)."""

    kernel: str
    params: tuple[tuple[str, Any], ...]
    # one entry per grid dim: "parallel" | "arbitrary" (pipelining hint)
    dimension_semantics: tuple[str, ...] | None = None

    @classmethod
    def make(cls, kernel: str,
             dimension_semantics: tuple[str, ...] | None = None,
             **params: Any) -> "KernelConfig":
        return cls(kernel, tuple(sorted(params.items())),
                   dimension_semantics)

    @property
    def dict(self) -> dict[str, Any]:
        return dict(self.params)

    def get(self, name: str, default: Any = None) -> Any:
        return self.dict.get(name, default)

    def replace(self, **params: Any) -> "KernelConfig":
        merged = {**self.dict, **params}
        return KernelConfig(self.kernel, tuple(sorted(merged.items())),
                            self.dimension_semantics)

    def label(self) -> str:
        """Comma-free param summary (safe inside CSV `derived` fields)."""
        inner = ";".join(f"{k}={v}" for k, v in self.params)
        return f"{self.kernel}({inner})"

    def to_dict(self) -> dict[str, Any]:
        return {"kernel": self.kernel, "params": self.dict,
                "dimension_semantics": (list(self.dimension_semantics)
                                        if self.dimension_semantics
                                        else None)}

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "KernelConfig":
        ds = d.get("dimension_semantics")
        return cls.make(str(d.get("kernel", "?")),
                        tuple(ds) if ds else None,
                        **dict(d.get("params", {})))


# the former hardcoded module constants, as explicit defaults; the
# dimension_semantics encode which grid dims carry state (sequential) vs
# which the Mosaic pipeliner may partition across cores
DEFAULTS: dict[str, KernelConfig] = {
    "triad": KernelConfig.make(
        "triad", ("parallel",), block=16384, double_buffer=False),
    "fma_chain": KernelConfig.make(
        "fma_chain", ("parallel",), block=4096),
    "ert_gemm": KernelConfig.make(
        "ert_gemm", ("parallel", "parallel", "arbitrary"),
        block_m=256, block_n=256, block_k=256),
    "flash_attention": KernelConfig.make(
        "flash_attention", ("parallel", "parallel"),
        block_q=512, block_k=512),
    "ssd_scan": KernelConfig.make(
        "ssd_scan", ("parallel", "parallel", "arbitrary"), chunk=128),
    # fused epilogue kernels (repro.kernels.fused): row blocks are
    # independent → a single parallel grid dim each
    "fused_norm": KernelConfig.make(
        "fused_norm", ("parallel",), block_rows=1024),
    "fused_swiglu": KernelConfig.make(
        "fused_swiglu", ("parallel",), block_rows=1024),
    "fused_adamw": KernelConfig.make(
        "fused_adamw", ("parallel",), block=65536),
}


def default_config(kernel: str) -> KernelConfig:
    try:
        return DEFAULTS[kernel]
    except KeyError:
        raise KeyError(f"unknown kernel {kernel!r}; known: {KERNELS}")


def resolve(kernel: str, config: "KernelConfig | None",
            **overrides: Any) -> KernelConfig:
    """Layer explicit kwargs over ``config`` over the kernel default.

    ``overrides`` entries that are ``None`` mean "not specified" and fall
    through to the config / default value.
    """
    base = config if config is not None else default_config(kernel)
    if base.kernel != kernel:
        raise ValueError(f"config for {base.kernel!r} passed to {kernel!r}")
    explicit = {k: v for k, v in overrides.items() if v is not None}
    return base.replace(**explicit) if explicit else base


def compiler_params(config: KernelConfig):
    """pallas_call ``compiler_params`` for a config (None = no hints).

    Interpret mode accepts and ignores TPU compiler params, so callers can
    pass this unconditionally.
    """
    if not config.dimension_semantics:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=tuple(config.dimension_semantics))


def interpret_mode(interpret: bool | None = None) -> bool:
    """Whether a ``pallas_call`` runs in the Pallas interpreter.

    ``None`` (every wrapper's default) derives it from the platform: the
    interpreter only on the CPU backend, where Mosaic cannot lower, and
    the compiled kernel everywhere else.  An explicit bool wins, so a
    compile for a described chip passes ``interpret=False`` from a CPU
    process.
    """
    if interpret is not None:
        return bool(interpret)
    import jax
    return jax.default_backend() == "cpu"
