"""The empirical search loop: compile every candidate, time it, keep the
winner.

Timing goes through the exact harness the rest of the system measures
with — ``repro.core.profiler.compile_fn`` + ``time_samples`` — so a tuned
wall time and a ``repro.trace`` wall time are the same measurement.  The
per-candidate statistic is *min of samples* (the classic autotuner
discipline: noise only ever adds time); the stored record also keeps the
default config's numbers so every consumer can report before/after.

A search over a (kernel, shape, dtype, machine, backend) point that is
already in the :class:`~repro.tune.store.TuneStore` returns the stored
winner without timing anything (``cached=True``) unless ``force=True`` —
the zero-search-cost invariant the store exists for.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

from repro.tune import space as sp
from repro.tune.store import (TuneRecord, TuneStore, make_record, tune_key)


@dataclasses.dataclass
class CandidateResult:
    params: dict[str, Any]
    wall_s: float
    metric: float
    is_default: bool


@dataclasses.dataclass
class TuneOutcome:
    record: TuneRecord
    candidates: list[CandidateResult]     # [] on a store hit
    cached: bool

    @property
    def speedup(self) -> float:
        return self.record.speedup

    def describe(self) -> str:
        r = self.record
        tag = "store hit" if self.cached else f"{len(self.candidates)} cands"
        return (f"{r.kernel}/{r.backend} {'x'.join(map(str, r.shape))} "
                f"{r.dtype}: best {r.params} "
                f"{r.wall_s*1e6:.1f}us (default {r.default_wall_s*1e6:.1f}us, "
                f"{r.speedup:.2f}x) [{tag}]")


def _time_candidate(cand: sp.Candidate, iters: int, warmup: int) -> float:
    """Default timer: the shared compile-once/time-that-object harness."""
    from repro.core.profiler import compile_fn, time_samples
    fn, args = cand.build()
    compiled = compile_fn(fn, args=args)
    return min(time_samples(compiled, args, iters=iters, warmup=warmup))


def search(kernel: str, shape: Sequence[int] | None = None,
           dtype: str = "float32", machine: str = "cpu-host",
           backend: str = "pallas",
           store: TuneStore | str | None = None,
           iters: int = 3, warmup: int = 1, smoke: bool = False,
           force: bool = False,
           timer: Callable[[sp.Candidate, int, int], float] | None = None
           ) -> TuneOutcome:
    """Tune one (kernel, shape, dtype, machine, backend) point.

    ``timer`` is injectable for tests (it replaces compile+time for one
    candidate); the default is the real harness.  Store hit → no timer
    calls at all.
    """
    if shape is None:
        shape = sp.default_shape(kernel, smoke)
    if not isinstance(store, TuneStore):
        store = TuneStore(store)
    key = tune_key(kernel, shape, dtype, machine, backend)
    if not force:
        hit = store.get(key)
        if hit is not None:
            return TuneOutcome(hit, [], cached=True)

    timer = timer or _time_candidate
    cands = sp.candidates(kernel, shape, dtype, backend, smoke)
    results: list[CandidateResult] = []
    for cand in cands:
        wall = float(timer(cand, iters, warmup))
        metric = (cand.work / wall) if wall > 0 else 0.0
        results.append(CandidateResult(
            cand.dict, wall, metric,
            is_default=sp.is_default(kernel, backend, shape, cand.dict,
                                     dtype)))

    best = max(results, key=lambda r: r.metric)
    default = next(r for r in results if r.is_default)
    rec = store.put(make_record(
        kernel, shape, dtype, machine, backend,
        params=best.params, wall_s=best.wall_s, metric=best.metric,
        metric_name=cands[0].metric_name,
        default_wall_s=default.wall_s, default_metric=default.metric,
        n_candidates=len(results)))
    return TuneOutcome(rec, results, cached=False)


def search_all(kernels: Sequence[str] | None = None, *,
               machine: str = "cpu-host",
               store: TuneStore | str | None = None,
               iters: int = 3, warmup: int = 1, smoke: bool = False,
               force: bool = False, dtype: str = "float32",
               progress: Callable[[str], None] | None = None
               ) -> list[TuneOutcome]:
    """Tune every Pallas kernel at its default shape (the CLI's default)."""
    say = progress or (lambda s: None)
    if not isinstance(store, TuneStore):
        store = TuneStore(store)
    out = []
    for kernel in (kernels or sp.PALLAS_KERNELS):
        outcome = search(kernel, dtype=dtype, machine=machine, store=store,
                         iters=iters, warmup=warmup, smoke=smoke,
                         force=force)
        say(outcome.describe())
        out.append(outcome)
    return out


# --------------------------------------------------------------------------
# Ceiling searches: the measurements behind empirical_cpu_spec
# --------------------------------------------------------------------------

def ceiling_shapes(smoke: bool = False) -> dict[str, tuple[int, ...]]:
    """Problem sizes the ceiling searches run at (level semantics: the
    large triad is DRAM-resident, the small one cache-resident)."""
    if smoke:
        return {"flops_n": (1 << 14,), "gemm": (128, 128, 128),
                "bw_hbm": (1 << 18,), "bw_vmem": (1 << 13,)}
    return {"flops_n": (1 << 20,), "gemm": (1024, 1024, 1024),
            "bw_hbm": (1 << 24,), "bw_vmem": (1 << 16,)}


def tune_ceilings(machine: str = "cpu-host",
                  store: TuneStore | str | None = None,
                  iters: int = 3, warmup: int = 1, smoke: bool = False,
                  force: bool = False,
                  progress: Callable[[str], None] | None = None
                  ) -> dict[str, TuneOutcome]:
    """Best-of-tuned ceiling measurements over the XLA oracle spaces.

    Keys: ``flops_f32`` / ``flops_bf16`` (FMA-ladder winners),
    ``gemm_bf16`` (MXU/units analogue), ``bw_hbm`` / ``bw_vmem``
    (DRAM- and cache-resident triad).  All persisted — a second call is
    pure store hits.
    """
    say = progress or (lambda s: None)
    if not isinstance(store, TuneStore):
        store = TuneStore(store)
    shapes = ceiling_shapes(smoke)
    kw = dict(machine=machine, store=store, iters=iters, warmup=warmup,
              smoke=smoke, force=force, backend="xla")
    out = {
        "flops_f32": search("fma_chain", shapes["flops_n"],
                            dtype="float32", **kw),
        "flops_bf16": search("fma_chain", shapes["flops_n"],
                             dtype="bfloat16", **kw),
        "gemm_bf16": search("ert_gemm", shapes["gemm"],
                            dtype="bfloat16", **kw),
        "bw_hbm": search("triad", shapes["bw_hbm"], dtype="float32", **kw),
        "bw_vmem": search("triad", shapes["bw_vmem"], dtype="float32", **kw),
    }
    for name, oc in out.items():
        say(f"[{name}] {oc.describe()}")
    return out
