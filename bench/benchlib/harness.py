"""What every cell's run shares: finding its files, the device, the compile
cache, the count of compilations, the traced sub-window and the result line.

A cell is found by name in ``BENCHMARK.json``.  Its configuration is the
JSON file the entry names, with its plain reference ``<file>.py`` beside
it; its traffic mix is ``bench/traffic/<traffic>.json``; the runner that
runs it is ``bench/runners/<runner>.py``, named by the configuration's
``runner`` key; each per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding any of these is adding files.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)
SRC = os.path.join(CHECKOUT, "src")
PEAKS = os.path.join(BENCH_DIR, "peaks.json")

#: length of the profiler's window inside a ``--trace 1`` run, seconds
TRACE_SECONDS = 10.0

#: events of ``jax.monitoring`` that mean an executable was built or
#: loaded, which must not happen inside the measured window
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class BenchError(Exception):
    """A run that cannot produce a result; ``run.py`` exits non-zero."""


# --------------------------------------------------------------------------
# discovery
# --------------------------------------------------------------------------

def load_benchmark(checkout: str = CHECKOUT) -> dict:
    with open(os.path.join(checkout, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bm: dict, name: str) -> tuple[dict, dict]:
    """(the workload entry, its configuration entry)."""
    cells = {c["name"]: c for c in bm["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; have "
                         f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    return cell, configs[cell["config"]]


def load_config(entry: dict, checkout: str = CHECKOUT) -> dict:
    with open(os.path.join(checkout, entry["file"])) as f:
        cfg = json.load(f)
    cfg["_file"] = os.path.join(checkout, entry["file"])
    return cfg


def load_module(path: str, name: str | None = None):
    """Import a file by path (metric readers, runners and references
    have names that are not Python identifiers)."""
    if not os.path.exists(path):
        raise BenchError(f"no such file: {path}")
    name = name or "bench_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def reference_of(cfg: dict):
    """The plain reference beside a configuration's file."""
    return load_module(os.path.splitext(cfg["_file"])[0] + ".py")


def runner_of(cfg: dict):
    return load_module(os.path.join(BENCH_DIR, "runners",
                                    f"{cfg['runner']}.py"))


def metric_reader(name: str):
    return load_module(os.path.join(BENCH_DIR, "metrics", f"{name}.py"))


def cell_metrics(bm: dict, cell: dict, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


# --------------------------------------------------------------------------
# device, cache, compilations
# --------------------------------------------------------------------------

def require_chips(chips: int) -> list:
    """The first ``chips`` TPU devices; anything else is an error."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def peaks_for(kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in {PEAKS}")
    return table[kind]


def enable_compile_cache(checkout: str = CHECKOUT) -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    the checkout's ``.jax-cache``; every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        checkout, ".jax-cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts executables built or loaded while ``on`` is set."""

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, _secs: float, **_kw) -> None:
        if self.on and name in COMPILE_EVENTS:
            self.n += 1


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# --------------------------------------------------------------------------
# the traced sub-window
# --------------------------------------------------------------------------

class Tracer:
    """Host spans always; the profiler only in a ``--trace 1`` run, for
    :data:`TRACE_SECONDS` in the middle of the measured window."""

    def __init__(self, enabled: bool, seconds: float,
                 clock: Callable[[], float] = time.perf_counter):
        self.enabled = enabled
        self.seconds = seconds
        self.clock = clock
        self.length = min(TRACE_SECONDS, seconds)
        self.start_at = self.stop_at = None
        self.started = self.stopped = None       # host clock at the edges
        self.active = False
        self.dir = None
        with self.span("bench.setup"):    # the first span loads the profiler
            pass

    def open_window(self, t0: float) -> None:
        lead = max(0.0, (self.seconds - self.length) / 2)
        self.start_at = t0 + lead
        self.stop_at = self.start_at + self.length

    def poll(self, now: float) -> bool:
        """Start or stop the profiler at the sub-window's edges; True
        while it records."""
        if not self.enabled:
            return False
        import jax
        if not self.active and self.started is None and now >= self.start_at:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # Python calls: costly, unread
            # level 1 keeps the benchmark's own spans and the runtime's
            # coarse events; the finer levels add host work to every
            # dispatch, which is what the untraced runs are judged on
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.started = self.clock()
            self.active = True
        elif self.active and now >= self.stop_at:
            self.close()
        return self.active

    def close(self) -> None:
        if self.active:
            import jax
            self.stopped = self.clock()
            jax.profiler.stop_trace()
            self.active = False

    def span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def xplane(self) -> str:
        import glob
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise BenchError("the profiler wrote no trace")
        return found[0]

    def cleanup(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


# --------------------------------------------------------------------------
# output
# --------------------------------------------------------------------------

def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def emit(result: dict, checks: dict[str, tuple[float, float]]) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard output,
    with the same numbers under ``checks``, its last key."""
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value!r} (limit {limit!r})",
              file=sys.stderr, flush=True)
    out = dict(result)
    out["checks"] = {n: {"value": v, "limit": lim}
                     for n, (v, lim) in checks.items()}
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()


@contextlib.contextmanager
def program_on_path():
    """Make the program (``src/``) importable; it must be there."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise BenchError(f"the program is not in this checkout ({SRC})")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    yield


def fmt_checks(values: dict[str, float], limits: dict[str, float]
               ) -> dict[str, tuple[float, float]]:
    return {k: (float(values[k]), float(limits[k])) for k in limits}


def all_within(checks: dict[str, tuple[float, float]]) -> bool:
    return all(v == v and v <= lim for v, lim in checks.values())



# --------------------------------------------------------------------------
# what a runner is given and gives back
# --------------------------------------------------------------------------

def check_layout(mine, theirs) -> None:
    """The reference's parameter tree (``mine``) has the program's
    structure, shapes and dtypes."""
    import jax
    if jax.tree.structure(mine) != jax.tree.structure(theirs):
        raise BenchError("the program's parameter tree differs from the "
                         "reference's")
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise BenchError(f"parameter {a.shape} {a.dtype} of the "
                             f"reference against {b.shape} {b.dtype} of the "
                             "program")


def seed_key(seed: int):
    """A JAX key for any non-negative whole number, however large."""
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@dataclasses.dataclass
class Ctx:
    seed: int
    seconds: float
    trace: bool
    chips: int
    devices: list
    cfg: dict
    traffic: dict
    cell: dict
    tracer: Tracer
    counter: CompileCounter
    t_start: float                      # host clock when the run began
    limits: dict


@dataclasses.dataclass
class Outcome:
    correct: bool
    attempted: int
    failed: int
    end_to_end: dict[str, float]        # metric name -> value
    checks: dict[str, tuple[float, float]]
    memory_peak_bytes: int
    reading: dict                       # what the per-layer readers read
    extra: dict = dataclasses.field(default_factory=dict)  # result keys


class Reading:
    """What a per-layer reader is given: ``trace`` (a
    :class:`benchlib.trace.Trace`), ``chips``, ``cfg``, ``peaks``,
    ``window_host_s`` (the traced sub-window on the host clock) and what
    the runner counted there."""

    def __init__(self, **fields):
        self.__dict__.update(fields)

    def get(self, name: str, default=None):
        return self.__dict__.get(name, default)


def build_ctx(workload: str, seed: int, seconds: float, trace: bool,
              t_start: float) -> tuple[dict, Ctx]:
    """(BENCHMARK.json, the run's context) for a cell on this machine's
    chips; raises :class:`BenchError` where the chips are not there."""
    from benchlib import traffic as T
    bm = load_benchmark()
    cell, entry = find_cell(bm, workload)
    cfg = load_config(entry)
    spec = T.load(cell["traffic"])
    devices = require_chips(cell["chips"])
    enable_compile_cache()
    ctx = Ctx(seed=seed, seconds=seconds, trace=trace, chips=cell["chips"],
              devices=devices, cfg=cfg, traffic=spec, cell=cell,
              tracer=Tracer(trace, seconds), counter=CompileCounter(),
              t_start=t_start,
              limits={**cfg.get("limits", {}), **spec.get("limits", {})})
    return bm, ctx
