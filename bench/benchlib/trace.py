"""From the profiler's ``.xplane.pb`` to numbers: the benchmark's reduction.

A device is a plane named ``/device:TPU:<n>``.  Its line ``XLA Ops``
holds one event per HLO instruction executed, named by the instruction's
text (``%fusion.12 = bf16[...] fusion(...)``); an instruction that runs
others (a ``while``) spans them, so its events nest.  ``XLA Modules``
holds one event per program execution, named ``<module>(<id>)``.
Host spans are the events of the ``/host:CPU`` plane whose
names start with ``bench.``: the benchmark's own ``TraceAnnotation``s.
The window is the time between the end of ``start_trace`` and the start
of ``stop_trace`` on the host.

All times are nanoseconds on the trace's clock.  Device and host events
share it to within a few milliseconds, which is enough to name an idle
gap of the device by what the host was doing in it.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses

SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Op:
    start: float
    end: float
    name: str        # instruction name, e.g. "%fusion.12"
    module: str      # "<module>(<id>)" of the execution it ran in, or ""


@dataclasses.dataclass
class Device:
    ops: list[Op]                                   # sorted by start
    modules: list[tuple[float, float, str]]         # (start, end, name)


@dataclasses.dataclass
class Trace:
    devices: dict[int, Device]
    spans: list[tuple[float, float, str]]           # host spans, by start
    window: tuple[float, float]


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] ...`` -> ``%fusion.12``."""
    i = text.find(" = ")
    return text if i < 0 else text[:i]


def _device_index(plane_name: str) -> int | None:
    prefix = "/device:TPU:"
    if plane_name.startswith(prefix) and plane_name[len(prefix):].isdigit():
        return int(plane_name[len(prefix):])
    return None


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` with JAX's own reader."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: dict[int, Device] = {}
    spans: list[tuple[float, float, str]] = []
    lo = hi = None
    last_end = 0.0
    for plane in pd.planes:
        idx = _device_index(plane.name)
        if idx is not None:
            ops, modules = [], []
            names: dict[str, str] = {}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [(e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        text = e.name
                        nm = names.get(text)
                        if nm is None:
                            nm = names[text] = op_name(text)
                        ops.append(Op(e.start_ns, e.start_ns + e.duration_ns,
                                      nm, ""))
            ops.sort(key=lambda o: o.start)
            modules.sort()
            _assign_modules(ops, modules)
            devices[idx] = Device(ops, modules)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    end = e.start_ns + e.duration_ns
                    last_end = max(last_end, end)
                    if name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, end, name))
                    elif name.endswith(" start_trace"):
                        lo = end if lo is None else max(lo, end)
                    elif name.endswith(" stop_trace"):
                        hi = e.start_ns if hi is None else min(hi, e.start_ns)
    spans.sort()
    if lo is None:
        lo = 0.0
    if hi is None:
        hi = last_end
    return Trace(devices, spans, (float(lo), float(hi)))


def _assign_modules(ops: list[Op], modules: list[tuple]) -> None:
    starts = [m[0] for m in modules]
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start < modules[i][1]:
            op.module = modules[i][2]


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``(start, end, ...)`` intervals clipped to [lo, hi],
    as sorted disjoint pairs."""
    out: list[list[float]] = []
    for iv in sorted((max(i[0], lo), min(i[1], hi)) for i in intervals):
        s, e = iv
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(pairs) -> float:
    return sum(e - s for s, e in pairs)


def subtract(a, b) -> list[tuple[float, float]]:
    """Disjoint sorted pairs ``a`` minus disjoint sorted pairs ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle pairs of [lo, hi] that ``busy`` (disjoint, sorted) leaves."""
    return subtract([(lo, hi)], busy)


# --------------------------------------------------------------------------
# what the metrics read
# --------------------------------------------------------------------------

def busy_ns(dev: Device, window) -> float:
    return length(union([(o.start, o.end) for o in dev.ops], *window))


def self_times(ops: list[Op]) -> list[float]:
    """Each op's duration less that of the ops it spans (a ``while`` spans
    its body's ops)."""
    self_t = [o.end - o.start for o in ops]
    stack: list[int] = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack and o.end <= ops[stack[-1]].end:
            self_t[stack[-1]] -= o.end - o.start
        stack.append(i)
    return self_t


def in_window(ops: list[Op], window) -> list[Op]:
    lo, hi = window
    return [o for o in ops if o.start >= lo and o.end <= hi]


def op_time_by_name(ops: list[Op]) -> dict[str, float]:
    """Self time per instruction name, summed over executions."""
    out: dict[str, float] = collections.defaultdict(float)
    for o, t in zip(ops, self_times(ops)):
        out[o.name] += t
    return dict(out)


def span_at(spans, t: float) -> str:
    """The innermost host span open at ``t`` (the latest to start)."""
    best = None
    for s, e, n in spans:
        if s > t:
            break
        if e >= t:
            best = n
    return best or "no span"


def idle_by_span(dev: Device, spans, window) -> dict[str, float]:
    """Idle time of ``dev`` in the window, summed by the host span open
    at the middle of each gap."""
    busy = union([(o.start, o.end) for o in dev.ops], *window)
    out: dict[str, float] = collections.defaultdict(float)
    for s, e in gaps(busy, *window):
        out[span_at(spans, (s + e) / 2)] += e - s
    return dict(out)


def top(d: dict[str, float], n: int = 10) -> list[list]:
    """The ``n`` largest entries as ``[name, seconds]``."""
    return [[k, v / 1e9] for k, v in sorted(d.items(),
                                            key=lambda kv: -kv[1])[:n]]
