"""What the program records about its own training step, and the numbers
read from it.

Two records, both the program's:

* host spans: ``Trainer.fit`` opens ``jax.profiler`` annotations named
  ``repro.train.*`` around each part of a step (``step`` holding
  ``batch``, ``put``, ``dispatch``, ``wait``, ``readback`` and, when it
  saves, ``checkpoint``); they are events of the ``/host:CPU`` plane of
  the same ``.xplane.pb`` as the device's ops;
* phase scopes: the step wraps its gradient in ``jax.named_scope
  ("train.grad")`` and its update in ``"train.optimizer"``, so each HLO
  instruction's ``op_name`` metadata says where it came from.  Under
  ``train.grad`` the backward's instructions carry ``transpose(`` (JAX's
  name for the transposed, reverse-mode computation), the forward's do
  not.

An instruction that runs on the device is keyed as ``hlo.costs`` keys
it.  A fusion takes the ``op_name`` of the convolution or dot it holds
with the most FLOPs, else of its root; so a weight gradient fused with
its AdamW update counts as backward.  An instruction the compiler left
without an ``op_name`` takes that of its operand in the latest phase.

The readers take a :class:`benchlib.harness.Reading` with ``trace``,
``program`` (the step's module name), ``scopes`` (from :func:`scopes`)
and ``program_spans`` (from :func:`load_spans`), and return ``None``
where what they read is not there.
"""

from __future__ import annotations

import collections
import re
import statistics

from benchlib import hlo
from benchlib import trace as T

SPAN_PREFIX = "repro."
STEP = "repro.train.step"
BATCH = "repro.train.batch"
PUT = "repro.train.put"
DISPATCH = "repro.train.dispatch"
WAIT = "repro.train.wait"
READBACK = "repro.train.readback"
CHECKPOINT = "repro.train.checkpoint"
#: the spans a step holds, in the order it opens them
STEP_PARTS = (BATCH, PUT, DISPATCH, WAIT, READBACK, CHECKPOINT)

_OP_NAME_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_ROOT_RE = re.compile(r"^\s*ROOT\s+%?([\w.\-]+)\s*=")
_PART_RE = re.compile(r"deepcam\.(\w+)")


# --------------------------------------------------------------------------
# the phase scopes, from the compiled HLO text
# --------------------------------------------------------------------------

def phase_of(op_name: str) -> str:
    """``optimizer``, ``backward``, ``forward`` or ``other``."""
    if "train.optimizer" in op_name:
        return "optimizer"
    if "transpose(" in op_name:
        return "backward"
    if "train.grad" in op_name:
        return "forward"
    return "other"


def part_of(op_name: str) -> str:
    """The model part an ``op_name`` names (``deepcam.stage3`` ->
    ``stage3``), or ``""``."""
    m = _PART_RE.search(op_name)
    return m.group(1) if m else ""


def _roots(text: str) -> dict[str, str]:
    """Computation name -> the name of its root instruction."""
    roots, cur = {}, None
    for line in text.splitlines():
        hm = hlo._HEADER_RE.match(line)
        if hm and " = " not in line.split("->")[0]:
            cur = hm.group(2)
            continue
        m = _ROOT_RE.match(line)
        if m and cur is not None:
            roots[cur] = m.group(1)
    return roots


def _op_name(op: hlo.Instr) -> str:
    m = _OP_NAME_RE.search(op.attrs)
    return m.group(1) if m else ""


def op_names(text: str) -> dict[str, str]:
    """Instruction name (no ``%``) -> the ``op_name`` it is judged by, for
    every instruction that is not inside a fusion."""
    comps = hlo.parse_module(text)
    roots = _roots(text)
    fused = {c for comp in comps.values() for op in comp.values()
             if op.opcode == "fusion" for c in hlo._called(op.attrs)}

    def heaviest(cname: str, seen: set) -> tuple[float, str]:
        """(FLOPs, op_name) of the heaviest convolution or dot in a fused
        computation and the fusions it calls."""
        best = (0.0, "")
        if cname in seen:
            return best
        seen.add(cname)
        comp = comps.get(cname, {})
        for op in comp.values():
            if op.opcode == "convolution":
                cand = (hlo.conv_flops(op, comp), _op_name(op))
            elif op.opcode == "dot":
                cand = (hlo.dot_flops(op, comp), _op_name(op))
            elif op.opcode == "fusion":
                cand = max((heaviest(c, seen) for c in hlo._called(op.attrs)),
                           default=best)
            else:
                continue
            if cand[0] > best[0]:
                best = cand
        return best

    out: dict[str, str] = {}
    for cname, comp in comps.items():
        if cname in fused:
            continue
        own: dict[str, str] = {}
        for op in comp.values():
            name = _op_name(op)
            if op.opcode == "fusion":
                called = hlo._called(op.attrs)
                heavy = max((heaviest(c, set()) for c in called),
                            default=(0.0, ""))[1]
                root = next((comps[c][roots[c]] for c in called
                             if c in roots and roots[c] in comps.get(c, {})),
                            None)
                name = heavy or (_op_name(root) if root else "") or name
            own[op.name] = name
        out.update(_inherit(comp, own))
    return out


_RANK = {"other": 0, "forward": 1, "backward": 2, "optimizer": 3}


def _inherit(comp: dict[str, hlo.Instr], own: dict[str, str]
             ) -> dict[str, str]:
    """``own``, where an instruction the compiler made without an
    ``op_name`` (a layout copy, a rewritten weight gradient) takes that of
    its operand in the latest phase, found the same way: an instruction
    cannot run before what it reads."""
    out: dict[str, str] = {}
    for start in comp:
        stack = [(start, False)]
        while stack:                        # each operand before its users
            n, expanded = stack.pop()
            if n in out or n not in comp:
                continue
            if own[n]:
                out[n] = own[n]
            elif not expanded:
                stack.append((n, True))
                stack.extend((o, False) for o in comp[n].operands
                             if o not in out)
            else:
                out[n] = max((out.get(o, "") for o in comp[n].operands),
                             key=lambda v: _RANK[phase_of(v)] if v else -1,
                             default="")
    return out


def scopes(text: str) -> dict[str, str]:
    """Instruction name (no ``%``) -> its phase (:func:`phase_of`); empty
    where no instruction carries the step's ``train.grad`` scope (a
    program without the scopes, or an executable compiled from one: JAX's
    compilation cache leaves ``op_name`` out of its key by default)."""
    names = op_names(text)
    if not any("train.grad" in v for v in names.values()):
        return {}
    return {k: phase_of(v) for k, v in names.items()}


# --------------------------------------------------------------------------
# the host spans, from the trace
# --------------------------------------------------------------------------

def load_spans(path: str) -> list[tuple[float, float, str]]:
    """The program's host spans in an ``.xplane.pb``, by start: the
    ``/host:CPU`` events whose names start with ``repro.``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    out.sort()
    return out


def _whole(spans, window, name: str) -> list[tuple[float, float, str]]:
    lo, hi = window
    return [s for s in spans if s[2] == name and s[0] >= lo and s[1] <= hi]


def step_parts(r) -> list[dict[str, float]] | None:
    """Per ``repro.train.step`` span whole in the window: nanoseconds of
    each part it holds (:data:`STEP_PARTS`, summed where a part recurs)
    and ``self``, the step less its parts."""
    spans = r.get("program_spans")
    if not spans:
        return None
    out = []
    for s, e, _ in _whole(spans, r.trace.window, STEP):
        parts = dict.fromkeys(STEP_PARTS, 0.0)
        for cs, ce, n in spans:
            if n in parts and cs >= s and ce <= e:
                parts[n] += ce - cs
        parts["self"] = (e - s) - sum(parts.values())
        out.append(parts)
    return out or None


def host_split_ms(r) -> dict[str, float] | None:
    """The median over whole steps of each part, and of the step, in ms."""
    steps = step_parts(r)
    if not steps:
        return None
    out = {k: statistics.median(p[k] for p in steps) / 1e6
           for k in (*STEP_PARTS, "self")}
    out[STEP] = statistics.median(sum(p.values()) for p in steps) / 1e6
    return out


def host_input_ms(r) -> float | None:
    """The median over steps whole in the window of the time in the
    step's ``batch`` and ``put`` spans, in ms."""
    steps = step_parts(r)
    if not steps or not any(p[BATCH] or p[PUT] for p in steps):
        return None
    return statistics.median(p[BATCH] + p[PUT] for p in steps) / 1e6


def host_dispatch_ms(r) -> float | None:
    """The median length of the ``dispatch`` spans whole in the window,
    in ms."""
    spans = _whole(r.get("program_spans") or [], r.trace.window, DISPATCH)
    if not spans:
        return None
    return statistics.median(e - s for s, e, _ in spans) / 1e6


# --------------------------------------------------------------------------
# device time by phase
# --------------------------------------------------------------------------

def step_ops(r) -> tuple[list[T.Op], list[float], int] | None:
    """The ops of the step program's executions that lie whole in the
    window on the first chip, their self times, and the number of those
    executions."""
    if not r.trace.devices or not r.get("program"):
        return None
    dev = r.trace.devices[min(r.trace.devices)]
    lo, hi = r.trace.window
    runs = [(s, e) for s, e, m in dev.modules
            if m.startswith(r.program + "(") and s >= lo and e <= hi]
    if not runs:
        return None
    ops, j = [], 0
    for s, e in runs:                            # both sorted by start
        while j < len(dev.ops) and dev.ops[j].start < s:
            j += 1
        while j < len(dev.ops) and dev.ops[j].start < e:
            o = dev.ops[j]
            if o.end <= e and o.module.startswith(r.program + "("):
                ops.append(o)
            j += 1
    return ops, T.self_times(ops), len(runs)


def device_ms_by(r, key: dict[str, str]) -> dict[str, float] | None:
    """Device self time per execution of the step, in ms, summed by
    ``key[instruction]`` (``"other"`` where the instruction is not in
    ``key``)."""
    got = step_ops(r)
    if got is None or not key:
        return None
    ops, self_t, n = got
    out: dict[str, float] = collections.defaultdict(float)
    for o, t in zip(ops, self_t):
        out[key.get(o.name.lstrip("%"), "other")] += t / n / 1e6
    return dict(out)


def _phase_ms(r, phase: str) -> float | None:
    by = device_ms_by(r, r.get("scopes") or {})
    return None if by is None else by.get(phase, 0.0)


def forward_ms(r):
    """Device ms per step of the forward (``train.grad``, no
    ``transpose(``)."""
    return _phase_ms(r, "forward")


def backward_ms(r):
    """Device ms per step of the backward (``transpose(`` under
    ``train.grad``)."""
    return _phase_ms(r, "backward")


def optimizer_ms(r):
    """Device ms per step of the update (``train.optimizer``)."""
    return _phase_ms(r, "optimizer")
