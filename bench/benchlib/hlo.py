"""Operations and bytes of each instruction of a compiled program, from the
shapes in its optimized HLO text (``compiled.as_text()``).

An instruction that runs on the device is one of a computation that is
not a fusion's body: the entry, a loop's body or condition, a called
computation.  Its FLOPs are those of the convolutions and dots it holds,
its own or inside the fusion it calls.  Its bytes are those of its
operands and results that live in HBM (memory space 0): a shape whose
layout names another space, ``S(1)`` for VMEM, does not cross HBM.

The convolution and dot rules are those of ``repro.core.hlo_analysis``
(2 x output batch x output features x input features x the window taps
that read input, padding and dilation holes left out), copied so that no
change to the program changes the yardstick.
"""

from __future__ import annotations

import dataclasses
import math
import re

import numpy as np

DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u2": 1, "u4": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\](\{[^}]*\})?")
_HEADER_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")


@dataclasses.dataclass(frozen=True)
class Shape:
    dtype: str
    dims: tuple[int, ...]
    space: int = 0                 # memory space: 0 is HBM

    @property
    def bytes(self) -> int:
        return math.prod(self.dims) * DTYPE_BYTES[self.dtype]


def parse_shapes(expr: str) -> list[Shape]:
    out = []
    for m in _SHAPE_RE.finditer(expr):
        if m.group(1) not in DTYPE_BYTES:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        sp = re.search(r"S\((\d+)\)", m.group(3) or "")
        out.append(Shape(m.group(1), dims, int(sp.group(1)) if sp else 0))
    return out


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    shapes: list[Shape]
    operands: list[str]
    attrs: str


def _split(rhs: str) -> tuple[str, str, list[str], str]:
    """``type opcode(operands), attrs`` -> its four parts."""
    depth, type_end = 0, -1
    for i, ch in enumerate(rhs):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            type_end = i
            break
    if type_end < 0:
        return rhs, "", [], ""
    type_expr, rest = rhs[:type_end], rhs[type_end + 1:]
    paren = rest.find("(")
    if paren < 0:
        return type_expr, rest.strip(), [], ""
    opcode = rest[:paren].strip()
    depth, end = 0, len(rest)
    for i in range(paren, len(rest)):
        if rest[i] in "([{":
            depth += 1
        elif rest[i] in ")]}":
            depth -= 1
            if depth == 0:
                end = i
                break
    names = re.findall(r"%([\w.\-]+)", rest[paren + 1:end])
    return type_expr, opcode, names, rest[end + 1:].lstrip(", ")


def parse_module(text: str) -> dict[str, dict[str, Instr]]:
    """Computation name -> instruction name -> :class:`Instr`."""
    comps: dict[str, dict[str, Instr]] = {}
    cur = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if not line.strip():
            continue
        if line.strip() == "}":
            cur = None
            continue
        hm = _HEADER_RE.match(line)
        if hm and " = " not in line.split("->")[0]:
            cur = comps.setdefault(hm.group(2), {})
            continue
        if cur is None or " = " not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        name = lhs.strip()
        if name.startswith("ROOT "):
            name = name[5:].strip()
        name = name.lstrip("%")
        type_expr, opcode, operands, attrs = _split(rhs)
        if opcode:
            cur[name] = Instr(name, opcode, parse_shapes(type_expr),
                              operands, attrs)
    return comps


# --------------------------------------------------------------------------
# FLOPs of one convolution or dot
# --------------------------------------------------------------------------

def _valid_taps(in_size, out_size, window, stride, pad_lo, lhs_dil, rhs_dil):
    pos = (np.arange(out_size)[:, None] * stride
           + np.arange(window)[None, :] * rhs_dil - pad_lo)
    ok = ((pos >= 0) & (pos <= (in_size - 1) * lhs_dil)
          & (pos % lhs_dil == 0))
    return int(ok.sum())


def _window(attrs: str) -> dict | None:
    m = re.search(r"window=\{([^}]*)\}", attrs)
    if not m:
        return None
    fields = dict(kv.split("=", 1) for kv in m.group(1).split())
    size = [int(x) for x in fields["size"].split("x")]

    def ints(key):
        return ([int(x) for x in fields[key].split("x")] if key in fields
                else [1] * len(size))
    pad = ([[int(v) for v in p.split("_")] for p in fields["pad"].split("x")]
           if "pad" in fields else [[0, 0]] * len(size))
    return {"size": size, "stride": ints("stride"), "pad": pad,
            "lhs_dilate": ints("lhs_dilate"), "rhs_dilate": ints("rhs_dilate")}


def conv_flops(op: Instr, comp: dict[str, Instr]) -> float:
    lhs = comp.get(op.operands[0]) if op.operands else None
    rhs = comp.get(op.operands[1]) if len(op.operands) > 1 else None
    out = op.shapes[0]
    m = re.search(r"dim_labels=([a-z0-9]+)_([a-z0-9]+)->([a-z0-9]+)",
                  op.attrs)
    # no window: a convolution without spatial dimensions
    win = _window(op.attrs) or {"size": []}
    if not (lhs and rhs and m):
        raise ValueError(f"cannot read the convolution {op.name}")
    lhs_l, rhs_l, out_l = m.groups()
    taps, out_spatial = 1, 1
    for d in range(len(win["size"])):
        o = out.dims[out_l.index(str(d))]
        out_spatial *= o
        taps *= _valid_taps(lhs.shapes[0].dims[lhs_l.index(str(d))], o,
                            win["size"][d], win["stride"][d],
                            win["pad"][d][0], win["lhs_dilate"][d],
                            win["rhs_dilate"][d])
    # the kernel's "i" dimension is the input features of one group
    cin = rhs.shapes[0].dims[rhs_l.index("i")]
    return 2.0 * (math.prod(out.dims) / max(out_spatial, 1)) * cin * taps


def dot_flops(op: Instr, comp: dict[str, Instr]) -> float:
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.attrs)
    lhs = comp.get(op.operands[0]) if op.operands else None
    contract = 1
    if m and lhs:
        for d in m.group(1).split(","):
            if d.strip():
                contract *= lhs.shapes[0].dims[int(d)]
    return 2.0 * math.prod(op.shapes[0].dims) * contract


# --------------------------------------------------------------------------
# per instruction
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Cost:
    flops: float
    hbm_bytes: float
    opcodes: frozenset       # opcodes run, fusion bodies included


def _called(attrs: str) -> list[str]:
    return re.findall(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)",
                      attrs)


def costs(text: str) -> dict[str, Cost]:
    """Instruction name (no ``%``) -> :class:`Cost`, for every instruction
    of the program that is not inside a fusion."""
    comps = parse_module(text)
    fused = set()
    for comp in comps.values():
        for op in comp.values():
            if op.opcode == "fusion":
                fused.update(_called(op.attrs))

    memo: dict[str, tuple[float, frozenset]] = {}

    def inside(cname: str) -> tuple[float, frozenset]:
        if cname in memo:
            return memo[cname]
        memo[cname] = (0.0, frozenset())        # guards recursion
        comp = comps.get(cname, {})
        flops, codes = 0.0, set()
        for op in comp.values():
            codes.add(op.opcode)
            if op.opcode == "convolution":
                flops += conv_flops(op, comp)
            elif op.opcode == "dot":
                flops += dot_flops(op, comp)
            elif op.opcode == "fusion":
                for c in _called(op.attrs):
                    f, k = inside(c)
                    flops += f
                    codes |= k
        memo[cname] = (flops, frozenset(codes))
        return memo[cname]

    out: dict[str, Cost] = {}
    for cname, comp in comps.items():
        if cname in fused:
            continue
        for op in comp.values():
            flops, codes = 0.0, {op.opcode}
            if op.opcode == "convolution":
                flops = conv_flops(op, comp)
            elif op.opcode == "dot":
                flops = dot_flops(op, comp)
            elif op.opcode == "fusion":
                for c in _called(op.attrs):
                    f, k = inside(c)
                    flops += f
                    codes |= k
            nbytes = sum(s.bytes for s in op.shapes if s.space == 0)
            for o in op.operands:
                src = comp.get(o)
                if src is not None:
                    nbytes += sum(s.bytes for s in src.shapes if s.space == 0)
            out[op.name] = Cost(flops, float(nbytes), frozenset(codes))
    return out
