"""Application characterization from compiled HLO (paper §II-B, Table II).

The paper collects, per GPU kernel via Nsight Compute: run time, FLOPs per
precision (+ Tensor Core), and bytes at each memory level (L1/L2/HBM).  The
XLA analogue of a "kernel" is a top-level *fusion* (or standalone op) in the
optimized, partitioned HLO module.  This module parses ``compiled.as_text()``
and produces one :class:`KernelRecord` per executed kernel with:

* FLOPs, split by dtype class (``bf16`` → MXU, ``f32`` → VPU — the paper's
  Tensor-Core vs CUDA-core split),
* ``hbm_bytes`` — operands/results crossing the fusion boundary (the paper's
  ``dram__bytes``),
* ``vmem_bytes`` — traffic of every op *inside* the fusion (the paper's
  L1/L2 ``lts__t_bytes`` analogue: intermediate values stream through
  VMEM/VREGs),
* execution count (``while`` bodies are multiplied by their
  ``known_trip_count`` — NB: XLA's own ``cost_analysis()`` counts loop bodies
  **once**, so for scanned-layer models this analyzer is the only source of
  correct totals; we cross-check the two in tests),
* collective records with algorithm-corrected wire bytes and an ICI/DCN
  split (cross-pod groups) for the sharding-aware roofline term.

Zero-AI kernels (paper Table III) fall out of the same walk: records whose
FLOP count is zero (convert / copy / transpose / reshape / gather /
collective fusions).
"""

from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict
from typing import Iterable

import numpy as np

# --------------------------------------------------------------------------
# Shapes and dtypes
# --------------------------------------------------------------------------

DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u2": 1, "u4": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3": 1, "f8e5m2": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1,
    "token": 0, "opaque": 0,
}

# dtype → roofline ceiling class (paper: FP64/FP32/FP16/TC → here VPU/MXU)
def dtype_class(dtype: str) -> str:
    if dtype in ("bf16", "f16"):
        return "bf16"
    if dtype.startswith("f8") or dtype in ("s8", "u8", "s4", "u4", "s2", "u2"):
        return "int8"
    return "f32"


@dataclasses.dataclass(frozen=True)
class Shape:
    dtype: str
    dims: tuple[int, ...]

    @property
    def elements(self) -> int:
        return int(np.prod(self.dims)) if self.dims else 1

    @property
    def bytes(self) -> int:
        return self.elements * DTYPE_BYTES.get(self.dtype, 4)


def _parse_shape_expr(expr: str) -> list[Shape]:
    """Parse a result-type expression, flattening tuples: ``(f32[2]{0}, s32[])``."""
    shapes: list[Shape] = []
    for m in re.finditer(r"([a-z][a-z0-9]*)\[([0-9,]*)\]", expr):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in DTYPE_BYTES:
            continue
        dim_t = tuple(int(d) for d in dims.split(",") if d) if dims else ()
        shapes.append(Shape(dtype, dim_t))
    return shapes


# --------------------------------------------------------------------------
# HLO text parsing
# --------------------------------------------------------------------------

@dataclasses.dataclass
class HloOp:
    name: str
    opcode: str
    shapes: list[Shape]            # result shape(s), tuple flattened
    operands: list[str]            # operand op names (same computation)
    attrs: str                     # raw attribute tail
    op_name: str                   # JAX metadata op_name ("" if absent)

    @property
    def result_bytes(self) -> int:
        return sum(s.bytes for s in self.shapes)


@dataclasses.dataclass
class HloComputation:
    name: str
    ops: dict[str, HloOp] = dataclasses.field(default_factory=dict)
    root: str = ""


@dataclasses.dataclass
class HloModule:
    computations: dict[str, HloComputation]
    entry: str


_HEADER_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')


def _split_type_op(rhs: str) -> tuple[str, str, list[str], str]:
    """Split ``type opcode(operands), attrs`` with nesting-aware scanning."""
    depth = 0
    type_end = -1
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
    type_expr = rhs[:type_end]
    rest = rhs[type_end + 1:]
    paren = rest.find("(")
    if paren < 0:
        return type_expr, rest.strip(), [], ""
    opcode = rest[:paren].strip()
    # balanced operand list
    depth = 0
    end = len(rest)
    for i in range(paren, len(rest)):
        if rest[i] in "([{":
            depth += 1
        elif rest[i] in ")]}":
            depth -= 1
            if depth == 0:
                end = i
                break
    operand_str = rest[paren + 1:end]
    attrs = rest[end + 1:].lstrip(", ")
    # split top-level commas
    operands: list[str] = []
    depth = 0
    cur = []
    for ch in operand_str:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            operands.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if cur:
        tail = "".join(cur).strip()
        if tail:
            operands.append(tail)
    # operand entries are "%name" or "type %name"; keep the trailing %name
    names = []
    for o in operands:
        m = re.search(r"%([\w.\-]+)\s*$", o)
        names.append(m.group(1) if m else o)
    return type_expr, opcode, names, attrs


def parse_hlo_module(text: str) -> HloModule:
    computations: dict[str, HloComputation] = {}
    entry = ""
    current: HloComputation | None = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if not line.strip():
            continue
        if line.strip() == "}":
            current = None
            continue
        hm = _HEADER_RE.match(line)
        if hm and " = " not in line.split("->")[0]:
            current = HloComputation(hm.group(2))
            computations[current.name] = current
            if hm.group(1):
                entry = current.name
            continue
        if current is None or " = " not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        name = lhs.strip()
        is_root = name.startswith("ROOT ")
        if is_root:
            name = name[5:].strip()
        name = name.lstrip("%")
        if is_root:
            current.root = name
        type_expr, opcode, operands, attrs = _split_type_op(rhs)
        if not opcode:
            continue
        mo = _OPNAME_RE.search(attrs)
        current.ops[name] = HloOp(
            name=name,
            opcode=opcode,
            shapes=_parse_shape_expr(type_expr),
            operands=operands,
            attrs=attrs,
            op_name=mo.group(1) if mo else "",
        )
    if not entry and computations:
        entry = next(reversed(computations))
    return HloModule(computations, entry)


# --------------------------------------------------------------------------
# Replica groups (for collective wire-byte modeling)
# --------------------------------------------------------------------------

def parse_replica_groups(attrs: str) -> list[list[int]]:
    """Parse explicit ``{{0,1},{2,3}}`` or iota ``[2,4]<=[8]`` replica groups."""
    m = re.search(r"replica_groups=\{(\{[^=]*\})\}", attrs)
    if m:
        return [
            [int(x) for x in g.split(",") if x.strip()]
            for g in re.findall(r"\{([0-9, ]*)\}", m.group(1))
        ]
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?",
                  attrs)
    if m:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        reshape_dims = [int(x) for x in m.group(3).split(",")]
        arr = np.arange(int(np.prod(reshape_dims)))
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            arr = arr.reshape(reshape_dims).transpose(perm).reshape(-1)
        return arr.reshape(n_groups, group_size).tolist()
    return []


# --------------------------------------------------------------------------
# FLOP / byte model per op
# --------------------------------------------------------------------------

_ELEMENTWISE_1 = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "remainder", "atan2", "floor", "ceil", "round-nearest-afz",
    "round-nearest-even", "sign", "clamp",
}
_TRANSCENDENTAL = {
    "exponential", "exponential-minus-one", "log", "log-plus-one", "tanh",
    "sqrt", "rsqrt", "cbrt", "power", "logistic", "sine", "cosine", "tan",
    "erf", "expm1", "log1p",
}
_ZERO_FLOP = {
    "copy", "copy-start", "copy-done", "transpose", "reshape", "bitcast",
    "bitcast-convert", "broadcast", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "gather", "iota", "reverse",
    "convert", "select", "compare", "and", "or", "xor", "not", "shift-left",
    "shift-right-logical", "shift-right-arithmetic", "is-finite",
    "rng-bit-generator", "rng-get-and-update-state", "partition-id",
    "replica-id", "real", "imag", "after-all", "optimization-barrier",
    "reduce-precision", "stochastic-convert", "sort", "set-dimension-size",
}
_FREE = {"parameter", "constant", "tuple", "get-tuple-element", "after-all"}
_COLLECTIVES = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "all-reduce-start", "all-gather-start",
    "collective-permute-start", "collective-broadcast", "ragged-all-to-all",
}
_ASYNC_DONE = {"all-reduce-done", "all-gather-done", "collective-permute-done",
               "async-done", "async-update"}

# wire-traffic multiplier (ring algorithms): bytes_on_slowest_link ≈ mult × payload
_COLL_MULT = {
    "all-reduce": lambda n: 2.0 * (n - 1) / n,
    "reduce-scatter": lambda n: (n - 1) / n,
    "all-gather": lambda n: (n - 1) / n,
    "all-to-all": lambda n: (n - 1) / n,
    "collective-permute": lambda n: 1.0,
    "collective-broadcast": lambda n: 1.0,
    "ragged-all-to-all": lambda n: (n - 1) / n,
}


def _dot_flops(op: HloOp, comp: HloComputation) -> float:
    out_elems = op.shapes[0].elements
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.attrs)
    lhs = comp.ops.get(op.operands[0]) if op.operands else None
    contract = 1
    if m and lhs and lhs.shapes:
        for d in m.group(1).split(","):
            if d.strip():
                contract *= lhs.shapes[0].dims[int(d)]
    return 2.0 * out_elems * contract


def _valid_taps(in_size: int, out_size: int, window: int, stride: int,
                pad_lo: int, lhs_dilate: int, rhs_dilate: int) -> int:
    """(output position, window tap) pairs of one spatial dim whose tap
    lands on an input element rather than on padding or a dilation hole.
    """
    pos = (np.arange(out_size)[:, None] * stride
           + np.arange(window)[None, :] * rhs_dilate - pad_lo)
    ok = ((pos >= 0) & (pos <= (in_size - 1) * lhs_dilate)
          & (pos % lhs_dilate == 0))
    return int(ok.sum())


def _window(attrs: str) -> dict[str, list] | None:
    m = re.search(r"window=\{([^}]*)\}", attrs)
    if not m:
        return None
    fields = dict(kv.split("=", 1) for kv in m.group(1).split())
    size = [int(x) for x in fields["size"].split("x")]

    def ints(key: str) -> list[int]:
        return ([int(x) for x in fields[key].split("x")] if key in fields
                else [1] * len(size))
    pad = ([[int(v) for v in p.split("_")] for p in fields["pad"].split("x")]
           if "pad" in fields else [[0, 0]] * len(size))
    return {"size": size, "stride": ints("stride"), "pad": pad,
            "lhs_dilate": ints("lhs_dilate"),
            "rhs_dilate": ints("rhs_dilate")}


def _conv_flops(op: HloOp, comp: HloComputation) -> float:
    """2 x output batch x output features x input features per group x
    the window taps that read input, summed over output positions.

    Taps that land on padding are not counted: the TPU compiler lowers
    some dots to convolutions whose window is mostly padding (a window of
    384 over a one-element input), where counting every tap would inflate
    the FLOPs by the window size.
    """
    out_elems = op.shapes[0].elements
    lhs = comp.ops.get(op.operands[0]) if op.operands else None
    rhs = comp.ops.get(op.operands[1]) if len(op.operands) > 1 else None
    if not (rhs and rhs.shapes):
        return 2.0 * out_elems
    m = re.search(r"dim_labels=([a-z0-9]+)_([a-z0-9]+)->([a-z0-9]+)",
                  op.attrs)
    win = _window(op.attrs)
    out_dims = op.shapes[0].dims
    if m and win and lhs and lhs.shapes:
        lhs_l, rhs_l, out_l = m.groups()
        taps, out_spatial = 1, 1
        for d in range(len(win["size"])):
            o = out_dims[out_l.index(str(d))]
            out_spatial *= o
            taps *= _valid_taps(
                lhs.shapes[0].dims[lhs_l.index(str(d))], o, win["size"][d],
                win["stride"][d], win["pad"][d][0], win["lhs_dilate"][d],
                win["rhs_dilate"][d])
        cin = rhs.shapes[0].dims[rhs_l.index("i")]
        return 2.0 * (out_elems / max(out_spatial, 1)) * cin * taps
    cout = 1
    if m:
        fpos = m.group(3).find("f")
        if 0 <= fpos < len(out_dims):
            cout = out_dims[fpos]
    return 2.0 * out_elems * rhs.shapes[0].elements / max(cout, 1)


_PEEL = {"convert", "copy", "bitcast", "bitcast-convert", "broadcast",
         "reshape", "transpose", "slice"}
_NARROW = {"f8e4m3": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4,
           "f64": 8}


def _narrower(a: str | None, b: str | None) -> str | None:
    if a is None:
        return b
    if b is None:
        return a
    return a if _NARROW.get(a, 9) <= _NARROW.get(b, 9) else b


def _peel_dtype(name: str, comp: HloComputation,
                param_dtypes: dict[int, str] | None,
                module: "HloModule | None" = None) -> str | None:
    """*Narrowest* float dtype along an operand's producer chain.

    XLA's CPU bf16 legalization lowers a bf16 matmul as
    ``convert(f32→bf16→f32)`` (often fused as ``convert_convert_fusion``)
    feeding an f32 dot — the compute is MXU/bf16 even though every visible
    dtype is f32.  Peeling tracks the narrowest float seen through convert/
    layout chains and *inside* single-input fusions, so FLOPs classify onto
    the ceiling the math actually uses.
    """
    seen: str | None = None
    for _ in range(12):
        src = comp.ops.get(name)
        if src is None:
            return seen
        cur = src.shapes[0].dtype if src.shapes else None
        if cur in _NARROW:
            seen = _narrower(seen, cur)
        if src.opcode == "parameter":
            if param_dtypes is not None and src.operands:
                try:
                    idx = int(src.operands[0])
                except ValueError:
                    idx = -1
                if idx in param_dtypes:
                    return _narrower(seen, param_dtypes[idx])
            return seen
        if src.opcode in _PEEL and src.operands:
            name = src.operands[0]
            continue
        if src.opcode == "fusion" and len(src.operands) == 1:
            # look inside convert/layout wrapper fusions for a bf16 hop
            if module is not None:
                called = _called_computation(src, module)
                if called is not None and called.root:
                    inner = called.ops.get(called.root)
                    hops = 0
                    while inner is not None and hops < 12:
                        dt = (inner.shapes[0].dtype if inner.shapes
                              else None)
                        if dt in _NARROW:
                            seen = _narrower(seen, dt)
                        if not inner.operands:
                            break
                        inner = called.ops.get(inner.operands[0])
                        hops += 1
            name = src.operands[0]
            continue
        return seen
    return seen


def _flop_dtype(op: HloOp, comp: HloComputation,
                param_dtypes: dict[int, str] | None = None,
                module: "HloModule | None" = None) -> str:
    """Ceiling class for an op's FLOPs, from its *input* dtype (MXU intake)."""
    for operand in op.operands[:2]:
        dt = _peel_dtype(operand, comp, param_dtypes, module)
        if dt is not None:
            return dtype_class(dt)
    return dtype_class(op.shapes[0].dtype) if op.shapes else "f32"


def _op_flops(op: HloOp, comp: HloComputation) -> float:
    oc = op.opcode
    if oc == "dot":
        return _dot_flops(op, comp)
    if oc == "convolution":
        return _conv_flops(op, comp)
    if oc in _ELEMENTWISE_1:
        return float(op.shapes[0].elements) if op.shapes else 0.0
    if oc in _TRANSCENDENTAL:
        # the paper counts SASS instructions; we count 1 FLOP/element and
        # cross-check totals against XLA's cost_analysis in tests.
        return float(op.shapes[0].elements) if op.shapes else 0.0
    if oc in ("reduce", "reduce-window", "select-and-scatter"):
        if op.operands:
            src = comp.ops.get(op.operands[0])
            if src and src.shapes:
                n = float(src.shapes[0].elements)
                if oc == "reduce-window":
                    m = re.search(r"window=\{size=([0-9x]+)", op.attrs)
                    if m:
                        n = float(op.shapes[0].elements) * float(
                            np.prod([int(x) for x in m.group(1).split("x")]))
                return n
        return float(op.shapes[0].elements) if op.shapes else 0.0
    if oc == "scatter":
        if len(op.operands) > 2:
            upd = comp.ops.get(op.operands[2])
            if upd and upd.shapes:
                return float(upd.shapes[0].elements)
        return 0.0
    return 0.0


def _op_bytes(op: HloOp, comp: HloComputation) -> int:
    """Operand + result bytes: traffic this op pushes through its level.

    ``dynamic-update-slice`` is modeled in place (XLA aliases the buffer):
    traffic = read + write of the *update slice*, not the whole buffer —
    loop-carried KV caches / stacked outputs would otherwise be counted at
    full size every iteration.
    """
    if op.opcode == "dynamic-update-slice" and len(op.operands) >= 2:
        upd = comp.ops.get(op.operands[1])
        if upd is not None:
            return 2 * upd.result_bytes
    total = op.result_bytes
    for name in op.operands:
        src = comp.ops.get(name)
        if src is not None:
            total += src.result_bytes
    return total


def _fusion_boundary_bytes(op: HloOp, comp: HloComputation,
                           called: "HloComputation | None") -> int:
    """HBM traffic across a fusion boundary, with in-place DUS discounts.

    If the fusion's root (or a root-tuple element) is a dynamic-update-slice
    whose destination is one of the fusion's own parameters with the same
    shape as the output, XLA updates that buffer in place: subtract the
    full-buffer read+write and charge 2x the update slice instead.
    """
    total = _op_bytes(op, comp)
    if called is None or not called.root:
        return total
    roots = [called.ops.get(called.root)]
    if roots[0] is not None and roots[0].opcode == "tuple":
        roots = [called.ops.get(n) for n in roots[0].operands]
    # parameter index → fusion operand result bytes
    param_bytes: dict[str, int] = {}
    for o in called.ops.values():
        if o.opcode == "parameter":
            param_bytes[o.name] = o.result_bytes
    for r in roots:
        if r is None or r.opcode != "dynamic-update-slice":
            continue
        dst = called.ops.get(r.operands[0]) if r.operands else None
        upd = called.ops.get(r.operands[1]) if len(r.operands) > 1 else None
        if dst is None or upd is None:
            continue
        if dst.opcode == "parameter" and dst.result_bytes == r.result_bytes:
            # drop full-buffer read (operand) + write (result); add slice r/w
            total -= 2 * r.result_bytes
            total += 2 * upd.result_bytes
    return max(total, 0)


# --------------------------------------------------------------------------
# Kernel / collective records
# --------------------------------------------------------------------------

@dataclasses.dataclass
class KernelRecord:
    """Per-kernel data of paper Table II, on XLA fusion granularity."""

    name: str
    opcode: str
    op_name: str                      # JAX-level provenance
    exec_count: int                   # while-trip multiplier
    flops_by_class: dict[str, float]  # ceiling class → FLOPs (one execution)
    hbm_bytes: int                    # fusion-boundary traffic (one execution)
    vmem_bytes: int                   # internal traffic (one execution)
    category: str                     # matmul|conv|elementwise|reduction|collective|zero-ai|...

    @property
    def flops(self) -> float:
        return sum(self.flops_by_class.values())

    @property
    def total_flops(self) -> float:
        return self.flops * self.exec_count

    @property
    def total_hbm_bytes(self) -> float:
        return float(self.hbm_bytes) * self.exec_count

    @property
    def total_vmem_bytes(self) -> float:
        return float(self.vmem_bytes) * self.exec_count

    @property
    def is_zero_ai(self) -> bool:
        return self.flops == 0.0

    def ai(self, level: str = "hbm") -> float:
        b = self.hbm_bytes if level == "hbm" else self.vmem_bytes
        return self.flops / b if b else math.inf


@dataclasses.dataclass
class CollectiveRecord:
    name: str
    opcode: str                       # canonical (no -start suffix)
    exec_count: int
    payload_bytes: int                # per-device shard payload (one execution)
    wire_bytes: float                 # algorithm-corrected bytes on the wire
    group_size: int
    cross_pod: bool

    @property
    def total_wire_bytes(self) -> float:
        return self.wire_bytes * self.exec_count


@dataclasses.dataclass
class ModuleAnalysis:
    kernels: list[KernelRecord]
    collectives: list[CollectiveRecord]

    # -- totals ------------------------------------------------------------
    @property
    def total_flops_by_class(self) -> dict[str, float]:
        out: dict[str, float] = defaultdict(float)
        for k in self.kernels:
            for cls, f in k.flops_by_class.items():
                out[cls] += f * k.exec_count
        return dict(out)

    @property
    def total_flops(self) -> float:
        return sum(self.total_flops_by_class.values())

    @property
    def total_hbm_bytes(self) -> float:
        return sum(k.total_hbm_bytes for k in self.kernels)

    @property
    def total_vmem_bytes(self) -> float:
        return sum(k.total_vmem_bytes for k in self.kernels)

    def collective_wire_bytes(self, cross_pod: bool | None = None) -> float:
        return sum(c.total_wire_bytes for c in self.collectives
                   if cross_pod is None or c.cross_pod == cross_pod)

    def zero_ai_census(self) -> dict[str, tuple[int, int]]:
        """Paper Table III: {zero-AI: (invocations, bytes), non-zero-AI: ...}."""
        z_inv = z_bytes = n_inv = n_bytes = 0
        for k in self.kernels:
            if k.is_zero_ai:
                z_inv += k.exec_count
                z_bytes += int(k.total_hbm_bytes)
            else:
                n_inv += k.exec_count
                n_bytes += int(k.total_hbm_bytes)
        return {"zero-AI": (z_inv, z_bytes), "non zero-AI": (n_inv, n_bytes)}


# --------------------------------------------------------------------------
# Module walk
# --------------------------------------------------------------------------

def _categorize(op: HloOp, comp: HloComputation,
                module: HloModule) -> str:
    oc = op.opcode
    if oc in _COLLECTIVES:
        return "collective"
    if oc == "fusion":
        called = _called_computation(op, module)
        if called is not None:
            cats = {_categorize(o, called, module) for o in called.ops.values()
                    if o.opcode not in _FREE}
            for pri in ("matmul", "conv", "collective", "reduction"):
                if pri in cats:
                    return pri
            if "elementwise" in cats:
                return "elementwise"
        return "zero-ai"
    if oc == "dot":
        return "matmul"
    if oc == "convolution":
        return "conv"
    if oc in ("reduce", "reduce-window", "select-and-scatter", "scatter"):
        return "reduction"
    if oc in _ELEMENTWISE_1 or oc in _TRANSCENDENTAL:
        return "elementwise"
    if oc in ("custom-call",):
        return "custom"
    return "zero-ai"


def _called_computation(op: HloOp, module: HloModule) -> HloComputation | None:
    m = re.search(r"(?:calls|to_apply)=%?([\w.\-]+)", op.attrs)
    if m:
        return module.computations.get(m.group(1))
    return None


def _trip_count(op: HloOp) -> int:
    m = re.search(r'known_trip_count\\?":\s*\{\\?"n\\?":\\?"(\d+)', op.attrs)
    return int(m.group(1)) if m else 1


def _operand_dtypes(op: HloOp, comp: HloComputation,
                    param_dtypes: dict[int, str] | None,
                    module: "HloModule | None" = None) -> dict[int, str]:
    """Peeled dtypes of a call-site's operands (for the callee's params)."""
    out: dict[int, str] = {}
    for i, name in enumerate(op.operands):
        dt = _peel_dtype(name, comp, param_dtypes, module)
        if dt is not None:
            out[i] = dt
    return out


def _fusion_internals(comp: HloComputation, module: HloModule,
                      depth: int = 0,
                      param_dtypes: dict[int, str] | None = None,
                      matmul_class: str | None = None
                      ) -> tuple[dict[str, float], int]:
    """Sum FLOPs-by-class and byte traffic of every op inside a fusion."""
    flops: dict[str, float] = defaultdict(float)
    vbytes = 0
    for op in comp.ops.values():
        if op.opcode in _FREE:
            continue
        if op.opcode == "fusion" and depth < 8:
            called = _called_computation(op, module)
            if called is not None:
                f2, b2 = _fusion_internals(
                    called, module, depth + 1,
                    _operand_dtypes(op, comp, param_dtypes, module),
                    matmul_class)
                for c, f in f2.items():
                    flops[c] += f
                vbytes += b2
                continue
        f = _op_flops(op, comp)
        if f:
            cls = _flop_dtype(op, comp, param_dtypes, module)
            if (cls == "f32" and matmul_class
                    and op.opcode in ("dot", "convolution")):
                cls = matmul_class      # policy default (see analyze_hlo_text)
            flops[cls] += f
        vbytes += _op_bytes(op, comp)
    return dict(flops), vbytes


def _async_payload_shapes(op: HloOp, comp: HloComputation) -> list[Shape]:
    """Output-only shapes of an async ``-start`` collective.

    XLA lowers ``all-reduce`` to an ``(operands..., results..., contexts...)``
    tuple-shaped ``-start`` op whose ``-done`` consumes the tuple; summing
    every tuple element double-counts the payload (the operand buffers ride
    along as aliases).  Strip the leading operand aliases — an exact prefix
    match against the operand shapes — plus any trailing scalar context
    slots (the u32[] tokens collective-permute-start carries), so each
    ``-start``/``-done`` pair contributes wire bytes exactly once.
    """
    shapes = list(op.shapes)
    operand_shapes: list[Shape] = []
    for name in op.operands:
        src = comp.ops.get(name)
        if src is not None:
            operand_shapes.extend(src.shapes)
    if (operand_shapes and len(shapes) > len(operand_shapes)
            and shapes[:len(operand_shapes)] == operand_shapes):
        shapes = shapes[len(operand_shapes):]
    while len(shapes) > 1 and not shapes[-1].dims:
        shapes.pop()
    return shapes


def _walk(comp: HloComputation, module: HloModule, multiplier: int,
          kernels: list[KernelRecord], collectives: list[CollectiveRecord],
          devices_per_pod: int, seen: set[str],
          matmul_class: str | None = None) -> None:
    for op in comp.ops.values():
        oc = op.opcode
        if oc in _FREE or oc in _ASYNC_DONE:
            continue
        if oc == "while":
            trips = _trip_count(op)
            body = re.search(r"body=%?([\w.\-]+)", op.attrs)
            if body and body.group(1) in module.computations:
                _walk(module.computations[body.group(1)], module,
                      multiplier * trips, kernels, collectives,
                      devices_per_pod, seen, matmul_class)
            continue
        if oc in ("call", "async-start"):
            called = _called_computation(op, module)
            if called is not None:
                _walk(called, module, multiplier, kernels, collectives,
                      devices_per_pod, seen, matmul_class)
            continue
        if oc == "conditional":
            # attribute the most expensive branch (upper bound)
            branches = re.findall(r"branch_computations=\{([^}]*)\}", op.attrs)
            names = []
            if branches:
                names = [b.strip().lstrip("%") for b in branches[0].split(",")]
            else:
                names = [b for b in
                         re.findall(r"(?:true|false)_computation=%?([\w.\-]+)",
                                    op.attrs)]
            if names and names[0] in module.computations:
                _walk(module.computations[names[0]], module, multiplier,
                      kernels, collectives, devices_per_pod, seen,
                      matmul_class)
            continue

        if oc in _COLLECTIVES:
            canonical = oc.removesuffix("-start")
            if oc.endswith("-start"):
                payload = sum(s.bytes
                              for s in _async_payload_shapes(op, comp))
            else:
                payload = op.result_bytes
            if canonical in ("reduce-scatter", "all-to-all"):
                # wire traffic keyed on the larger (input) side
                payload = max(payload, sum(
                    comp.ops[o].result_bytes for o in op.operands
                    if o in comp.ops))
            groups = parse_replica_groups(op.attrs)
            gsize = len(groups[0]) if groups else 1
            cross = any(
                len({d // devices_per_pod for d in g}) > 1 for g in groups
            ) if devices_per_pod else False
            mult = _COLL_MULT.get(canonical, lambda n: 1.0)(max(gsize, 2))
            collectives.append(CollectiveRecord(
                name=op.name, opcode=canonical, exec_count=multiplier,
                payload_bytes=payload, wire_bytes=payload * mult,
                group_size=gsize, cross_pod=cross))
            # the collective is also a zero-AI kernel occupying HBM traffic
            # (async starts: operand read + payload write, not the whole
            # aliased tuple — same exactly-once rule as the wire bytes)
            if oc.endswith("-start"):
                mem_bytes = payload + sum(
                    comp.ops[o].result_bytes for o in op.operands
                    if o in comp.ops)
            else:
                mem_bytes = _op_bytes(op, comp)
            kernels.append(KernelRecord(
                name=op.name, opcode=canonical, op_name=op.op_name,
                exec_count=multiplier, flops_by_class={},
                hbm_bytes=mem_bytes, vmem_bytes=mem_bytes,
                category="collective"))
            continue

        if oc == "fusion":
            called = _called_computation(op, module)
            if called is not None:
                flops, vbytes = _fusion_internals(
                    called, module, 0,
                    _operand_dtypes(op, comp, None, module), matmul_class)
                kernels.append(KernelRecord(
                    name=op.name, opcode="fusion", op_name=op.op_name,
                    exec_count=multiplier, flops_by_class=flops,
                    hbm_bytes=_fusion_boundary_bytes(op, comp, called),
                    vmem_bytes=vbytes,
                    category=_categorize(op, comp, module)))
                continue

        f = _op_flops(op, comp)
        cls = _flop_dtype(op, comp, None, module)
        if (cls == "f32" and matmul_class
                and oc in ("dot", "convolution")):
            cls = matmul_class
        flops = {cls: f} if f else {}
        b = _op_bytes(op, comp)
        kernels.append(KernelRecord(
            name=op.name, opcode=oc, op_name=op.op_name,
            exec_count=multiplier, flops_by_class=flops,
            hbm_bytes=b, vmem_bytes=b,
            category=_categorize(op, comp, module)))


def analyze_hlo_text(text: str, devices_per_pod: int = 0,
                     matmul_class: str | None = None) -> ModuleAnalysis:
    """Full application characterization of one compiled HLO module.

    ``matmul_class``: ceiling class to assume for dot/convolution FLOPs
    whose operand chains show no reduced-precision hop.  XLA's CPU bf16
    legalization can erase bf16 entirely (loop carries widened to f32), so
    for modules built under a known AMP policy the caller passes the policy
    dtype ("bf16" for O1/O2); genuinely narrow chains still classify
    themselves, and elementwise/softmax FLOPs keep their true (f32) class.
    On a TPU-backend module this parameter is unnecessary.
    """
    module = parse_hlo_module(text)
    kernels: list[KernelRecord] = []
    collectives: list[CollectiveRecord] = []
    if module.entry:
        _walk(module.computations[module.entry], module, 1, kernels,
              collectives, devices_per_pod, set(), matmul_class)
    return ModuleAnalysis(kernels, collectives)


def analyze_compiled(compiled, devices_per_pod: int = 0,
                     matmul_class: str | None = None) -> ModuleAnalysis:
    return analyze_hlo_text(compiled.as_text(), devices_per_pod,
                            matmul_class)
