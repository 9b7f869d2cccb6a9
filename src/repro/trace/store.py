"""Append-only JSONL results store for measured trace runs.

One line per run, schema-versioned (``schema_version``) so records written
by older code stay readable as the format grows (automated collection +
persistence workflow in the spirit of arXiv 2009.02449).  Run metadata
binds every record to its provenance: git SHA, host fingerprint, machine
model, config name and mesh — enough to answer "what changed?" when
``repro.trace.compare`` flags a regression between two commits.

The store is deliberately boring: plain JSONL, append-only, corrupt lines
skipped on read (a crashed writer never poisons history), records from a
*newer* schema skipped with a warning instead of mis-parsed.  Appends are
durable (flush + fsync) and self-healing: a torn final line left by a
crashed writer is repaired before the next record lands, so one crash
costs at most its own record, never a neighbour's.
"""

from __future__ import annotations

import dataclasses
import json
import os
import platform
import sys
import time
import uuid
import warnings
from typing import Any, Iterable, Mapping

from repro.trace.collector import PhaseMeasurement

SCHEMA_VERSION = 1

# phase-payload metric keys every record carries (compare iterates these)
PHASE_METRICS = ("wall_s", "achieved_flops_per_s", "pct_of_roofline",
                 "bound_overlap_s", "bound_serial_s")


#: the checkout this package sits in (``src/repro/trace`` -> its root)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _read(path: str) -> str:
    with open(path) as f:
        return f.read().strip()


def git_sha(repo_root: str | None = None) -> str:
    """HEAD commit of the checkout at ``repo_root`` (default: the one
    this package sits in).

    Read from the checkout's own ``.git`` rather than by running ``git``:
    the measuring process starts no other process.  ``.git`` is a
    directory, or in a worktree a file whose ``gitdir:`` line names the
    worktree's git directory; branches then live in the directory its
    ``commondir`` names.  Nothing above ``repo_root`` is looked at: a
    tree without its own ``.git`` (an export, an unpacked archive) is
    ``"unknown"``, never the HEAD of a repository around it.
    """
    dot = os.path.join(repo_root or REPO_ROOT, ".git")
    try:
        git = dot
        if os.path.isfile(dot):
            line = _read(dot)
            if not line.startswith("gitdir: "):
                return "unknown"
            git = os.path.join(os.path.dirname(dot), line[len("gitdir: "):])
        head = _read(os.path.join(git, "HEAD"))
        if not head.startswith("ref: "):
            return head
        ref = head[len("ref: "):]
        common = git
        if os.path.isfile(os.path.join(git, "commondir")):
            common = os.path.join(git, _read(os.path.join(git, "commondir")))
        for d in (git, common):
            if os.path.isfile(os.path.join(d, ref)):
                return _read(os.path.join(d, ref))
        with open(os.path.join(common, "packed-refs")) as f:
            for line in f:
                if line.rstrip().endswith(" " + ref):
                    return line.split()[0]
    except OSError:
        pass
    return "unknown"


def host_fingerprint() -> dict[str, str]:
    """Where the measurement ran (cross-host comparisons need a warning).

    Reads the devices, so it initialises JAX's backend: call it from the
    process that measures, never from a parent about to start workers.
    """
    import jax
    devices = jax.devices()
    return {
        "host": platform.node(),
        "platform": platform.platform(),
        "python": sys.version.split()[0],
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind,
        "device_count": str(len(devices)),
    }


@dataclasses.dataclass
class TraceRecord:
    """One measured run of one config: the unit of storage and comparison."""

    schema_version: int
    run_id: str
    timestamp: float                 # unix seconds
    git_sha: str
    config: str
    machine: str                     # MachineSpec.name the %s are against
    mesh: dict[str, int]             # axis name -> size ({} = single device)
    host: dict[str, str]
    phases: dict[str, dict[str, Any]]   # phase name -> metric payload
    meta: dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_json(self) -> str:
        # no sort_keys: phase insertion order IS the step order (fwd→bwd→opt)
        # and the timeline re-renders from it
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "TraceRecord":
        """Tolerant constructor: unknown keys dropped, missing keys defaulted
        (older minor revisions of the same schema stay loadable)."""
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in fields}
        kw.setdefault("schema_version", 0)
        kw.setdefault("run_id", "")
        kw.setdefault("timestamp", 0.0)
        kw.setdefault("git_sha", "unknown")
        kw.setdefault("config", "")
        kw.setdefault("machine", "")
        kw.setdefault("mesh", {})
        kw.setdefault("host", {})
        kw.setdefault("phases", {})
        return cls(**kw)


def phase_payload(m: PhaseMeasurement, top_kernels: int = 8
                  ) -> dict[str, Any]:
    """Serializable per-phase metrics (the record's unit cell).

    Besides the top-``top_kernels`` kernel payloads, the cell keeps three
    whole-phase launch totals computed over *every* kernel (the paper's
    Table III census, per stored phase): total launches, zero-FLOP
    launches, and scatter launches — the ``repro.obs`` advisor mines
    them without re-lowering anything.
    """
    t = m.terms
    launches = sum(k.exec_count for k in m.kernels)
    zero_ai = sum(k.exec_count for k in m.kernels if not k.flops)
    scatter = sum(k.exec_count for k in m.kernels
                  if "scatter" in k.name.lower())
    return {
        "launches": launches,
        "zero_ai_launches": zero_ai,
        "scatter_launches": scatter,
        "wall_s": m.wall_s,
        "iters": m.iters,
        "achieved_flops_per_s": m.achieved_flops_per_s,
        "pct_of_roofline": m.pct_of_roofline,
        "bound_overlap_s": m.bound_overlap_s,
        "bound_serial_s": m.bound_serial_s,
        "compute_s": t.compute_s,
        "memory_s": t.memory_s,
        "collective_s": t.collective_s,
        "dominant": m.dominant,
        "flops": m.flops,
        "xla_flops": m.xla_flops,
        "xla_transcendentals": m.xla_transcendentals,
        "hbm_bytes": m.hbm_bytes,
        "vmem_bytes": m.vmem_bytes,
        # interconnect level (third roofline hierarchy level): algorithm-
        # corrected wire bytes split by pod locality + their time bounds
        "ici_bytes": t.ici_wire_bytes,
        "dcn_bytes": t.dcn_wire_bytes,
        "net_bytes": t.ici_wire_bytes + t.dcn_wire_bytes,
        "ici_bound_s": t.collective_ici_s,
        "dcn_bound_s": t.collective_dcn_s,
        "kernels": [
            {"name": k.name, "category": k.category,
             "exec_count": k.exec_count,
             "flops": k.flops, "hbm_bytes": k.hbm_bytes,
             "vmem_bytes": k.vmem_bytes,
             "ai_hbm": k.ai_hbm, "bound_s": k.bound_s,
             "attributed_s": k.attributed_s,
             "achieved_flops_per_s": k.achieved_flops_per_s,
             "pct_of_roofline": k.pct_of_roofline}
            for k in m.kernels[:top_kernels]
        ],
    }


def record_from_payloads(config: str,
                         phases: Mapping[str, Mapping[str, Any]],
                         machine: str,
                         mesh: Mapping[str, int] | None = None,
                         meta: Mapping[str, Any] | None = None) -> TraceRecord:
    """TraceRecord from already-serialized phase payloads.

    The construction path shared by ``record_from_phases`` (live
    measurements) and ``repro.sweep`` (cached / analytical payloads):
    provenance stamping happens in exactly one place.
    """
    return TraceRecord(
        schema_version=SCHEMA_VERSION,
        run_id=uuid.uuid4().hex[:12],
        timestamp=time.time(),
        git_sha=git_sha(),
        config=config,
        machine=machine,
        mesh=dict(mesh or {}),
        host=host_fingerprint(),
        phases={name: dict(p) for name, p in phases.items()},
        meta=dict(meta or {}))


def record_from_phases(config: str,
                       measurements: Mapping[str, PhaseMeasurement],
                       machine: str,
                       mesh: Mapping[str, int] | None = None,
                       meta: Mapping[str, Any] | None = None,
                       top_kernels: int = 8) -> TraceRecord:
    return record_from_payloads(
        config,
        {name: phase_payload(m, top_kernels)
         for name, m in measurements.items()},
        machine=machine, mesh=mesh, meta=meta)


class TraceStore:
    """Append-only JSONL store of :class:`TraceRecord` lines."""

    def __init__(self, path: str):
        self.path = path

    @property
    def _store_kind(self) -> str:
        """Store name fault specs target (``torn_tail:trace`` etc.)."""
        base = os.path.basename(self.path)
        return base[:-len(".jsonl")] if base.endswith(".jsonl") else base

    def append(self, rec: TraceRecord) -> TraceRecord:
        from repro.resilience import faults
        from repro.resilience.jsonl import repair_jsonl_tail
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        repair_jsonl_tail(self.path)
        line = rec.to_json()
        spec = faults.active_plan().fires("torn_tail", self._store_kind)
        if spec is not None:
            # simulate a writer crash mid-append: half the payload, no
            # newline, durably on disk — then die (well, raise)
            with open(self.path, "a") as f:
                f.write(line[:max(1, len(line) // 2)])
                f.flush()
                os.fsync(f.fileno())
            raise faults.InjectedFault(
                f"injected {spec.render()}: torn append to {self.path}")
        with open(self.path, "a") as f:
            f.write(line + "\n")
            f.flush()
            os.fsync(f.fileno())
        return rec

    def records(self, config: str | None = None) -> list[TraceRecord]:
        """All readable records, oldest first; corrupt lines and
        newer-schema records are skipped (with a warning), never fatal."""
        if not os.path.exists(self.path):
            return []
        out: list[TraceRecord] = []
        with open(self.path) as f:
            for i, line in enumerate(f):
                if not line.strip():
                    continue
                try:
                    d = json.loads(line)
                except json.JSONDecodeError:
                    warnings.warn(f"{self.path}:{i+1}: corrupt line skipped")
                    continue
                if d.get("schema_version", 0) > SCHEMA_VERSION:
                    warnings.warn(
                        f"{self.path}:{i+1}: schema "
                        f"{d.get('schema_version')} > {SCHEMA_VERSION} "
                        "(written by newer code) — skipped")
                    continue
                rec = TraceRecord.from_dict(d)
                if config is None or rec.config == config:
                    out.append(rec)
        return out

    def last(self, config: str | None = None, n: int = 1
             ) -> list[TraceRecord]:
        """Last ``n`` records (oldest→newest among those returned)."""
        recs = self.records(config)
        return recs[-n:] if n else []

    def run(self, run_id: str) -> TraceRecord | None:
        for rec in self.records():
            if rec.run_id == run_id or rec.run_id.startswith(run_id):
                return rec
        return None

    def configs(self) -> list[str]:
        seen: dict[str, None] = {}
        for rec in self.records():
            seen.setdefault(rec.config)
        return list(seen)

    def records_where(self, predicate) -> list["TraceRecord"]:
        """Readable records matching ``predicate(rec)``, oldest first
        (e.g. ``lambda r: r.meta.get("sweep") == name``)."""
        return [rec for rec in self.records() if predicate(rec)]


def iter_jsonl(path: str) -> Iterable[dict]:
    """Raw dict view of a store file (debugging / ad-hoc analysis)."""
    with open(path) as f:
        for line in f:
            if line.strip():
                try:
                    yield json.loads(line)
                except json.JSONDecodeError:
                    continue
