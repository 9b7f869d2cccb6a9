"""Interconnect characterization: measured collective ceilings → TuneStore.

The driver half of the net subsystem (docs/DESIGN.md §18).  The worker
half (``repro.net.collectives``) times ring collectives over forced host
devices; this module runs it through the same :class:`SupervisedPool` +
``_worker_init`` harness the sweep engine uses (XLA's device count is
fixed at jax import, so the measurement always happens in a spawned
worker), fits the alpha-beta model per (leg, op), and persists the
ceilings machine-keyed in the tune store right next to the kernel
ceilings:

* one record per (leg, op): ``kernel="net_<leg>_<op>"``, shape
  ``[n_devices]`` — the raw evidence;
* one summary record per leg: ``kernel="net_ici"`` / ``"net_dcn"``,
  shape ``[0]`` (the "any shape" sentinel, same convention as the ERT
  ceiling records) — what :func:`machine_with_net` folds into a
  :class:`~repro.core.machine.MachineSpec`.

Store discipline matches ``repro.tune``: a second characterization of
the same machine key is a pure store hit — zero re-timing — unless
``force=True``.

Import-light: jax, the pool and the stores all load inside functions
(worker processes import this module before fixing their device count).
"""

from __future__ import annotations

from typing import Any, Mapping

from repro.net.collectives import LEGS, OPS, measure_collectives

#: backend tag net records carry in their tune keys
NET_BACKEND = "collective"
#: dtype every collective sample uses (float32 payloads)
NET_DTYPE = "float32"
#: shape sentinel for the per-leg summary records ("any shape")
SUMMARY_SHAPE = (0,)

#: per-device float32 elements per sample (divisible by ring sizes 2..8)
DEFAULT_SIZES = (1024, 8192, 65536, 262144)
SMOKE_SIZES = (1024, 16384, 131072)
DEFAULT_DEVICES = 8


def summary_key(leg: str, machine: str) -> str:
    from repro.tune.store import tune_key
    return tune_key(f"net_{leg}", SUMMARY_SHAPE, NET_DTYPE, machine,
                    backend=NET_BACKEND)


def net_ceilings(machine: Any, store: Any = None
                 ) -> dict[str, dict[str, Any]] | None:
    """Stored empirical interconnect ceilings for one machine key.

    ``{"ici": {bytes_per_s, latency_s, n_devices, key, timestamp,
    git_sha}, "dcn": {...}}`` — or ``None`` when either leg is missing
    (consumers fall back to the datasheet numbers, exactly like an
    untuned kernel falls back to its default config).
    """
    from repro.tune.store import _as_store
    name = machine if isinstance(machine, str) else machine.name
    store = _as_store(store)
    out: dict[str, dict[str, Any]] = {}
    for leg in LEGS:
        rec = store.get(summary_key(leg, name))
        if rec is None:
            return None
        out[leg] = {
            "bytes_per_s": float(rec.params.get("bytes_per_s", rec.metric)),
            "latency_s": float(rec.params.get("latency_s", 0.0)),
            "n_devices": int(rec.params.get("n_devices", 0)),
            "key": rec.key,
            "timestamp": rec.timestamp,
            "git_sha": rec.git_sha,
        }
    return out


def machine_with_net(machine: Any, store: Any = None):
    """The machine spec, with stored net ceilings folded in when present.

    The one resolution rule every attribution path shares (sweep engine,
    ``Session.record``): measured interconnect roofs when the store has
    them, datasheet otherwise — never a mix of legs.
    """
    from repro.core.machine import get_machine
    spec = get_machine(machine) if isinstance(machine, str) else machine
    ceil = net_ceilings(spec.name, store)
    if not ceil:
        return spec
    return spec.with_empirical_net(
        {leg: c["bytes_per_s"] for leg, c in ceil.items()},
        {leg: c["latency_s"] for leg, c in ceil.items()})


# --------------------------------------------------------------------------
# measurement driver
# --------------------------------------------------------------------------

def _measure_job(n_devices: int, sizes: tuple, iters: int, warmup: int
                 ) -> dict:
    """Worker entry (picklable, module scope): measure, return rows."""
    import traceback
    try:
        rows = measure_collectives(n_devices, tuple(sizes),
                                   iters=iters, warmup=warmup)
    except Exception:
        return {"error": traceback.format_exc()}
    return {"rows": rows}


def _datasheet_bw(machine: str) -> dict[str, float]:
    from repro.core.machine import MACHINES
    spec = MACHINES.get(machine)
    if spec is None:
        return {}
    return {lv.name: lv.bytes_per_s for lv in spec.interconnect}


def _fit_rows(rows: list[Mapping[str, Any]]
              ) -> dict[tuple[str, str], dict[str, Any]]:
    """(leg, op) → fitted ceiling + the samples behind it."""
    from repro.net.collectives import fit_ceiling
    grouped: dict[tuple[str, str], list[Mapping[str, Any]]] = {}
    for r in rows:
        grouped.setdefault((r["leg"], r["op"]), []).append(r)
    out: dict[tuple[str, str], dict[str, Any]] = {}
    for key, rs in grouped.items():
        bw, lat = fit_ceiling([(r["wire_bytes"], r["t_s"]) for r in rs])
        out[key] = {"bytes_per_s": bw, "latency_s": lat,
                    "group_size": int(rs[0]["group_size"]),
                    "n_samples": len(rs),
                    "wall_s": max(float(r["t_s"]) for r in rs)}
    return out


def _persist(fits: Mapping[tuple[str, str], Mapping[str, Any]],
             machine: str, n_devices: int, sizes: tuple,
             store: Any) -> dict[str, dict[str, Any]]:
    """Per-op + per-leg summary records into the tune store (one atomic
    write), returning the fresh :func:`net_ceilings` view."""
    from repro.tune.store import make_record
    datasheet = _datasheet_bw(machine)
    recs = {}
    per_leg: dict[str, dict[str, Any]] = {}
    for (leg, op), fit in sorted(fits.items()):
        rec = make_record(
            kernel=f"net_{leg}_{op}", shape=(n_devices,), dtype=NET_DTYPE,
            machine=machine, backend=NET_BACKEND,
            params={"leg": leg, "op": op,
                    "bytes_per_s": fit["bytes_per_s"],
                    "latency_s": fit["latency_s"],
                    "group_size": fit["group_size"],
                    "sizes": [int(s) for s in sizes]},
            wall_s=fit["wall_s"], metric=fit["bytes_per_s"],
            metric_name="wire_bytes_per_s", default_wall_s=0.0,
            default_metric=datasheet.get(leg, 0.0),
            n_candidates=fit["n_samples"])
        recs[rec.key] = rec.to_dict()
        per_leg.setdefault(leg, {})[op] = {
            "bytes_per_s": fit["bytes_per_s"],
            "latency_s": fit["latency_s"]}
    for leg, ops in per_leg.items():
        # the *ceiling* of a leg is the best throughput any collective
        # achieved over it (ERT discipline: roofs are attainable maxima),
        # with the smallest fitted launch latency — an optimistic bound,
        # so attributed collective time stays a lower bound on the truth
        bw = max(o["bytes_per_s"] for o in ops.values())
        lat = min(o["latency_s"] for o in ops.values())
        rec = make_record(
            kernel=f"net_{leg}", shape=SUMMARY_SHAPE, dtype=NET_DTYPE,
            machine=machine, backend=NET_BACKEND,
            params={"leg": leg, "bytes_per_s": bw, "latency_s": lat,
                    "n_devices": n_devices, "ops": ops,
                    "sizes": [int(s) for s in sizes]},
            wall_s=0.0, metric=bw, metric_name="wire_bytes_per_s",
            default_wall_s=0.0, default_metric=datasheet.get(leg, 0.0),
            n_candidates=len(ops))
        recs[rec.key] = rec.to_dict()
    store.put_many(recs)
    ceil = net_ceilings(machine, store)
    assert ceil is not None
    return ceil


def characterize_net(machine: Any = "cpu-host", *,
                     n_devices: int = DEFAULT_DEVICES,
                     sizes: tuple | None = None,
                     iters: int = 3, warmup: int = 1,
                     store: Any = None, force: bool = False,
                     smoke: bool = False, deadline_s: float = 900.0,
                     inline: bool = False) -> dict[str, Any]:
    """Measure (or fetch) this host's interconnect ceilings.

    Returns ``{machine, n_devices, ceilings, ops, cached, store}``.
    ``cached=True`` means both per-leg summaries were already stored
    under this machine key and **nothing was re-timed**.  ``inline=True``
    measures in this process (the caller must already have enough
    devices — tests force the count before importing jax); the default
    spawns one supervised worker that pins
    ``--xla_force_host_platform_device_count`` first, exactly like a
    sweep point.  On an accelerator the measurement always runs here,
    over the real devices: this process holds them.
    """
    from repro.tune.store import _as_store
    name = machine if isinstance(machine, str) else machine.name
    store = _as_store(store)

    if not force:
        cached = net_ceilings(name, store)
        if cached is not None:
            return {"machine": name, "n_devices": n_devices,
                    "ceilings": cached, "ops": {}, "cached": True,
                    "store": store.path}

    if sizes is None:
        sizes = SMOKE_SIZES if smoke else DEFAULT_SIZES
    sizes = tuple(int(s) for s in sizes)
    if n_devices % 2:
        raise ValueError(f"n_devices must be even (the dcn leg splits the "
                         f"ring into two pods), got {n_devices}")

    from repro.resilience.watchdog import holds_accelerator
    if inline or holds_accelerator():
        # on an accelerator this process holds the chips: measure here
        rows = measure_collectives(n_devices, sizes, iters=iters,
                                   warmup=warmup)
    else:
        from repro.resilience.watchdog import SupervisedPool
        from repro.sweep.engine import _worker_init
        with SupervisedPool(_measure_job, 1, init=_worker_init,
                            initargs=(n_devices,),
                            deadline_s=deadline_s) as pool:
            outcomes = pool.run(
                [("net", (n_devices, sizes, iters, warmup))])
        out = outcomes["net"]
        value = out.value if out.ok else None
        if value is None or value.get("error"):
            err = (value or {}).get("error") or out.error or out.kind
            raise RuntimeError(
                f"collective characterization failed ({out.kind}): {err}")
        rows = value["rows"]

    fits = _fit_rows(rows)
    ceilings = _persist(fits, name, n_devices, sizes, store)
    ops = {}
    for (leg, op), fit in sorted(fits.items()):
        ops.setdefault(leg, {})[op] = {
            "bytes_per_s": fit["bytes_per_s"],
            "latency_s": fit["latency_s"]}
    return {"machine": name, "n_devices": n_devices, "ceilings": ceilings,
            "ops": ops, "cached": False, "store": store.path}
