"""Collective microbenchmarks: the ERT discipline applied to the wire.

Each benchmark times one collective primitive (all-reduce / all-gather /
reduce-scatter / all-to-all) over a ring of forced host devices across a
sweep of message sizes, exactly the way ``repro.kernels.ert`` times the
FMA chain and triad across working-set sizes.  The *wire* bytes of each
sample use the same algorithm-corrected ring formulas
``core/hlo_analysis.py`` applies to compiled collectives
(all-reduce ``2(n-1)/n``, all-gather/reduce-scatter/all-to-all
``(n-1)/n``), so the measured ceiling and the attributed traffic live in
the same unit.

Two legs mirror the ICI/DCN split:

* ``ici`` — the collective runs over the full device ring (one "pod");
* ``dcn`` — the devices are split into two "pods" and the collective
  runs over the pod axis only (the cross-pod leg
  ``distributed/compression.py`` optimizes).  On a forced-host ring both
  legs traverse the same silicon — the point is exercising the
  characterize→store→attribute discipline end to end, so a real
  multi-pod deployment only swaps the mesh (docs/DESIGN.md §18).

This module imports jax lazily: it is shipped to a *spawned* worker whose
initializer pins ``--xla_force_host_platform_device_count`` before the
first jax import (the same harness the sweep engine uses).
"""

from __future__ import annotations

from typing import Any

#: benchmarked collective primitives, in report order
OPS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all")
#: interconnect legs, fastest first (matches MachineSpec.interconnect)
LEGS = ("ici", "dcn")


def wire_bytes(op: str, payload_bytes: float, group_size: int) -> float:
    """Ring-algorithm wire bytes for one collective execution.

    Mirrors ``core/hlo_analysis._COLL_MULT`` (including the
    ``max(group_size, 2)`` floor) so measured ceilings divide the same
    quantity the HLO walk attributes.
    """
    n = max(group_size, 2)
    if op == "all_reduce":
        return 2.0 * (n - 1) / n * payload_bytes
    if op in ("all_gather", "reduce_scatter", "all_to_all"):
        return (n - 1) / n * payload_bytes
    return float(payload_bytes)


def payload_bytes(op: str, elems_per_device: int, group_size: int,
                  itemsize: int = 4) -> float:
    """Payload of one collective, in the HLO walk's convention.

    all-reduce keys on the (replicated) result, all-gather on its n×
    output, reduce-scatter / all-to-all on the larger (input) side.
    """
    if op == "all_gather":
        return float(group_size * elems_per_device * itemsize)
    return float(elems_per_device * itemsize)


def _collective_fns(n_devices: int, leg: str):
    """{op: jitted collective over the leg's mesh axis} + the group size.

    ``ici`` runs over the full ring; ``dcn`` splits the ring into two
    pods and runs over the pod axis (group size 2).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    if leg == "dcn":
        if n_devices % 2:
            raise ValueError(f"dcn leg needs an even device count, "
                             f"got {n_devices}")
        mesh = jax.make_mesh((2, n_devices // 2), ("pod", "x"))
        axis, gsize = "pod", 2
        in_spec = P("pod")
    else:
        mesh = jax.make_mesh((n_devices,), ("x",))
        axis, gsize = "x", n_devices
        in_spec = P("x")

    def wrap(body, out_spec):
        # check_rep=False: replication inference for collectives varies
        # across jax versions; the outputs here are structurally correct
        fn = shard_map(body, mesh=mesh, in_specs=(in_spec,),
                       out_specs=out_spec, check_rep=False)
        return jax.jit(fn)

    fns = {
        "all_reduce": wrap(lambda x: lax.psum(x, axis), P()),
        "all_gather": wrap(lambda x: lax.all_gather(x, axis, tiled=True),
                           P()),
        "reduce_scatter": wrap(lambda x: lax.psum_scatter(x, axis,
                                                          tiled=True),
                               in_spec),
        "all_to_all": wrap(lambda x: lax.all_to_all(x, axis, 0, 0,
                                                    tiled=True),
                           in_spec),
    }
    return fns, gsize, jnp


def measure_collectives(n_devices: int, sizes: tuple[int, ...],
                        iters: int = 3, warmup: int = 1,
                        legs: tuple[str, ...] = LEGS
                        ) -> list[dict[str, Any]]:
    """Time every (leg × op × size) sample on this process's devices.

    ``sizes`` are per-device elements (float32); each must be divisible
    by the group size so tiled reduce-scatter / all-to-all lower cleanly.
    Returns one row per sample: ``{leg, op, group_size, elems,
    payload_bytes, wire_bytes, t_s}`` with ``t_s`` the min-of-samples
    wall time (ceiling discipline: noise only ever adds time).
    """
    import time

    import jax

    if jax.device_count() < n_devices:
        raise RuntimeError(
            f"collective characterization needs {n_devices} devices but "
            f"this process has {jax.device_count()} — run through the "
            "sweep engine's worker harness (it pins the XLA host-device "
            "count), not inline")
    rows: list[dict[str, Any]] = []
    for leg in legs:
        fns, gsize, jnp = _collective_fns(n_devices, leg)
        for op in OPS:
            fn = fns[op]
            for elems in sizes:
                if elems % max(gsize, 1):
                    continue
                x = jnp.ones((n_devices * elems,), jnp.float32)
                out = None
                for _ in range(max(warmup, 1)):
                    out = fn(x)
                jax.block_until_ready(out)
                best = float("inf")
                for _ in range(max(iters, 1)):
                    t0 = time.perf_counter()
                    out = fn(x)
                    jax.block_until_ready(out)
                    best = min(best, time.perf_counter() - t0)
                pay = payload_bytes(op, elems, gsize)
                rows.append({
                    "leg": leg, "op": op, "group_size": gsize,
                    "elems": elems, "payload_bytes": pay,
                    "wire_bytes": wire_bytes(op, pay, gsize),
                    "t_s": best,
                })
    return rows


def fit_ceiling(samples: list[tuple[float, float]]
                ) -> tuple[float, float]:
    """(bytes_per_s, latency_s) from (wire_bytes, seconds) samples.

    Least-squares fit of ``t = latency + wire / bw`` — the classic
    alpha-beta collective model.  Degenerate fits (noise producing a
    non-positive slope) fall back to the best observed throughput with
    zero latency, so the stored ceiling is never nonsense.
    """
    if not samples:
        raise ValueError("no samples to fit")
    n = len(samples)
    sx = sum(w for w, _ in samples)
    sy = sum(t for _, t in samples)
    sxx = sum(w * w for w, _ in samples)
    sxy = sum(w * t for w, t in samples)
    denom = n * sxx - sx * sx
    slope = (n * sxy - sx * sy) / denom if denom else 0.0
    intercept = (sy - slope * sx) / n
    if slope <= 0:
        return max(w / t for w, t in samples if t > 0), 0.0
    return 1.0 / slope, max(intercept, 0.0)
