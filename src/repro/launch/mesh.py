"""Production mesh construction (task spec §multi-pod dry-run).

A FUNCTION, not a module constant — importing this module never touches jax
device state, so tests/benches see the real (1-device) CPU while the
dry-run, which sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before any jax import, sees 512 placeholder devices.

Every mesh here has ``Auto`` axes: the sharding rules
(``repro.distributed.sharding``) place arrays with ``NamedSharding`` and
let the partitioner propagate, which ``jax.make_mesh``'s default
``Explicit`` axes refuse for the model's gathers.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *,
              devices=None) -> jax.sharding.Mesh:
    """Arbitrary mesh for tests / hillclimb variants."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto, devices=devices)


def make_host_mesh(devices=None) -> jax.sharding.Mesh:
    """Data-parallel over ``devices`` (default: every device this
    process has)."""
    devices = list(jax.devices() if devices is None else devices)
    return make_mesh((len(devices), 1), ("data", "model"), devices=devices)


def devices_per_pod(mesh: jax.sharding.Mesh) -> int:
    """Chips per pod (for the ICI/DCN split in collective analysis)."""
    if "pod" in mesh.shape:
        return int(mesh.devices.size // mesh.shape["pod"])
    return int(mesh.devices.size)
