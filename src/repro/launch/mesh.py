"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run entrypoint must set XLA_FLAGS
before anything initializes the backend.
"""
from __future__ import annotations

import jax
import numpy as np


def _make_auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over host/CPU devices for tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count set by the caller)."""
    return _make_auto_mesh((data, model), ("data", "model"))


def replica_slices(mesh, n_replicas: int):
    """Device slices for a replica-sharded serving cluster: replica ``r``
    gets the ``data``-axis slice ``r % data_size`` of ``mesh`` — a list of
    the devices spanning the remaining (``model``/``pod``) axes.  Replicas
    on distinct slices dispatch their device work concurrently; when
    ``n_replicas`` exceeds the data-axis size, slices wrap (replicas then
    share devices — still correct, just serialized)."""
    if "data" not in mesh.axis_names:
        raise ValueError(f"mesh has no 'data' axis: {mesh.axis_names}")
    di = mesh.axis_names.index("data")
    # move the data axis to the front, flatten the rest into one slice axis
    dev = np.moveaxis(mesh.devices, di, 0)
    dev = dev.reshape(dev.shape[0], -1)
    return [list(dev[r % dev.shape[0]]) for r in range(n_replicas)]


def dp_axes(mesh) -> tuple:
    """Mesh axes carrying the batch dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]
