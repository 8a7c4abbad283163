"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state -- the dry-run sets
``--xla_force_host_platform_device_count=512`` before any jax import and
only then calls this.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one v5e pod's worth of chips) or 2x16x16 (two pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(data: int = 1, model: int = 1):
    """Small ``(data, model)`` mesh for CPU tests.

    Built from an explicit device subset: ``jax.make_mesh`` insists on
    consuming EVERY addressable device, which made a (1, 1) debug mesh
    impossible under ``--xla_force_host_platform_device_count=8`` -- the
    exact configuration the mesh-vs-single-device identity tests need."""
    import numpy as np

    n = data * model
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"mesh ({data}, {model}) needs {n} devices, have "
            f"{len(devices)} -- on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before jax starts")
    return jax.sharding.Mesh(np.asarray(devices[:n]).reshape(data, model),
                             ("data", "model"))


def parse_mesh(spec: str | None):
    """``"DATAxMODEL"`` (e.g. ``"2x4"``) -> debug mesh; None/"" -> None."""
    if not spec:
        return None
    parts = spec.lower().split("x")
    if len(parts) != 2:
        raise ValueError(f"mesh spec must be DATAxMODEL, got {spec!r}")
    return make_debug_mesh(data=int(parts[0]), model=int(parts[1]))
