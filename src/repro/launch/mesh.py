"""Production mesh builders.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import and then calls these.

Single pod:  (16, 16)      axes ("data", "model")     = 256 chips (v5e pod)
Multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") = 512 chips
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_local_mesh(*, data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    if pod:
        shape, axes = (pod, data, model), ("pod", "data", "model")
    else:
        shape, axes = (data, model), ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def mesh_info(mesh) -> dict:
    return {
        "axes": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "n_devices": mesh.devices.size,
    }


def collective_tiers(mesh, client_axes) -> tuple:
    """``CostModel.mesh_tiers`` for a concrete mesh: the client axes the
    round step psums over, outer->inner, with their sizes —
    ``(("pod", 2), ("data", 16))`` on the multi-pod production mesh.  The
    one place the cost model's tier layout is derived from a mesh, so byte
    accounting cannot drift from the mesh actually launched."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    missing = [a for a in client_axes if a not in sizes]
    if missing:
        raise ValueError(
            f"client axes {missing} not on mesh axes {tuple(sizes)}"
        )
    return tuple((a, int(sizes[a])) for a in client_axes)
