"""Mesh helpers shared by the stencil stack's pooled and elastic paths.

``factor_slot_mesh`` adds a slot (batch) axis to a spatial mesh for
``api.pooled_target`` and the tuner's search space; ``reshard`` places
host arrays under a possibly different mesh for the resilience driver's
elastic restore.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding


def factor_slot_mesh(
    mesh: Mesh,
    slots: int = 1,
    axis: str = "slot",
    devices=None,
) -> Mesh:
    """Extend a spatial ``mesh`` with a leading slot axis of size
    ``slots`` factored out of the device inventory.

    The slot axis carries a *batch* dimension (pooled serving slots, or
    ensemble members), not an array dimension: collectives keep binding
    the spatial axis names, so each slot block of ``slots × spatial``
    devices runs the exact solo exchange pattern.  ``slots == 1`` reuses
    the mesh's own devices (shard_map over ``(slot=1, *spatial)`` — the
    vmap inside still pools the batch); ``slots > 1`` takes the first
    ``slots * spatial`` devices of ``devices`` (default: the process
    inventory), slot-major, so slot block 0 is the original mesh's
    device prefix.
    """
    import numpy as np

    if int(slots) != slots or slots < 1:
        raise ValueError(f"slots must be a positive integer, got {slots!r}")
    slots = int(slots)
    if axis in mesh.axis_names:
        raise ValueError(
            f"slot axis {axis!r} collides with mesh axes "
            f"{tuple(mesh.axis_names)}"
        )
    spatial_shape = tuple(mesh.shape[a] for a in mesh.axis_names)
    names = (axis,) + tuple(mesh.axis_names)
    if slots == 1:
        devs = mesh.devices.reshape((1,) + spatial_shape)
        return Mesh(devs, names)
    n_spatial = int(np.prod(spatial_shape))
    pool = list(devices) if devices is not None else jax.devices()
    need = slots * n_spatial
    if need > len(pool):
        raise ValueError(
            f"slot axis of {slots} over a {n_spatial}-rank spatial mesh "
            f"needs {need} devices, have {len(pool)}"
        )
    devs = np.array(pool[:need]).reshape((slots,) + spatial_shape)
    return Mesh(devs, names)


def reshard(arrays, mesh: Optional[Mesh], specs) -> tuple:
    """Place host arrays onto ``mesh`` with one ``PartitionSpec`` each —
    the elastic-restore path: state checkpointed under one mesh
    factorization is ``device_put`` under a *different* one (or none),
    so a killed 4-rank run resumes onto 2 ranks unchanged.  ``mesh`` is
    ``None`` for a single-device restore (plain device_put)."""
    arrays = tuple(arrays)
    if mesh is None:
        return tuple(jax.device_put(a) for a in arrays)
    if len(arrays) != len(tuple(specs)):
        raise ValueError(
            f"{len(arrays)} arrays for {len(tuple(specs))} partition specs"
        )
    return tuple(
        jax.device_put(a, NamedSharding(mesh, spec))
        for a, spec in zip(arrays, specs)
    )
