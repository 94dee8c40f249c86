"""A configuration's layout on the chips: the ``Target`` it compiles for
and the sharding its state lives in.

Cells name no backend: ``Target()`` plus only the mesh and strategy the
layout needs, so the path is the program's choice.
"""
from __future__ import annotations

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding


def target_and_sharding(config: dict, devices):
    from repro import api
    from repro.core.passes.decompose import make_strategy_2d

    chips = int(config["chips"])
    if len(devices) < chips:
        raise RuntimeError(f"the configuration needs {chips} chips, found {len(devices)}")
    mesh_shape = tuple(config.get("mesh") or (1,))
    if chips == 1:
        return api.Target(), SingleDeviceSharding(devices[0])
    if len(mesh_shape) != 2 or mesh_shape[0] * mesh_shape[1] != chips:
        raise ValueError(f"mesh {mesh_shape} does not hold {chips} chips as a 2-D grid")
    mesh = Mesh(np.array(devices[:chips]).reshape(mesh_shape), ("x", "y"))
    target = api.Target(mesh=mesh, strategy=make_strategy_2d(mesh_shape))
    return target, NamedSharding(mesh, P("x", "y"))
