"""Tiny stand-ins for the cells' configurations, for runs on the CPU."""
import jax

from bench import registry, run

SIZES = {
    "heat2d-16384.loop": [256, 256],
    "heat2d-65536.2x2": [256, 256],
}


def config(cell: str) -> dict:
    spec = registry.spec()
    cfg = registry.data("configs", registry.workload(cell, spec)["config"])
    cfg["grid"] = SIZES[cell]
    return cfg


def execute(cell: str, seed: int = 2**33 + 5, seconds: float = 1.0,
            trace: bool = False, hook=None, devices=None) -> dict:
    return run.execute(cell, seed, seconds, trace, devices or jax.devices(),
                       config=config(cell), driver_hook=hook)
