"""Heat, ``Eq(u.dt, u.laplace)``, through the Devito-like frontend, with
its plain reference: ``u' = u + dt * laplacian(u)``, zero boundary.

Configuration keys: ``grid``, ``space_order``, ``dt``, ``boundary``
(only ``"zero"``), ``dtype`` (only ``"float32"``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import reference

LEVELS = 1  # time levels the update reads


def _check(config: dict) -> None:
    if config["boundary"] != "zero" or config["dtype"] != "float32":
        raise ValueError(
            f"heat reference: boundary 'zero' and dtype 'float32' only, got "
            f"{config['boundary']!r} / {config['dtype']!r}"
        )


def program(config: dict):
    """The program under test, built through the frontend as a user would."""
    from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction

    _check(config)
    grid = Grid(shape=tuple(config["grid"]))
    u = TimeFunction(name="u", grid=grid, space_order=config["space_order"])
    return Operator(Eq(u.dt, u.laplace), dt=config["dt"],
                    boundary=config["boundary"]).program


def radius(config: dict) -> int:
    return config["space_order"] // 2


def state_maker(config: dict, sharding):
    """A jitted ``key -> (u,)``: one standard-normal level, made on the
    device (sharded as the layout wants) in float32."""
    shape = tuple(config["grid"])
    make = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32),
                   out_shardings=sharding)
    return lambda key: (make(key),)


def advancer(config: dict):
    """``advance(levels, steps, dtype)``: the newest level after ``steps``
    plain steps from ``levels`` (oldest to newest), zero boundary."""
    _check(config)
    order = int(config["space_order"])

    def advance(levels, steps, dtype):
        dt = jnp.asarray(config["dt"], dtype)

        def body(_, u):
            return u + dt * reference.laplacian(u, order, dtype)

        return jax.lax.fori_loop(0, steps, body, levels[-1].astype(dtype))

    return advance
