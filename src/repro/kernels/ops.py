"""Jit'd wrappers around the Pallas kernels — the stable public surface.

Each op takes halo-inclusive inputs and returns the core, mirroring the
post-swap calling convention of the lowering (halos are filled by dmp/comm
upstream).

``interpret`` defaults to ``None`` — resolved through the same
:func:`repro.kernels.default_interpret` the compile surface uses for
``Target.pallas_interpret``, so ops-level callers and compiled programs
agree on one flag source (native Pallas on a TPU, the interpreter on any
other backend).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import ir
from repro.core.builder import build_apply
from repro.core.dialects import stencil
from repro.core.fd import laplacian_star, radius
from repro.kernels import default_interpret
from repro.kernels.stencil_apply import run_apply_pallas


def _star_apply_ir(coeffs: Dict[Tuple[int, ...], float], core: tuple, halo: tuple):
    """Build a one-operand apply op computing the weighted-star sum."""
    rank = len(core)
    func = ir.FuncOp("star", [])
    operand_bounds = stencil.Bounds(
        tuple(-h for h in halo), tuple(c + h for c, h in zip(core, halo))
    )
    # fabricate a block argument typed as the halo-grown temp
    holder = ir.Block([stencil.TempType(operand_bounds)])
    rb = stencil.Bounds.from_shape(core)

    def body(b, u):
        acc = None
        for off, c in sorted(coeffs.items()):
            term = u.at(*off) * float(c)
            acc = term if acc is None else acc + term
        return acc

    apply_op = build_apply(func.body, [holder.args[0]], rb, body)
    return apply_op, operand_bounds


def star_stencil(
    x,
    coeffs: Dict[Tuple[int, ...], float],
    halo: Tuple[int, ...],
    tile=None,
    interpret: Optional[bool] = None,
):
    """Apply a star/box stencil with static coefficients via Pallas."""
    if interpret is None:
        interpret = default_interpret()
    core = tuple(s - 2 * h for s, h in zip(x.shape, halo))
    apply_op, ob = _star_apply_ir(coeffs, core, halo)
    rb = stencil.Bounds.from_shape(core)
    (out,) = run_apply_pallas(
        apply_op, [x], [ob.lb], rb, tile=tile, interpret=interpret
    )
    return out


@partial(jax.jit, static_argnames=("order", "halo", "interpret"))
def laplacian(
    x, order: int = 2, halo: int = None, interpret: Optional[bool] = None  # type: ignore[assignment]
):
    h = halo if halo is not None else radius(order)
    star = laplacian_star(x.ndim, order)
    return star_stencil(x, star, (h,) * x.ndim, interpret=interpret)


@partial(jax.jit, static_argnames=("alpha", "order", "interpret"))
def heat_step(u, alpha: float, order: int = 2, interpret: Optional[bool] = None):
    """Fused u + alpha∇²u (one kernel, one VMEM round-trip)."""
    h = radius(order)
    star = dict(laplacian_star(u.ndim, order))
    star = {k: alpha * v for k, v in star.items()}
    center = tuple([0] * u.ndim)
    star[center] = star.get(center, 0.0) + 1.0
    return star_stencil(u, star, (h,) * u.ndim, interpret=interpret)


def wave_step(
    u_t, u_tm1_core, c2dt2: float, order: int = 2, interpret: Optional[bool] = None
):
    """2 u_t - u_{t-1} + c²dt² ∇²u_t; u_t halo-inclusive, u_{t-1} core."""
    h = radius(order)
    star = {k: c2dt2 * v for k, v in laplacian_star(u_t.ndim, order).items()}
    center = tuple([0] * u_t.ndim)
    star[center] = star.get(center, 0.0) + 2.0
    lap2u = star_stencil(u_t, star, (h,) * u_t.ndim, interpret=interpret)
    return lap2u - u_tm1_core
