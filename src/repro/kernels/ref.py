"""Pure-jnp oracles for every kernel in this package.

Independent implementations (no shared code with the kernels) used by the
allclose test sweeps.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def star_stencil_ref(x, coeffs: Dict[Tuple[int, ...], float], halo: Tuple[int, ...]):
    """Weighted sum of shifted reads.

    ``x`` is halo-inclusive; the output is the core (x minus ``halo`` on
    both sides per dim).  Out-of-core values come from the halo content —
    boundary semantics live in whoever filled the halo.
    """
    rank = x.ndim
    core = tuple(s - 2 * h for s, h in zip(x.shape, halo))
    out = jnp.zeros(core, x.dtype)
    for off, c in coeffs.items():
        idx = tuple(
            slice(h + o, h + o + n) for h, o, n in zip(halo, off, core)
        )
        out = out + jnp.asarray(c, x.dtype) * x[idx]
    return out


def heat_step_ref(u, alpha: float, order: int, halo: int):
    """u_core + alpha * laplacian(u) — Jacobi-like heat-diffusion update."""
    from repro.core.fd import laplacian_star

    rank = u.ndim
    star = laplacian_star(rank, order)
    lap = star_stencil_ref(u, star, (halo,) * rank)
    core = tuple(slice(halo, s - halo) for s in u.shape)
    return u[core] + jnp.asarray(alpha, u.dtype) * lap


def wave_step_ref(u_t, u_tm1, c2dt2: float, order: int, halo: int):
    """2nd-order-in-time acoustic update:
    u_{t+1} = 2 u_t - u_{t-1} + c²dt² ∇²u_t."""
    from repro.core.fd import laplacian_star

    rank = u_t.ndim
    star = laplacian_star(rank, order)
    lap = star_stencil_ref(u_t, star, (halo,) * rank)
    core = tuple(slice(halo, s - halo) for s in u_t.shape)
    return (
        2.0 * u_t[core]
        - u_tm1[core]
        + jnp.asarray(c2dt2, u_t.dtype) * lap
    )


def _sign(a, b):
    """Fortran ``SIGN(a, b)``: ``|a|`` where ``b >= 0``, else ``-|a|``."""
    return jnp.where(b >= 0, jnp.abs(a), -jnp.abs(a))


def _limited_slope(lo, hi):
    """MUSCL's slope from the gradients on either side, limited."""
    s = (lo + hi) * (0.25 + _sign(0.25, lo * hi))
    return _sign(1.0, s) * jnp.minimum(
        jnp.minimum(jnp.abs(s), 2.0 * jnp.abs(lo)), 2.0 * jnp.abs(hi)
    )


def _muscl_flux(vel, up, down, zind_c, slope_up, slope_down, upward):
    """Flux ``vel * (a * (up + zind*z*slope_up) + (1-a) * (down + ...))``
    with NEMO's switch: ``a = 0.5 -+ sign(0.5, vel)``."""
    z0 = _sign(0.5, vel)
    a = 0.5 + z0 if upward else 0.5 - z0
    z = z0 - 0.5 * vel
    return vel * (a * (up + zind_c * (z * slope_up))
                  + (1.0 - a) * (down + zind_c * (z * slope_down)))


def traadv_step_ref(state):
    """One step of NEMO's MUSCL tracer advection (``frontends.nemo_traadv``
    states the equations) in plain ``jnp`` slices: ``state`` is
    ``(pun, pvn, pwn, umask, vmask, tmask, zind, T)``; returns the new T."""
    pun, pvn, pwn, umask, vmask, tmask, zind, t = state
    K, J, I = t.shape
    with jax.default_matmul_precision("highest"):
        zero = jnp.zeros_like(t)
        k_ = slice(0, K - 1)
        # H1
        c = (k_, slice(0, J - 1), slice(0, I - 1))
        zwx = zero.at[c].set(umask[c] * (t[k_, :J - 1, 1:] - t[c]))
        zwy = zero.at[c].set(vmask[c] * (t[k_, 1:, :I - 1] - t[c]))
        # H2, H3
        c = (k_, slice(1, J), slice(1, I))
        zslpx = zero.at[c].set(_limited_slope(zwx[k_, 1:, :I - 1], zwx[c]))
        zslpy = zero.at[c].set(_limited_slope(zwy[k_, :J - 1, 1:], zwy[c]))
        # H4, H5
        c = (k_, slice(1, J - 1), slice(1, I - 1))
        ip = (k_, slice(1, J - 1), slice(2, I))
        jp = (k_, slice(2, J), slice(1, I - 1))
        im = (k_, slice(1, J - 1), slice(0, I - 2))
        jm = (k_, slice(0, J - 2), slice(1, I - 1))
        zwx = zwx.at[c].set(_muscl_flux(pun[c], t[ip], t[c], zind[c],
                                        zslpx[ip], zslpx[c], upward=False))
        zwy = zwy.at[c].set(_muscl_flux(pvn[c], t[jp], t[c], zind[c],
                                        zslpy[jp], zslpy[c], upward=False))
        t = t.at[c].set(t[c] - (zwx[c] - zwx[im] + zwy[c] - zwy[jm]))
        # V1
        zwx = zero.at[1:K - 1].set(tmask[1:K - 1] * (t[:K - 2] - t[1:K - 1]))
        # V2, V3
        zslpx = zero.at[1:K - 1].set(_limited_slope(zwx[2:], zwx[1:K - 1]))
        # V4, V5
        zwx = zwx.at[0].set(pwn[0] * t[0])
        hi = (slice(1, K), slice(1, J - 1), slice(1, I - 1))
        lo = (k_, slice(1, J - 1), slice(1, I - 1))
        zwx = zwx.at[hi].set(_muscl_flux(pwn[hi], t[hi], t[lo], zind[lo],
                                         zslpx[hi], zslpx[lo], upward=True))
        return t.at[lo].set(t[lo] - (zwx[lo] - zwx[hi]))
