"""Plain float32 reference for the stencil cells, and the comparison that
decides ``correct``.

Nothing here imports the program.  The finite-difference table is copied
from the standard central-difference coefficients (Fornberg 1988), so no
change to the program's tables can move the reference.

The comparison runs in blocks of rows so that it fits beside the
program's answers: a block of rows ``[r0, r1)`` after ``steps`` steps
depends only on rows ``[r0 - h, r1 + h)`` of the input, ``h = steps *
radius``.  Each block is advanced on its own, with the zero boundary on
every side of the block; rows the false edges reach stay outside
``[r0, r1)``, so each row is computed as in one whole-grid run.
"""
from __future__ import annotations

import dataclasses
from fractions import Fraction
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

# d²/dx² at unit spacing, offsets -order/2 .. order/2.
D2 = {
    2: (1, -2, 1),
    4: (Fraction(-1, 12), Fraction(4, 3), Fraction(-5, 2), Fraction(4, 3),
        Fraction(-1, 12)),
    8: (Fraction(-1, 560), Fraction(8, 315), Fraction(-1, 5), Fraction(8, 5),
        Fraction(-205, 72), Fraction(8, 5), Fraction(-1, 5), Fraction(8, 315),
        Fraction(-1, 560)),
}

# Cap on the elements of one block (512 MiB of float32): a block's
# reference needs about four times that beside the answers it checks.
BLOCK_ELEMENTS = 2**27


def laplacian_taps(ndim: int, order: int) -> list:
    """``[(offset, coeff)]`` of the star-shaped n-D Laplacian."""
    coeffs = D2[order]
    r = order // 2
    taps: dict = {}
    for d in range(ndim):
        for i, c in enumerate(coeffs):
            off = tuple(i - r if k == d else 0 for k in range(ndim))
            taps[off] = taps.get(off, 0) + Fraction(c)
    return [(off, float(c)) for off, c in sorted(taps.items())]


def laplacian(u, order: int, dtype):
    """Laplacian of ``u`` with zeros outside it, in ``dtype``."""
    r = order // 2
    p = jnp.pad(u, r)
    out = jnp.zeros(u.shape, dtype)
    for off, c in laplacian_taps(u.ndim, order):
        idx = tuple(slice(r + o, r + o + n) for o, n in zip(off, u.shape))
        out = out + jnp.asarray(c, dtype) * p[idx]
    return out


@dataclasses.dataclass
class Answer:
    """One answer of the program to check: ``got`` is what the program
    made from the state ``inputs`` (oldest to newest level) in ``steps``
    time steps.  ``inputs`` may be a callable that makes the state anew."""

    inputs: object
    steps: int
    got: jax.Array

    def state(self) -> tuple:
        s = self.inputs() if callable(self.inputs) else self.inputs
        return tuple(s)


@partial(jax.jit, static_argnames=("advance", "rows", "dtype"))
def _block(levels, got, steps, core_lo, *, advance, rows, dtype):
    """Reference (float32) and candidate on one block of rows: returns
    (max|candidate - reference|, max|reference|) over the block's core.
    The candidate is ``got`` (the program's rows), or, where ``got`` is
    None, the reference itself computed in ``dtype`` (the control)."""
    ref = advance(tuple(x.astype(jnp.float32) for x in levels), steps, jnp.float32)
    ref = jax.lax.dynamic_slice_in_dim(ref, core_lo, rows, axis=0)
    if got is None:
        cand = advance(tuple(x.astype(dtype) for x in levels), steps, dtype)
        cand = jax.lax.dynamic_slice_in_dim(cand, core_lo, rows, axis=0)
        cand = cand.astype(jnp.float32)
    else:
        cand = got.astype(jnp.float32)
    return jnp.max(jnp.abs(cand - ref)), jnp.max(jnp.abs(ref))


def _rows(x: jax.Array, lo: int, hi: int, dev) -> jax.Array:
    """Rows ``[lo, hi)`` of ``x`` on ``dev``.  Of an array sharded over
    several devices, the rows are copied out of the shards that hold
    them and put together on ``dev``: slicing the sharded array itself
    makes a program that gathers whole bands of it on every device."""
    if len(x.sharding.device_set) == 1:
        return jax.device_put(x[lo:hi], dev)
    pieces = {}
    for shard in x.addressable_shards:
        starts = tuple(i.start or 0 for i in shard.index)
        r0 = starts[0]
        a, b = max(lo, r0), min(hi, r0 + shard.data.shape[0])
        if a < b and starts not in pieces:   # replicas hold the same rows
            pieces[starts] = jax.device_put(shard.data[a - r0:b - r0], dev)
    return _assemble(pieces, 0)


def _assemble(pieces: dict, axis: int) -> jax.Array:
    """Join blocks keyed by their start along each axis."""
    if len(pieces) == 1:
        return next(iter(pieces.values()))
    groups: dict = {}
    for starts, piece in pieces.items():
        groups.setdefault(starts[axis], {})[starts] = piece
    return jnp.concatenate([_assemble(groups[k], axis + 1) for k in sorted(groups)],
                           axis=axis)


def relative_errors(
    answers: Sequence[Answer],
    advance: Callable,
    radius: int,
    devices: Sequence,
    control_dtype=None,
) -> list:
    """max|candidate - reference| / max|reference| for each answer.

    The candidate is the program's answer, or with ``control_dtype`` the
    reference computed in that lower precision from the same inputs.
    ``advance(levels, steps, dtype)`` returns the newest level after
    ``steps`` steps with the zero boundary."""
    out = []
    k = 0
    for ans in answers:
        levels = ans.state()
        n = levels[-1].shape[0]
        row = max(1, levels[-1].size // n)
        block = max(1, min(n, BLOCK_ELEMENTS // row))
        h = ans.steps * radius
        diffs, refs = [], []
        for r0 in range(0, n, block):
            r1 = min(n, r0 + block)
            lo, hi = max(0, r0 - h), min(n, r1 + h)
            dev = devices[k % len(devices)]
            k += 1
            part = tuple(_rows(x, lo, hi, dev) for x in levels)
            got = None
            if control_dtype is None:
                got = _rows(ans.got, r0, r1, dev)
            d, m = _block(part, got, jnp.int32(ans.steps), jnp.int32(r0 - lo),
                          advance=advance, rows=r1 - r0,
                          dtype=control_dtype or jnp.float32)
            diffs.append(d)
            refs.append(m)
            if k % len(devices) == 0:
                # one block in flight a device: memory holds one at a time
                jax.block_until_ready((diffs[-len(devices):], refs[-len(devices):]))
        diff = max(float(d) for d in diffs)
        ref = max(float(m) for m in refs)
        out.append(diff / ref)
    return out
