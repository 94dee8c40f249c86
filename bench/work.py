"""Work counts of a stencil time loop, from shapes alone.

- points of a grid: the product of its extents;
- the least HBM bytes of one time step: every time level the update reads
  is read once and one level is written, ``(levels_read + 1) * itemsize *
  points``.  A kernel that advances ``k`` time steps per call (a fused
  epoch) reads and writes those levels once per call, so its least bytes
  per step are that divided by ``k``.
"""
from __future__ import annotations

import math


def points(shape) -> int:
    return math.prod(int(n) for n in shape)


def steps_per_kernel_call(kernel_dispatches: dict, exchange_every: int) -> int:
    """Time steps one kernel call advances, as the compiled artifact
    states it: the epoch depth where the epoch is one fused kernel, else 1."""
    return int(exchange_every) if kernel_dispatches.get("fused_epoch", 0) >= 1 else 1


def least_bytes_per_step(
    n_points: int, levels_read: int, itemsize: int = 4, steps_per_call: int = 1
) -> float:
    return (levels_read + 1) * itemsize * n_points / steps_per_call
