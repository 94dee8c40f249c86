"""Keys from ``--seed``, which may be any whole number below 2**64.

``jax.random.key`` folds a seed above 2**32 to 0 in 32-bit mode, so the
high word is folded in separately.
"""
from __future__ import annotations

import jax
import numpy as np


def key(seed: int, *path: int):
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    k = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    for p in path:
        k = jax.random.fold_in(k, int(p))
    return k


def rng(seed: int, stream: int) -> np.random.Generator:
    """A host generator for traffic decisions (sizes, order, sampling)."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, stream])
