"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, ``bench/run.py``, the examples) call
:func:`enable` once, before their first compile.  Importing ``repro``
never does: a library must not choose a process's cache.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing is set
  in code, and that directory is returned.
- unset: the cache goes to ``.jax_cache/`` at the root of this checkout —
  the same path in every run, since the path is part of what a later run
  must find (never a temporary name, a PID or a time).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
