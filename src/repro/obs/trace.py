"""Host spans on the profiler's clock: the timeline behind ``repro.obs``.

A span marks a stretch of host work — a compile, a pass, a time loop's
trace and dispatch, a serve-engine step, a checkpoint — as a
``jax.profiler.TraceAnnotation``.  Under ``jax.profiler.trace`` it lands
on the host plane of the same profile as the device's ops, on the same
clock, with its keyword arguments as event stats; outside a profile it
records nothing.  Device work is named by ``jax.named_scope`` in the
lowering (``core/lowering.scope``), not here.

Off by default: a disabled ``span()`` returns a shared no-op context
manager after one check.  Enable with ``REPRO_TRACE=1`` in the
environment or ``obs.enable()`` at runtime.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Callable, Optional

import jax

_ENABLED = os.environ.get("REPRO_TRACE", "") not in ("", "0")


class _NullSpan:
    """Shared do-nothing context manager: the entire cost of a disabled
    ``with obs.span(...):`` is one check and returning this."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **args) -> None:
        """Args added to a disabled span go nowhere."""


_NULL = _NullSpan()


def enable() -> None:
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


def enabled() -> bool:
    return _ENABLED


def span(name: str, **args):
    """Context manager marking a block of host work as ``name``, with
    ``args`` as its stats; ``.set_metadata(**more)`` adds stats known
    only inside the block."""
    if not _ENABLED:
        return _NULL
    return jax.profiler.TraceAnnotation(name, **args)


def traced(name_or_fn: Any = None) -> Callable:
    """Decorator form: ``@traced`` or ``@traced("custom.name")``.  Adds
    one check per call when tracing is disabled."""

    def deco(fn: Callable, _name: Optional[str] = None) -> Callable:
        label = _name or getattr(fn, "__qualname__", fn.__name__)

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            if not _ENABLED:
                return fn(*a, **kw)
            with jax.profiler.TraceAnnotation(label):
                return fn(*a, **kw)

        return wrapper

    if callable(name_or_fn):  # bare @traced
        return deco(name_or_fn)
    return lambda fn: deco(fn, name_or_fn)
