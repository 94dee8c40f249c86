"""``repro.obs`` — observability: host spans on the profiler's clock and
one snapshot of the stack's counters (DESIGN.md §12).

Quickstart::

    import jax
    from repro import api, obs

    obs.enable()                       # or REPRO_TRACE=1 in the env
    with jax.profiler.trace("/tmp/trace"):   # open in Perfetto/XProf
        step = api.compile(prog, api.Target())
        out = step.time_loop((u0,), 32)
    print(obs.snapshot())              # every subsystem's counters

Device ops carry the IR op that emitted them as a ``jax.named_scope``
(``stencil.apply``, ``comm.halo_pad``, ...) whether or not obs is
enabled; obs adds the host spans (``api.compile``, ``time_loop``,
``engine.step``, ...).  Tracing is off by default and a disabled span
costs one check — see ``repro.obs.trace``.
"""
from repro.obs.registry import NAMESPACES, snapshot
from repro.obs.trace import disable, enable, enabled, span, traced

__all__ = [
    "NAMESPACES",
    "snapshot",
    "disable",
    "enable",
    "enabled",
    "span",
    "traced",
]
