"""Device time by IR scope: what the program's ``jax.named_scope`` names
(``core/lowering.scope``) say about a traced window.

Each device op is given the innermost ``stencil.*`` or ``comm.*`` scope
of its ``tf_op`` path (``bench/xspace.scopes``); an op with none is
*unscoped*: what the compiler put in on its own (copies of the loop
state, loop bookkeeping).  Times are unions of op intervals inside the
window, per chip, like ``bench.trace.busy_ns``.

Three readings, all in %:

- ``halo_pad_share``: device time under ``comm.halo_pad`` over busy time,
  mean over chips;
- ``stencil_kernel_roofline``: the least HBM bytes of the window's work
  at the chip's peak bandwidth over the device time under ``stencil.*``
  scopes, per chip; None where those scopes hold no time;
- ``unscoped_device_share``: device time of unscoped ops over busy time,
  mean over chips.

Each is None where the trace's ops carry no scope at all (a program
that emits none).
"""
from __future__ import annotations

from typing import Callable

from bench import peaks
from bench import trace as tr


def scoped_ns(trace: tr.Trace, scopes: dict, keep: Callable) -> dict:
    """Per device: ns of the window in which an op runs whose scope
    ``keep`` accepts."""
    lo, hi = trace.window
    out = {}
    for d, ops in trace.ops.items():
        of = scopes.get(d, {})
        out[d] = tr.length(tr.merge(
            [(s, e) for n, s, e in ops if keep(of.get(n))], lo, hi))
    return out


def _has_scopes(trace: tr.Trace, scopes: dict) -> bool:
    return any(scopes.get(d, {}).get(n) for d, ops in trace.ops.items()
               for n, _, _ in ops)


def _share_of_busy(trace: tr.Trace, scopes: dict, keep: Callable):
    if not _has_scopes(trace, scopes):
        return None
    busy = tr.busy_ns(trace)
    part = scoped_ns(trace, scopes, keep)
    return 100.0 * sum(part[d] / busy[d] for d in busy) / len(busy)


def halo_pad_share(trace: tr.Trace, scopes: dict):
    return _share_of_busy(trace, scopes, lambda s: s == "comm.halo_pad")


def unscoped_device_share(trace: tr.Trace, scopes: dict):
    return _share_of_busy(trace, scopes, lambda s: s is None)


def stencil_kernel_roofline(record: dict, trace: tr.Trace, scopes: dict):
    stencil = scoped_ns(trace, scopes,
                        lambda s: s is not None and s.startswith("stencil."))
    stencil_s = sum(stencil.values()) / len(stencil) / 1e9
    if stencil_s <= 0:
        return None
    bw = peaks.peaks(record["device_kind"])["hbm_bytes_per_s"]
    least_s = record["least_bytes"] / record["chips"] / bw
    return 100.0 * least_s / stencil_s
