"""Reduce a profiler trace (``.xplane.pb``) of the measured window to the
numbers the per-layer metrics read.

- Device planes are those named ``/device:TPU:<n>``; of each, the line
  ``XLA Ops`` holds one event per operation the chip ran (op level, not
  the ``XLA Modules`` line of whole programs), named by the op's whole
  HLO text; an op is named here by its instruction name (``%fusion.2``).
  Control-flow ops (``while``, ``conditional``, ``call``) span the ops of
  their bodies, which have events of their own, and are left out.
- The harness's own spans (``jax.profiler.TraceAnnotation``) lie on the
  host plane, on the same clock; ``bench.window`` bounds the window.

Busy time is the union of a device's op intervals inside the window; an
idle gap is a stretch of the window where no op runs, labelled by the
innermost harness span open at its middle.  A collective's exposed time
is the part of its intervals during which no other op runs on that
device.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Optional

DEVICE_PLANE = re.compile(r"/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
CONTAINER = re.compile(r"%(while|conditional|call)[.\s=]")
COLLECTIVE = re.compile(
    r"(collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all)"
)


@dataclasses.dataclass
class Trace:
    window: tuple                 # (start ns, end ns) of ``bench.window``
    ops: dict                     # device plane -> [(name, start ns, end ns)]
    spans: list                   # [(name, start ns, end ns)] harness spans


def load(path: str, spans=(WINDOW_SPAN,)) -> Optional[Trace]:
    """Read one ``.xplane.pb``, keeping the host events named in
    ``spans`` (the harness's own); None where it holds no window or no
    device op."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    names, ops, spans = frozenset(spans), {}, []
    for plane in data.planes:
        if DEVICE_PLANE.search(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = sorted(
                        (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if not CONTAINER.match(e.name)
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows or not any(ops.values()):
        return None
    w = max(windows, key=lambda s: s[2] - s[1])
    return Trace(window=(w[1], w[2]), ops=ops, spans=spans)


def op_name(hlo: str) -> str:
    """``%fusion.2 = f32[...] fusion(...)`` -> ``%fusion.2``."""
    return hlo.split(" = ", 1)[0]


def merge(intervals, lo: float, hi: float) -> list:
    """Union of ``(start, end)`` intervals clipped to [lo, hi], sorted."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def gaps(merged, lo: float, hi: float) -> list:
    """The stretches of [lo, hi] that ``merged`` leaves uncovered."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def subtract(a, b) -> float:
    """Length of merged intervals ``a`` minus merged intervals ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        t = s
        while j < len(b) and b[j][1] <= t:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                total += b[k][0] - t
            t = max(t, b[k][1])
            k += 1
        if e > t:
            total += e - t
    return total


def busy_ns(trace: Trace) -> dict:
    lo, hi = trace.window
    return {d: length(merge([(s, e) for _, s, e in ops], lo, hi))
            for d, ops in trace.ops.items()}


def window_ns(trace: Trace) -> float:
    return trace.window[1] - trace.window[0]


def exposed_ns(trace: Trace, is_collective: Callable = COLLECTIVE.search) -> dict:
    """Per device: ns in which a collective runs and no other op does.
    Devices with no collective op are left out."""
    lo, hi = trace.window
    out = {}
    for d, ops in trace.ops.items():
        coll = [(s, e) for n, s, e in ops if is_collective(n)]
        if not coll:
            continue
        other = [(s, e) for n, s, e in ops if not is_collective(n)]
        out[d] = subtract(merge(coll, lo, hi), merge(other, lo, hi))
    return out


def top_ops(trace: Trace, n: int = 10) -> list:
    """``[[op, seconds]]``: the ops that took most device time in the
    window, summed per name and averaged over the devices."""
    lo, hi = trace.window
    total: dict = {}
    for ops in trace.ops.values():
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                total[name] = total.get(name, 0.0) + d
    k = len(trace.ops)
    ranked = sorted(total.items(), key=lambda x: -x[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def span_at(trace: Trace, t: float) -> str:
    """The innermost harness span open at ``t`` (the one begun last)."""
    best = None
    for name, s, e in trace.spans:
        if s <= t < e and (best is None or s > best[1]):
            best = (name, s)
    return best[0] if best else "none"


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """``[[host span, seconds]]``: the longest idle gaps of any device in
    the window, each named by what the host was doing at its middle."""
    lo, hi = trace.window
    found = []
    for ops in trace.ops.values():
        for s, e in gaps(merge([(s, e) for _, s, e in ops], lo, hi), lo, hi):
            found.append((e - s, s, e))
    found.sort(reverse=True)
    return [[span_at(trace, (s + e) / 2), d / 1e9] for d, s, e in found[:n]]
