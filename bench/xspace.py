"""Read what ``jax.profiler.ProfileData`` does not expose from a profiler
trace (``.xplane.pb``): the stats of each event's metadata, among them
``tf_op``, the ``jax.named_scope`` path of the op an event ran.

A decoder of the protobuf wire format, for only the fields read here
(``XSpace``, ``tsl/profiler/protobuf/xplane.proto``):

- ``XSpace``: planes 1;
- ``XPlane``: name 2, lines 3 (skipped), event_metadata 4, stat_metadata 5
  (maps: entries of key 1 and value 2);
- ``XEventMetadata``: id 1, name 2, stats 5;
- ``XStat``: metadata_id 1, str_value 5, ref_value 7 (a string kept as the
  name of an ``XStatMetadata``);
- ``XStatMetadata``: id 1, name 2.

Only string stats are kept.
"""
from __future__ import annotations

from typing import Callable, Iterator

from bench import trace as tr

SCOPE_PREFIXES = ("stencil.", "comm.")


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def fields(buf) -> Iterator[tuple]:
    """``(field number, value)`` of each field of one message: an int for
    a varint, a memoryview of the bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane")
        yield number, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _map_values(entries) -> Iterator:
    for entry in entries:
        for number, value in fields(entry):
            if number == 2:
                yield value


def event_stats(data: bytes, plane: Callable[[str], bool] = lambda name: True) -> dict:
    """``{plane name: {event metadata name: {stat name: string}}}`` for
    the planes whose name ``plane`` accepts."""
    out = {}
    for number, body in fields(memoryview(data)):
        if number != 1:
            continue
        name, events, stats = "", [], []
        for n, value in fields(body):
            if n == 2:
                name = _text(value)
            elif n == 4:
                events.append(value)
            elif n == 5:
                stats.append(value)
        if not plane(name):
            continue
        stat_names = {}
        for value in _map_values(stats):
            meta = dict(fields(value))
            stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        by_event = {}
        for value in _map_values(events):
            event_name, kept = "", {}
            for n, v in fields(value):
                if n == 2:
                    event_name = _text(v)
                elif n == 5:
                    stat = dict(fields(v))
                    if 5 in stat:
                        kept[stat_names.get(stat.get(1, 0), "")] = _text(stat[5])
                    elif 7 in stat:
                        kept[stat_names.get(stat.get(1, 0), "")] = stat_names.get(stat[7], "")
            by_event[event_name] = kept
        out[name] = by_event
    return out


def innermost_scope(tf_op: str):
    """The last ``stencil.*`` or ``comm.*`` component of a ``tf_op``
    path (``jit(f)/.../comm.halo_pad/jit(_pad)/pad:`` -> ``comm.halo_pad``),
    or None.  ``tf_op`` ends in ``:<op type>``."""
    path = tf_op.rsplit(":", 1)[0]
    for part in reversed(path.split("/")):
        if part.startswith(SCOPE_PREFIXES):
            return part
    return None


def scopes(path: str) -> dict:
    """``{device plane: {op name: innermost scope or None}}`` of one
    ``.xplane.pb``, op names as ``bench.trace`` gives them
    (``%fusion.20``)."""
    with open(path, "rb") as f:
        data = f.read()
    planes = event_stats(data, lambda name: bool(tr.DEVICE_PLANE.search(name)))
    return {
        plane: {tr.op_name(name): innermost_scope(stats.get("tf_op", ""))
                for name, stats in events.items() if " = " in name}
        for plane, events in planes.items()
    }
