"""A profiler capture (``.xplane.pb``) with everything an event carries.

``jax.profiler.ProfileData`` gives an event's name, times and own stats, but
not the stats of its metadata, and on a TPU that is where XLA puts an
operation's name path (``tf_op``:
``jit(count_window)/check/while/body/flags/gather:``), the only place
a ``jax.named_scope`` shows. So this reads the file's wire format itself
(the ``XSpace`` message of tsl's ``xplane.proto``; field numbers below), with
nothing but the standard library.

``load(path)`` returns ``[(plane name, [(line name, [Event])])]`` with
``Event = (name, start ns, duration ns, stats)``; ``stats`` merges the
metadata's and the event's own, by stat name. Not a reader of a metric:
``trace_scope`` and ``gap_by_span`` use it.
"""

from __future__ import annotations

import struct
from typing import NamedTuple


class Event(NamedTuple):
    name: str
    start_ns: float
    duration_ns: float
    stats: dict


def _varint(buf, i: int):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: ints for varints, a float
    for a fixed64 (the schema's only one is a stat's double), bytes for
    strings and sub-messages."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = struct.unpack_from("<d", buf, i)[0]
            i += 8
        elif wire == 5:
            value = None
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield key >> 3, value


def _text(raw) -> str:
    return bytes(raw).decode("utf-8", "replace")


def _stat(buf, stat_names: dict):
    """XStat: metadata_id=1, double=2, uint64=3, int64=4, str=5, bytes=6,
    ref=7 (the value is the name of that stat metadata)."""
    name = value = None
    for field, v in _fields(buf):
        if field == 1:
            name = stat_names.get(v, str(v))
        elif field == 4:
            value = v - (1 << 64) if v >= 1 << 63 else v
        elif field in (5, 6):
            value = _text(v)
        elif field == 7:
            value = stat_names.get(v, str(v))
        else:
            value = v
    return name, value


def _map_entry(buf):
    key = value = None
    for field, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _plane(buf):
    """XPlane: name=2, lines=3, event_metadata=4, stat_metadata=5."""
    name, lines, event_meta, stat_meta = "", [], [], []
    for field, v in _fields(buf):
        if field == 2:
            name = _text(v)
        elif field == 3:
            lines.append(v)
        elif field == 4:
            event_meta.append(_map_entry(v)[1])
        elif field == 5:
            stat_meta.append(_map_entry(v)[1])
    stat_names = {}
    for raw in stat_meta:  # XStatMetadata: id=1, name=2
        got = dict(_fields(raw))
        stat_names[got.get(1, 0)] = _text(got.get(2, b""))
    metadata = {}
    for raw in event_meta:  # XEventMetadata: id=1, name=2, stats=5
        ident, label, stats = 0, "", {}
        for field, v in _fields(raw):
            if field == 1:
                ident = v
            elif field == 2:
                label = _text(v)
            elif field == 5:
                key, value = _stat(v, stat_names)
                stats[key] = value
        metadata[ident] = (label, stats)
    return name, [_line(raw, metadata, stat_names) for raw in lines]


def _line(buf, metadata: dict, stat_names: dict):
    """XLine: name=2, timestamp_ns=3, events=4, display_name=11.
    XEvent: metadata_id=1, offset_ps=2, duration_ps=3, stats=4."""
    name, t0, raw_events = "", 0, []
    for field, v in _fields(buf):
        if field == 2:
            name = _text(v)
        elif field == 3:
            t0 = v
        elif field == 4:
            raw_events.append(v)
    events = []
    for raw in raw_events:
        ident = offset_ps = duration_ps = 0
        own = []
        for field, v in _fields(raw):
            if field == 1:
                ident = v
            elif field == 2:
                offset_ps = v
            elif field == 3:
                duration_ps = v
            elif field == 4:
                own.append(v)
        label, stats = metadata.get(ident, (str(ident), {}))
        if own:
            stats = {**stats, **dict(_stat(s, stat_names) for s in own)}
        events.append(Event(label, t0 + offset_ps / 1e3, duration_ps / 1e3,
                            stats))
    return name, events


def load(path) -> list:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    return [_plane(v) for field, v in _fields(space) if field == 1]
