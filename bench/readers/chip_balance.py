"""How evenly the chips of a cell share the work: the busy seconds of the
least busy chip over the busiest's, in percent.

From the capture of the profiled slice: each device plane's busy time is the
union of its ``XLA Ops`` intervals, as ``trace_reduce`` merges them (the
mean of the same numbers is its ``busy_s``). 100% is every chip busy for the
same time; a chip that ran nothing reads 0%. A balance needs two chips: with
one device plane, or no capture, there is nothing to read (None).
"""

from __future__ import annotations

from bench import trace_reduce
from bench.readers import xplane


def busy_by_plane(planes: list) -> list:
    """Busy nanoseconds of each device plane, in the capture's order."""
    busy = []
    for name, lines in planes:
        if not name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        # An Event starts with trace_reduce's (name, start, duration).
        ops = [e[:3] for events in trace_reduce._op_lines(lines)
               for e in events]
        busy.append(sum(m[1] - m[0] for m in trace_reduce._merge(ops)))
    return busy


def read(args: dict, sources: dict):
    profile = sources["profile"]
    if not profile or not profile.get("file"):
        return None
    busy = busy_by_plane(xplane.load(profile["file"]))
    if len(busy) < 2 or not max(busy):
        return None
    return 100.0 * min(busy) / max(busy)
