"""Whose time the device's idle time is: each gap put down to a host span.

``obs.span`` writes itself into the profiler's capture as an annotation on
its thread's line of the host plane, on the clock the device's operations
are on. This reader takes the device's idle intervals in the slice (the gaps
between the merged ``XLA Ops`` intervals, as ``trace_reduce`` merges them)
and the program's spans on ONE host thread, the one that carries events named
``args["thread_span"]`` (the thread that feeds the chip), and puts each idle
interval down to the innermost span open over it, splitting an interval that
crosses spans. A program span is an event whose name the program's catalogue
(``obs/names.py``) has. The value is the share of the idle time that lies
under some span, in percent.

Before the result line it prints the table as one JSON line:
``{"phase": "idle_by_span", "rows": [[span, seconds], ...],
"unattributed_s": ..., "idle_s": ..., "clock": {...}}``. ``clock`` is the check that host and
device share a clock: of the device's BUSY time between the first and the
last span named ``args["clock_span"]``, the share that lies inside such a
span (work the device does for a span cannot lie outside it unless the
clocks are offset), ``lead_ms``, the median time from such a span's start to
the first operation that starts inside it, and ``tail_ms``, from the end of
the last operation inside it to its end. An offset between the clocks would
show as a negative lead or tail; nothing here corrects one.

No capture, or no thread with such a span in it (a program that writes no
annotations): None, and nothing is printed.
"""

from __future__ import annotations

import json
import statistics
from bisect import bisect_left
from collections import defaultdict

from bench import trace_reduce
from bench.readers import xplane

HOST_PLANE = "/host:CPU"


def span_names() -> frozenset:
    from spark_bam_tpu.obs.names import NAMES

    return NAMES


def busy_intervals(planes: list) -> list:
    """Merged ``[start, end]`` of the operations of the first device plane
    (the cells that report this run on one chip)."""
    for name, lines in planes:
        if name.startswith(trace_reduce.DEVICE_PREFIX):
            # An Event starts with trace_reduce's (name, start, duration).
            ops = [e[:3] for events in trace_reduce._op_lines(lines)
                   for e in events]
            return [m[:2] for m in trace_reduce._merge(ops)]
    return []


def thread_spans(planes: list, thread_span: str, known) -> list:
    """``[(name, start, end)]`` of the program's spans on the host thread
    that carries ``thread_span``; [] when no thread does."""
    for name, lines in planes:
        if name != HOST_PLANE:
            continue
        for _line, events in lines:
            if any(e.name == thread_span for e in events):
                return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in events if e.name in known]
    return []


def segments(spans: list) -> list:
    """Disjoint ``(start, end, innermost span)`` covering the spans' union,
    in time order. Spans of one thread nest; a child ends with its parent at
    the latest."""
    out, stack = [], []  # stack of (end, name)

    def emit(start, end):
        if stack and end > start:
            out.append((start, end, stack[-1][1]))

    cursor = 0.0
    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] <= start:
            emit(cursor, stack[-1][0])
            cursor = max(cursor, stack.pop()[0])
        emit(cursor, start)
        cursor = start
        stack.append((min(end, stack[-1][0]) if stack else end, name))
    while stack:
        emit(cursor, stack[-1][0])
        cursor = max(cursor, stack.pop()[0])
    return out


def attribute(intervals: list, segs: list) -> tuple:
    """``({span: ns}, unattributed ns)`` of ``intervals`` over ``segs``."""
    ends = [s[1] for s in segs]
    by_span: dict = defaultdict(float)
    loose = 0.0
    for start, end in intervals:
        covered = 0.0
        k = bisect_left(ends, start)
        while k < len(segs) and segs[k][0] < end:
            part = min(end, segs[k][1]) - max(start, segs[k][0])
            if part > 0:
                by_span[segs[k][2]] += part
                covered += part
            k += 1
        loose += (end - start) - covered
    return dict(by_span), loose


def clock_check(busy: list, spans: list, clock_span: str) -> dict:
    inside = [(s, e) for name, s, e in spans if name == clock_span]
    if not inside or not busy:
        return {"span": clock_span, "spans": len(inside)}
    inside.sort()
    first, last = inside[0][0], max(e for _, e in inside)
    busy = [(max(s, first), min(e, last)) for s, e in busy
            if e > first and s < last]
    covered, _ = attribute(busy, [(s, e, clock_span) for s, e in inside])
    starts, ends = [s for s, _ in busy], [e for _, e in busy]
    leads, tails = [], []
    for s, e in inside:
        k = bisect_left(starts, s)
        if k < len(starts) and starts[k] < e:
            leads.append((starts[k] - s) / 1e6)
        k = bisect_left(ends, e) - 1
        if k >= 0 and ends[k] > s:
            tails.append((e - ends[k]) / 1e6)
    return {
        "span": clock_span, "spans": len(inside),
        "busy_inside_share": covered.get(clock_span, 0.0)
        / max(sum(e - s for s, e in busy), 1.0),
        "lead_ms": statistics.median(leads) if leads else None,
        "tail_ms": statistics.median(tails) if tails else None,
    }


def reduce_planes(planes: list, thread_span: str, clock_span: str,
                  known) -> dict | None:
    spans = thread_spans(planes, thread_span, known)
    busy = busy_intervals(planes)
    if not spans or not busy:
        return None
    idle = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    by_span, loose = attribute(idle, segments(spans))
    idle_ns = sum(e - s for s, e in idle)
    rows = sorted(by_span.items(), key=lambda kv: -kv[1])
    return {
        "phase": "idle_by_span", "thread_span": thread_span,
        "rows": [[k, v / 1e9] for k, v in rows],
        "unattributed_s": loose / 1e9, "idle_s": idle_ns / 1e9,
        "clock": clock_check(busy, spans, clock_span),
    }


def read(args: dict, sources: dict):
    profile = sources["profile"]
    if not profile or not profile.get("file"):
        return None
    table = reduce_planes(xplane.load(profile["file"]), args["thread_span"],
                          args["clock_span"], span_names())
    if table is None:
        return None
    print(json.dumps(table), flush=True)
    if not table["idle_s"]:
        return None
    return 100.0 * (1.0 - table["unattributed_s"] / table["idle_s"])
