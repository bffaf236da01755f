"""From one profiler capture (``.xplane.pb``) to busy and idle time.

``load_planes`` turns ``jax.profiler.ProfileData`` into plain tuples;
``reduce_planes`` does the arithmetic, so a synthetic trace can check it
(``bench/tests/test_trace_reduce.py``). Device planes are those named
``/device:TPU:<n>``. On such a plane the line ``XLA Ops`` holds one event per
executed operation (nested where an operation has a body); busy time is the
union of those intervals, averaged over the chips. Operations keep the names
XLA gives them: stable names need ``jax.named_scope`` inside the program.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: Lines of a device plane that group operations and do not run themselves.
GROUPING_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                  "Framework Name Scope", "Source code")
TOP = 10
_LAYOUT = re.compile(r"\{[^{}]*\}")
_SHAPE = re.compile(r"\b([a-z]+[0-9]*\[[0-9,]*\])")
_HLO = re.compile(r"(\(.*?\)|\S+) ([\w\-]+)\((.*)$")


def short_name(hlo: str) -> str:
    """``fusion.369 s32[33554432] <- s32[512,65536] s32[33554432]`` from the
    HLO text XLA names an operation by: its name, result and operand shapes,
    without layouts. Anything else comes back unchanged (cut to 120)."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:120]
    m = _HLO.match(_LAYOUT.sub("", rest))
    if not m:
        return hlo[:120]
    result, operands = m.group(1), m.group(3).split("), ")[0]
    out = f"{head.lstrip('%')} {result}"
    shapes = _SHAPE.findall(operands)
    if shapes:
        out += " <- " + " ".join(shapes)
    return out[:120]


def load_planes(path) -> list:
    """``[(plane name, [(line name, [(event name, start ns, duration ns)])])]``
    of the device planes; a host plane comes with its lines' names alone."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    return [
        (plane.name, [
            (line.name, [(short_name(e.name), float(e.start_ns),
                          float(e.duration_ns)) for e in line.events]
             if plane.name.startswith(DEVICE_PREFIX) else [])
            for line in plane.lines
        ])
        for plane in data.planes
    ]


def _op_lines(lines: list) -> list:
    named = [ev for name, ev in lines if name == OPS_LINE]
    if named:
        return named
    return [ev for name, ev in lines if name not in GROUPING_LINES]


def _merge(events: list) -> list:
    """Disjoint ``[start, end, last op name]`` covering the events."""
    merged: list = []
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            if start + dur > merged[-1][1]:
                merged[-1][1] = start + dur
                merged[-1][2] = name
        else:
            merged.append([start, start + dur, name, name])
    return merged


def _self_times(events: list, into: dict) -> None:
    """Adds each event's duration less its children's to ``into[name]``."""
    stack: list = []  # (end, name, self time so far)

    def close(until: float) -> None:
        while stack and stack[-1][0] <= until:
            _end, name, own = stack.pop()
            into[name] += own

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:  # the part of it inside its parent
            stack[-1][2] -= min(start + dur, stack[-1][0]) - start
        stack.append([start + dur, name, dur])
    close(float("inf"))


def reduce_planes(planes: list) -> dict:
    """``busy_s`` (mean over device planes), ``devices``, the inventory of
    planes and lines, and ``breakdown``: the operations with most self time
    and the longest idle gaps, each named by the operations around it."""
    busy, ops, gaps = [], defaultdict(float), defaultdict(float)
    for name, lines in planes:
        if not name.startswith(DEVICE_PREFIX):
            continue
        chosen = _op_lines(lines)
        merged = _merge([e for ev in chosen for e in ev])
        busy.append(sum(end - start for start, end, *_ in merged) / 1e9)
        for ev in chosen:
            _self_times(ev, ops)
        for before, after in zip(merged, merged[1:]):
            gaps[f"after {before[2]} before {after[3]}"] += (
                after[0] - before[1])

    def top(table: dict) -> list:
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[k, v / 1e9] for k, v in rows]

    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "devices": len(busy),
        "inventory": {name: [(ln, len(ev)) for ln, ev in lines]
                      for name, lines in planes},
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(gaps)},
    }


def reduce_dir(profile_dir) -> dict:
    """Reduces the newest capture under ``profile_dir``."""
    found = sorted(Path(profile_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    out = reduce_planes(load_planes(found[-1]))
    out["file"] = str(found[-1])
    out["file_bytes"] = found[-1].stat().st_size
    return out
