"""Device time under named scopes PLUS the operations that lost their name.

``trace_scope`` finds an operation by its name path, and the compiler does
not keep one on everything it emits. The case this was written for: a scatter
under ``vmap`` (``check_window``'s ``_scatter_lanes`` in
``jit_confusion_step``). XLA flattens its batch dimension: the index
arithmetic keeps the path ``check/scatter/scatter``, and what does the work
comes out with no metadata at all: a ``sort`` of the indices with their
updates, the flat ``scatter`` (a custom fusion), and a ``while`` that copies
the flat result back a row at a time (``dynamic-slice``, ``reshape``,
``dynamic-update-slice``). Read by scope alone the scatter was 0.19 ms of a
step; those operations were 28 (``PERF.md`` §6, PR 33).

An orphan is told by what it makes: ``"orphan_results": ["s8"]`` takes the
operations inside an execution of the program that carry no name path and
whose result (any element of a tuple) has that element type, read from the
HLO text the capture names an operation by. In ``jit_confusion_step`` int8
is the walk's verdict code and nothing else, and the nameless operations that
make an int8 array are exactly the verdict scatter's expansion
(``tests/test_chip_compile.py`` holds that against the chip's compiler). A
rewrite that gives the scatter its name back moves its time from the second
half of the sum to the first.

The value is ``trace_scope``'s, computed by it: the orphans are given a name
path of their own and read as one more scope. Self time summed per execution
over the operations under one of ``scopes`` and the orphans, the median over
the executions, the mean over the chips, in milliseconds. None where there
is nothing to read: no capture, no execution, neither a scoped operation nor
an orphan.
"""

from __future__ import annotations

from bench import trace_reduce
from bench.readers import trace_scope, xplane

#: The name path an orphan is given, and the scope it is then found by.
ORPHAN = "<orphan>"


def result_types(hlo: str) -> set:
    """Element types of what an operation makes, from its HLO text:
    ``{"s32", "s8"}`` of ``%sort.1 = (s32[8]{0}, s8[8]{0}) sort(...)``."""
    _head, _sep, rest = hlo.partition(" = ")
    m = trace_reduce._HLO.match(trace_reduce._LAYOUT.sub("", rest))
    if not m:
        return set()
    return {shape.partition("[")[0]
            for shape in trace_reduce._SHAPE.findall(m.group(1))}


def adopt(event, orphan_results: set):
    """The event under the path ``ORPHAN`` if it has none of its own and
    makes one of ``orphan_results``; else as it is."""
    if event.stats.get("tf_op") or not (
            result_types(event.name) & orphan_results):
        return event
    return event._replace(stats={**event.stats, "tf_op": ORPHAN})


def read_planes(planes: list, program: str, scopes,
                orphan_results) -> float | None:
    wanted = set(orphan_results)
    adopted = [
        (plane, [(line, [adopt(e, wanted) for e in events]
                  if line == trace_reduce.OPS_LINE else events)
                 for line, events in lines])
        for plane, lines in planes
        if plane.startswith(trace_reduce.DEVICE_PREFIX)]
    return trace_scope.read_planes(adopted, program, [*scopes, ORPHAN])


def read(args: dict, sources: dict):
    profile = sources["profile"]
    if not profile or not profile.get("file"):
        return None
    return read_planes(xplane.load(profile["file"]), args["program"],
                       args.get("scopes", []), args["orphan_results"])
