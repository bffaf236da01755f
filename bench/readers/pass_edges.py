"""The two ends of a whole-file pass on the device's clock: the head, from
the pass's first line to the first operation any chip runs for it, and the
tail, from the last operation's end to the pass's last line.

``gap_by_span`` puts the idle time BETWEEN operations down to spans; a slice
that is one pass has most of its idle time before the first operation and
after the last, where that reader does not look. This one does. The pass is
its root span (``args["roots"]``: ``load.count`` or ``load.check_bam``,
written into the capture as an annotation like every ``obs.span``), found on
the host plane; the operations are those of EVERY device plane that start
inside it, so four chips read as one device that starts when its first chip
does. Head and tail are put down to the innermost program span open over
them on the root's thread, the root itself left out
(``gap_by_span.segments`` / ``attribute``): what is left lies under the root
alone, a stretch of the pass with no name.

``args["value"]`` picks the number: ``head_ms``, ``tail_ms`` or
``attributed_share`` (of head + tail, the percentage that lies under a span
other than the root). The table is worked out and printed once a run, as
one JSON line before the result line:
``{"phase": "pass_edges", "root", "head_ms", "host_ms", "launch_ms",
"tail_ms", "head_rows": [[span, ms], ...], "tail_rows", "unattributed_ms",
"planes"}``. ``host_ms`` is the root's start to the END of the first
dispatch span on its thread (``args["dispatch_spans"]``), ``launch_ms`` from
there to the first operation: what of the operand's put nothing hid. A chip
may take a program up while the call that dispatched it is still returning,
so ``launch_ms`` can fall a fraction of a millisecond under zero. What
cannot be is an operation that starts before that span STARTS, or ends after
the root's end: then host and device are not on one clock, the line says so
(``"clock": false``) and the value is None.

No capture, no root span in it (a program that writes no annotations, the
served cell) or no operation inside the root: None, and nothing is printed.
"""

from __future__ import annotations

import json

from bench import trace_reduce
from bench.readers import gap_by_span, xplane


def device_ops(planes: list) -> list:
    """``[[(start, end)] a device plane]`` of the operations."""
    out = []
    for name, lines in planes:
        if name.startswith(trace_reduce.DEVICE_PREFIX):
            out.append([(e[1], e[1] + e[2]) for events in
                        trace_reduce._op_lines(lines) for e in events])
    return out


def _rows(by_span: dict) -> list:
    return [[k, v / 1e6] for k, v in
            sorted(by_span.items(), key=lambda kv: -kv[1])]


def reduce_planes(planes: list, roots: list, dispatch_spans: list,
                  known) -> dict | None:
    for root in roots:
        spans = gap_by_span.thread_spans(planes, root, known)
        if spans:
            break
    else:
        return None
    _, r0, r1 = next(s for s in spans if s[0] == root)
    inside = [[(s, e) for s, e in ops if r0 <= s < r1]
              for ops in device_ops(planes)]
    inside = [ops for ops in inside if ops]
    if not inside:
        return None
    first = min(s for ops in inside for s, _ in ops)
    last = max(e for ops in inside for _, e in ops)
    dispatch = min(((s, e) for name, s, e in spans
                    if name in dispatch_spans and r0 <= s < r1),
                   default=None)
    segs = gap_by_span.segments([s for s in spans if s[0] != root])
    head, loose_head = gap_by_span.attribute([(r0, first)], segs)
    tail, loose_tail = gap_by_span.attribute([(last, r1)], segs)
    return {
        "phase": "pass_edges", "root": root,
        "head_ms": (first - r0) / 1e6,
        "host_ms": None if dispatch is None else (dispatch[1] - r0) / 1e6,
        "launch_ms": None if dispatch is None else (first - dispatch[1]) / 1e6,
        "tail_ms": (r1 - last) / 1e6,
        "head_rows": _rows(head), "tail_rows": _rows(tail),
        "unattributed_ms": (loose_head + loose_tail) / 1e6,
        "planes": len(inside),
        "clock": last <= r1 and (dispatch is None or first >= dispatch[0]),
    }


def read(args: dict, sources: dict):
    profile = sources["profile"]
    if not profile or not profile.get("file"):
        return None
    if "pass_edges" not in sources:  # three metrics, one table, one line
        table = reduce_planes(
            xplane.load(profile["file"]), args["roots"],
            args["dispatch_spans"], gap_by_span.span_names())
        if table is not None:
            print(json.dumps(table), flush=True)
        sources["pass_edges"] = table
    table = sources["pass_edges"]
    if table is None or not table["clock"]:
        return None
    if args["value"] != "attributed_share":
        return table[args["value"]]
    edges = table["head_ms"] + table["tail_ms"]
    if not edges:
        return None
    return 100.0 * (1.0 - table["unattributed_ms"] / edges)
