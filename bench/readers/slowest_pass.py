"""The slowest whole-file pass of the window over the median pass: what a
median or a mean over hundreds of passes hides.

The program's registry keeps, a root name (``load.count``,
``load.check_bam``), the longest pass it has seen with that pass's spans
summed by name (``snapshot["slowest_passes"]``: ``{root, ms, t, at_s, trace,
spans: {name: [count, summed ms, max ms]}}``). The value is that pass's
milliseconds over the median of the root span's histogram: a little over 1
in a window of even passes (the profiled pass is the slowest then), 4-60
where one pass stalled.

Before the result line it prints which phase held the pass:
``{"phase": "slowest_pass", "root", "ms", "median_ms", "at_s", "rows":
[[span, count, summed ms, that span's summed ms in a median pass], ...]}``,
the rows sorted by the excess of the third column over the fourth. A median
pass's sum of a span is the median of the span's histogram times its
observations a pass. A summed span includes its children, so the innermost
name that carries the excess names the phase.

A program that keeps no such record (no ``slowest_passes`` in its snapshot),
or no pass of ``args["roots"]`` in the window: None, and nothing is printed.
"""

from __future__ import annotations

import json
import statistics

from bench.readers import histogram


def table(snapshot: dict, roots: list) -> dict | None:
    kept = {p["root"]: p for p in snapshot.get("slowest_passes", [])}
    record = next((kept[r] for r in roots if r in kept), None)
    passes = record and histogram(snapshot, record["root"])
    if not passes:
        return None
    rows = []
    for name, (count, total, _top) in record["spans"].items():
        h = histogram(snapshot, name)
        usual = (statistics.median(h["values"]) * h["count"]
                 / passes["count"]) if h else 0.0
        rows.append([name, count, total, usual])
    rows.sort(key=lambda row: row[3] - row[2])
    return {
        "phase": "slowest_pass", "root": record["root"], "ms": record["ms"],
        "median_ms": float(statistics.median(passes["values"])),
        "at_s": record.get("at_s"), "rows": rows,
    }


def read(args: dict, sources: dict):
    line = table(sources["snapshot"], args["roots"])
    if line is None or not line["median_ms"]:
        return None
    print(json.dumps(line), flush=True)
    return line["ms"] / line["median_ms"]
