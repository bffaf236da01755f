"""Render JSONL metrics traces as a human report (reference stats format).

The ``spark-bam-tpu metrics-report`` subcommand consumes this: parse the JSONL a ``--metrics-out`` run emitted,
regroup span events by name, and render per-stage duration statistics
with the same ``core/stats.py`` formatting the golden CLI reports use.

A whole-file pass (``count-reads`` / ``check-bam`` on the device) is a
trace of its own (``obs.pass_span``), so its spans render as one tree a
pass, the slowest pass the registry kept first, with that record's
spans summed by name above it.

Multi-process traces: when several files are given (router + N fabric
workers, each exporting its own registry), span events carrying trace
ids are merged *across files* by ``trace_id`` and rendered as one tree
per trace — the cross-process view a single serve request produces.
"""

from __future__ import annotations

from spark_bam_tpu.obs.exporters import merge_snapshots, stats_summary
from spark_bam_tpu.obs.registry import read_jsonl


def load_trace(path) -> dict:
    """Parse a trace file into
    ``{"spans_by_name", "snapshot", "meta", "span_events"}``."""
    spans_by_name: dict[str, list[float]] = {}
    span_events: list[dict] = []
    snapshot: dict = {"counters": [], "gauges": [], "hists": []}
    meta: dict = {}
    dropped = 0
    for ev in read_jsonl(path):
        kind = ev.get("e")
        if kind == "span":
            spans_by_name.setdefault(ev["name"], []).append(float(ev["ms"]))
            span_events.append(ev)
        elif kind == "counter":
            snapshot["counters"].append(ev)
        elif kind == "gauge":
            snapshot["gauges"].append(ev)
        elif kind == "hist":
            snapshot["hists"].append(ev)
        elif kind == "slowest_pass":
            snapshot.setdefault("slowest_passes", []).append(ev)
        elif kind == "meta":
            meta = ev
        elif kind == "dropped":
            dropped = int(ev.get("count", 0))
    snapshot["dropped_events"] = dropped
    return {"spans_by_name": spans_by_name, "snapshot": snapshot,
            "meta": meta, "span_events": span_events}


def merge_traces(paths) -> dict:
    """Merge several per-process trace files into one view.

    Returns ``{"spans_by_name", "snapshot", "metas", "traces"}`` where
    ``traces`` maps each trace_id to its span events gathered across
    *all* files, sorted by start time — the single-request,
    cross-process span tree.
    """
    spans_by_name: dict[str, list[float]] = {}
    snapshots: list[dict] = []
    metas: list[dict] = []
    traces: dict[str, list[dict]] = {}
    for path in paths:
        t = load_trace(path)
        metas.append(dict(t["meta"], file=str(path)))
        snapshots.append(t["snapshot"])
        for name, vals in t["spans_by_name"].items():
            spans_by_name.setdefault(name, []).extend(vals)
        pid = t["meta"].get("pid")
        for ev in t["span_events"]:
            tid = ev.get("trace")
            if tid:
                traces.setdefault(tid, []).append(dict(ev, pid=pid))
    for evs in traces.values():
        evs.sort(key=lambda e: e.get("t", 0.0))
    return {"spans_by_name": spans_by_name,
            "snapshot": merge_snapshots(snapshots),
            "metas": metas, "traces": traces}


def render_trace_tree(events: list[dict]) -> str:
    """One trace's events as an indented parent→child tree.

    Events carry ``span``/``pspan`` ids; roots are events whose parent
    id is absent from the set (the minting process's root span).
    Children render under their parent ordered by start time.
    """
    by_id = {ev["span"]: ev for ev in events if ev.get("span")}
    children: dict[str, list[dict]] = {}
    roots: list[dict] = []
    for ev in events:
        pspan = ev.get("pspan")
        if pspan and pspan in by_id:
            children.setdefault(pspan, []).append(ev)
        else:
            roots.append(ev)
    lines: list[str] = []

    def walk(ev: dict, depth: int) -> None:
        pid = ev.get("pid")
        where = f" pid={pid}" if pid is not None else ""
        lines.append(
            f"{'  ' * depth}{ev['name']} {ev['ms']:.3f}ms{where}"
        )
        for child in sorted(children.get(ev.get("span") or "", []),
                            key=lambda e: e.get("t", 0.0)):
            walk(child, depth + 1)

    for root in roots:
        walk(root, 0)
    return "\n".join(lines)


def _trace_blocks(traces: dict, snapshot: dict, max_traces: int) -> list:
    """The report's blocks below the stats: the slowest pass of each root
    name (its spans summed by name), then one span tree a trace, those
    passes' first and the rest largest first, ``max_traces`` in all."""
    blocks = []
    slowest = snapshot.get("slowest_passes", [])
    for p in slowest:
        rows = sorted(p["spans"].items(), key=lambda kv: -kv[1][1])
        blocks.append(
            f"slowest {p['root']} pass: {p['ms']:.3f}ms"
            f" (trace {p.get('trace')})\n" + "\n".join(
                f"  {name}: {n} x, {total:.3f}ms, max {top:.3f}ms"
                for name, (n, total, top) in rows))
    first = [p.get("trace") for p in slowest]
    ranked = sorted(traces.items(),
                    key=lambda kv: (kv[0] not in first, -len(kv[1])))
    for tid, events in ranked[:max_traces]:
        blocks.append(
            f"trace {tid} ({len(events)} spans):\n"
            + render_trace_tree(events)
        )
    if len(traces) > max_traces:
        blocks.append(f"... {len(traces) - max_traces} more traces omitted")
    return blocks


def render_report(path, max_traces: int = 8) -> str:
    """The full metrics-report text for one trace file."""
    merged = merge_traces([path])
    spans = merged["spans_by_name"]
    snapshot = merged["snapshot"]
    header = [
        f"metrics trace: {path}",
        f"span events: {sum(len(v) for v in spans.values())}"
        + (f" (+{snapshot['dropped_events']} dropped)"
           if snapshot["dropped_events"] else ""),
    ]
    blocks = ["\n".join(header),
              stats_summary(snapshot, spans_by_name=spans).rstrip("\n")]
    blocks += _trace_blocks(merged["traces"], snapshot, max_traces)
    return "\n\n".join(blocks) + "\n"


def render_merged_report(paths, max_traces: int = 8) -> str:
    """The metrics-report text for several per-process trace files:
    fleet-merged stats plus one span tree per trace_id (largest first,
    capped at ``max_traces`` trees to keep the report readable)."""
    merged = merge_traces(paths)
    spans = merged["spans_by_name"]
    header = [
        "metrics traces: " + ", ".join(str(p) for p in paths),
        f"processes: {len(merged['metas'])}"
        f"  span events: {sum(len(v) for v in spans.values())}"
        f"  traces: {len(merged['traces'])}",
    ]
    blocks = ["\n".join(header), stats_summary(
        merged["snapshot"], spans_by_name=spans).rstrip("\n")]
    blocks += _trace_blocks(merged["traces"], merged["snapshot"], max_traces)
    return "\n\n".join(blocks) + "\n"
