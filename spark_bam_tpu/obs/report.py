"""Render JSONL metrics traces as a human report (reference stats format).

The ``spark-bam-tpu metrics-report`` subcommand consumes this: parse the JSONL a ``--metrics-out`` run emitted,
regroup span events by name, and render per-stage duration statistics
with the same ``core/stats.py`` formatting the golden CLI reports use.

A whole-file pass (``count-reads`` / ``check-bam`` on the device) is a
trace of its own (``obs.pass_span``), so its spans render as one tree a
pass, the slowest passes the registry kept first (eight a root name at
most, each with its account of the host on the tree's first line), with
the slowest's spans summed by name above them.

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
        elif kind == "slow_pass":
            snapshot.setdefault("slow_passes", []).append(ev)
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


def _account(p: dict) -> str:
    """A kept pass's account of the host, for its tree's first line."""
    return " ".join(f"{key}={p[key]:.3f}"
                    for key in ("stop_ms", "gc_ms", "cpu_ms") if key in p)


def _trace_blocks(traces: dict, snapshot: dict, max_traces: int) -> list:
    """The report's blocks below the stats: the slowest pass of each root
    name (its spans summed by name), then one span tree a trace: the slow
    passes the registry kept first, slowest first and all of them (eight a
    root name at most), each with its account of the host (``stop_ms``,
    ``gc_ms``, ``cpu_ms``) on its first line; then the rest, largest first,
    up to ``max_traces`` trees in all."""
    blocks = []
    slowest = snapshot.get("slowest_passes", [])
    for p in slowest:
        rows = sorted(p["spans"].items(), key=lambda kv: -kv[1][1])
        blocks.append(
            f"slowest {p['root']} pass: {p['ms']:.3f}ms"
            f" (trace {p.get('trace')})\n" + "\n".join(
                f"  {name}: {n} x, {total:.3f}ms, max {top:.3f}ms"
                for name, (n, total, top) in rows))
    # A program from before the eight kept the slowest alone.
    kept = {p.get("trace"): p
            for p in snapshot.get("slow_passes") or slowest}
    first = [tid for tid in kept if tid in traces]
    rest = sorted((tid for tid in traces if tid not in kept),
                  key=lambda tid: -len(traces[tid]))
    shown = first + rest[:max(0, max_traces - len(first))]
    for tid in shown:
        account = _account(kept[tid]) if tid in kept else ""
        blocks.append(
            f"trace {tid} ({len(traces[tid])} spans):"
            + (" " + account if account else "") + "\n"
            + render_trace_tree(traces[tid])
        )
    if len(traces) > len(shown):
        blocks.append(f"... {len(traces) - len(shown)} more traces omitted")
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
