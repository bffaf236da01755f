"""Registry exporters: Prometheus text snapshot + stats-format summary.

The JSONL trace exporter lives with the registry (``obs.export_jsonl`` —
it needs the event buffer); this module renders *snapshots*:

- ``prometheus_text``: the text exposition format — counters and gauges
  verbatim, histograms as summaries (quantiles from the retained
  samples). Metric names sanitize ``layer.stage`` dots to underscores.
- ``stats_summary``: the reference's descriptive-stats format
  (``core/stats.py`` — N/μ/σ, med/mad, percentile ladder), one block per
  histogram series, plus counter/gauge listings. This is the same shape
  the CLI golden reports use, so per-stage timings read like the rest of
  the toolkit's output.
- ``stage_totals``: compact ``{span_name: {count, total_ms}}`` dict —
  a per-stage breakdown small enough to attach to a run's record.
"""

from __future__ import annotations

import re

from spark_bam_tpu.core.stats import Stats, fmt_num

_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(name: str) -> str:
    name = _NAME_RE.sub("_", name)
    if not name or name[0].isdigit():
        name = "_" + name
    return name


def _prom_escape(value) -> str:
    # Exposition-format label value escaping: backslash first, then the
    # quote and newline (the three characters the format reserves).
    return (str(value)
            .replace("\\", "\\\\")
            .replace('"', '\\"')
            .replace("\n", "\\n"))


def _prom_labels(labels: dict) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{_prom_name(str(k))}="{_prom_escape(v)}"'
        for k, v in sorted(labels.items())
    )
    return "{" + inner + "}"


_LABEL_RE = re.compile(r'([a-zA-Z0-9_:]+)="((?:[^"\\]|\\.)*)"')
_UNESCAPE_RE = re.compile(r"\\(.)")
_UNESCAPES = {"n": "\n", '"': '"', "\\": "\\"}


def parse_prom_labels(block: str) -> dict:
    """Invert ``_prom_labels`` (round-trip testing + scrape tooling):
    parse ``{k="v",...}`` back into a dict, unescaping values in one
    left-to-right pass (sequential ``str.replace`` would corrupt a
    literal backslash-n)."""
    return {
        k: _UNESCAPE_RE.sub(lambda m: _UNESCAPES.get(m.group(1), m.group(1)), v)
        for k, v in _LABEL_RE.findall(block)
    }


def prometheus_text(snapshot: dict) -> str:
    """Render a ``Registry.snapshot()`` in Prometheus text format."""
    out: list[str] = []
    seen_type: set[str] = set()

    def type_line(name: str, kind: str):
        if name not in seen_type:
            seen_type.add(name)
            out.append(f"# TYPE {name} {kind}")

    for c in snapshot.get("counters", []):
        name = _prom_name(c["name"])
        type_line(name, "counter")
        out.append(f"{name}{_prom_labels(c.get('labels', {}))} {c['value']}")
    for g in snapshot.get("gauges", []):
        name = _prom_name(g["name"])
        type_line(name, "gauge")
        out.append(f"{name}{_prom_labels(g.get('labels', {}))} {g['value']}")
    for h in snapshot.get("hists", []):
        name = _prom_name(h["name"])
        type_line(name, "summary")
        labels = h.get("labels", {})
        values = sorted(h.get("values", []))
        if values:
            for q in (0.5, 0.9, 0.99):
                idx = min(len(values) - 1, int(q * len(values)))
                ql = dict(labels, quantile=q)
                out.append(f"{name}{_prom_labels(ql)} {values[idx]}")
        out.append(f"{name}_sum{_prom_labels(labels)} {h['sum']}")
        out.append(f"{name}_count{_prom_labels(labels)} {h['count']}")
        # Tail-sampler exemplars as comment lines: the classic text
        # format has no exemplar syntax (that's OpenMetrics), and a
        # comment keeps every scraper happy while still shipping the
        # trace ids next to the series they explain.
        for e in h.get("exemplars", []) or ():
            out.append(
                f"# exemplar {name}"
                f'{{trace_id="{_prom_escape(e[1])}"}} {e[0]}'
            )
    return "\n".join(out) + "\n"


def _series_title(name: str, labels: dict) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    return f"{name}[{inner}]"


def stats_summary(snapshot: dict, spans_by_name: dict | None = None) -> str:
    """Human summary in the reference stats format.

    ``spans_by_name`` ({name: [durations_ms]}), when given (the
    metrics-report path, rebuilt from trace events), replaces histogram
    series whose name matches — trace events are the full-fidelity
    source when both exist.
    """
    blocks: list[str] = []
    spans_by_name = dict(spans_by_name or {})
    hists = list(snapshot.get("hists", []))
    seen: set[str] = set()
    for h in hists:
        name = h["name"]
        values = spans_by_name.pop(name, None)
        if values is None:
            values = h.get("values", [])
        seen.add(name)
        title = _series_title(name, h.get("labels", {}))
        if values:
            blocks.append(f"{title}:\n{Stats(values).show()}")
        else:
            blocks.append(
                f"{title}:\nN: {h['count']}, sum: {fmt_num(h['sum'])}"
                f" (samples not retained)"
            )
    for name, values in sorted(spans_by_name.items()):
        blocks.append(f"{name}[unit=ms]:\n{Stats(values).show()}")

    counters = snapshot.get("counters", [])
    if counters:
        lines = ["counters:"]
        for c in sorted(counters, key=lambda c: c["name"]):
            lines.append(
                f"\t{_series_title(c['name'], c.get('labels', {}))}:"
                f" {c['value']}"
            )
        blocks.append("\n".join(lines))
    gauges = snapshot.get("gauges", [])
    if gauges:
        lines = ["gauges:"]
        for g in sorted(gauges, key=lambda g: g["name"]):
            peak = g.get("max")
            suffix = f" (peak {fmt_num(peak)})" if peak is not None else ""
            lines.append(
                f"\t{_series_title(g['name'], g.get('labels', {}))}:"
                f" {fmt_num(g['value'])}{suffix}"
            )
        blocks.append("\n".join(lines))
    return "\n\n".join(blocks) + ("\n" if blocks else "")


def merge_snapshots(snapshots: list[dict]) -> dict:
    """Merge per-worker ``Registry.snapshot()`` dicts into one fleet view.

    Counters sum; gauges sum values (queue depths and in-flight counts
    read as fleet totals) and take the max of peaks; histograms sum
    count/sum, merge min/max, and concatenate retained samples (capped)
    so fleet p50/p99 come from a cross-worker sample. Series identity is
    ``(name, sorted labels)`` — the registry's own key. Of the workers'
    slowest passes the fleet's is kept, a root name, and of their
    ``slow_passes`` the fleet's eight, slowest first.
    """
    from spark_bam_tpu.obs.registry import _HIST_SAMPLE_CAP, _SLOW_PASSES

    def key(entry):
        return (entry["name"], tuple(sorted(entry.get("labels", {}).items())))

    counters: dict = {}
    gauges: dict = {}
    hists: dict = {}
    slowest: dict = {}
    slow: dict = {}
    dropped = 0
    for snap in snapshots:
        if not snap:
            continue
        dropped += int(snap.get("dropped_events", 0))
        for p in snap.get("slowest_passes", []):
            kept = slowest.get(p["root"])
            if kept is None or p["ms"] > kept["ms"]:
                slowest[p["root"]] = p
        for p in snap.get("slow_passes", []):
            slow.setdefault(p["root"], []).append(p)
        for c in snap.get("counters", []):
            cur = counters.setdefault(
                key(c), {"name": c["name"],
                         "labels": dict(c.get("labels", {})), "value": 0})
            cur["value"] += c["value"]
        for g in snap.get("gauges", []):
            cur = gauges.setdefault(
                key(g), {"name": g["name"],
                         "labels": dict(g.get("labels", {})),
                         "value": 0.0, "max": None})
            cur["value"] += g["value"]
            gmax = g.get("max")
            if gmax is not None and (cur["max"] is None or gmax > cur["max"]):
                cur["max"] = gmax
        for h in snap.get("hists", []):
            cur = hists.setdefault(
                key(h), {"name": h["name"],
                         "labels": dict(h.get("labels", {})),
                         "count": 0, "sum": 0.0, "min": None, "max": None,
                         "values": [], "exemplars": []})
            cur["count"] += h["count"]
            cur["sum"] += h["sum"]
            if h.get("exemplars"):
                from spark_bam_tpu.obs.sampler import merge_exemplars

                cur["exemplars"] = merge_exemplars(
                    [cur["exemplars"], h["exemplars"]]
                )
            for bound, better in (("min", lambda a, b: b < a),
                                  ("max", lambda a, b: b > a)):
                v = h.get(bound)
                if v is not None and (cur[bound] is None
                                      or better(cur[bound], v)):
                    cur[bound] = v
            room = _HIST_SAMPLE_CAP - len(cur["values"])
            if room > 0:
                cur["values"].extend(h.get("values", [])[:room])
    for cur in hists.values():
        if not cur["exemplars"]:
            del cur["exemplars"]
    return {
        "counters": list(counters.values()),
        "gauges": list(gauges.values()),
        "hists": list(hists.values()),
        "dropped_events": dropped,
        "slowest_passes": list(slowest.values()),
        "slow_passes": [
            p for kept in slow.values()
            for p in sorted(kept, key=lambda p: -p["ms"])[:_SLOW_PASSES]],
    }


def stage_totals(snapshot: dict) -> dict:
    """``{span_name: {"count": n, "total_ms": x}}`` for every ms-unit
    histogram — the compact per-stage breakdown for bench captures."""
    out: dict[str, dict] = {}
    for h in snapshot.get("hists", []):
        if h.get("labels", {}).get("unit") != "ms":
            continue
        out[h["name"]] = {
            "count": h["count"],
            "total_ms": round(h["sum"], 3),
        }
    return out
