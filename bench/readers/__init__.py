"""Per-layer metric readers: ``read(args, sources) -> number or None``.

``sources`` holds what a traced run gathered: ``snapshot`` of the obs registry
over the window, ``profile`` (the reduced device trace, or None), ``device``,
``peaks`` of the device kind and ``config``. A reader that finds nothing to
read returns None and the metric is left out of the line.
"""

from __future__ import annotations


def histogram(snapshot: dict, name: str):
    """The obs histogram ``name`` with its label sets merged, or None."""
    found = [h for h in snapshot["hists"] if h["name"] == name and h["count"]]
    if not found:
        return None
    return {
        "count": sum(h["count"] for h in found),
        "sum": sum(h["sum"] for h in found),
        "max": max(h["max"] for h in found),
        "values": [v for h in found for v in h["values"]],
    }


def counter_sum(snapshot: dict, name: str):
    return sum(c["value"] for c in snapshot["counters"] if c["name"] == name)
