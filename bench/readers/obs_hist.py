"""A statistic of one obs histogram (spans feed a histogram of their name)."""

from __future__ import annotations

import statistics

from bench.readers import counter_sum, histogram


def read(args: dict, sources: dict):
    """``stat`` of ``histogram`` (``median``, ``mean``, ``sum``, ``max``),
    over ``per_counter``'s value where given. None when nothing was
    observed."""
    h = histogram(sources["snapshot"], args["histogram"])
    if h is None:
        return None
    stat = args["stat"]
    if stat == "sum":
        value = h["sum"]
    elif stat == "mean":
        value = h["sum"] / h["count"]
    elif stat == "max":
        value = h["max"]
    else:
        value = float(getattr(statistics, stat)(h["values"]))
    if "per_counter" in args:
        n = counter_sum(sources["snapshot"], args["per_counter"])
        if not n:
            return None
        value /= n
    return value
