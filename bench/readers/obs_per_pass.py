"""A counter, or the summed time of a span, a whole-file pass.

A pass is one ``load.count`` span (``load.tpu_load.count_reads_tpu``). A
name the program's own catalogue (``obs/names.py``) does not hold reads as
nothing: the program under measurement has no such counter or span, and the
metric is left out of the line. A name it holds and did not emit over the
window reads 0, which is a reading (no escape in any pass, no pass that
started over).
"""

from __future__ import annotations

from bench.readers import counter_sum, histogram

PASS_SPAN = "load.count"


def read(args: dict, sources: dict):
    """``counter`` (its value) or ``span`` (its summed milliseconds) over
    the number of passes; None without a pass or without the name."""
    from spark_bam_tpu.obs.names import NAMES

    snapshot = sources["snapshot"]
    passes = histogram(snapshot, PASS_SPAN)
    name = args.get("counter") or args["span"]
    if passes is None or name not in NAMES:
        return None
    if "counter" in args:
        total = counter_sum(snapshot, name)
    else:
        span = histogram(snapshot, name)
        total = span["sum"] if span else 0.0
    return total / passes["count"]
