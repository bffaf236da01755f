"""The confusion step's share of its memory roofline.

The least a check-bam step could move: each of its rows' kernel window read
once, and the truth beside it (a byte a position) read once. The verdicts
never need to leave the chip: the step's results are four sums and a list.
It does far more today (the lane stage, the scatter over every position);
this says how far that is from what the chip's memory could do, not how well
the present algorithm runs. Memory bounds it: the step makes no matrix
product.
"""

from __future__ import annotations

import statistics

from bench.readers import counter_sum, histogram


def least_bytes(rows: float, kernel_window_bytes: int,
                truth_bytes_per_position: int = 1) -> float:
    return rows * kernel_window_bytes * (1.0 + truth_bytes_per_position)


def read(args: dict, sources: dict):
    snapshot = sources["snapshot"]
    h = histogram(snapshot, args["time_histogram"])
    steps = counter_sum(snapshot, args["steps_counter"])
    rows = counter_sum(snapshot, args["rows_counter"])
    peaks = sources["peaks"]
    if h is None or not steps or not rows or peaks is None:
        return None
    shapes = sources["config"]["shapes"]
    seconds = float(getattr(statistics, args["stat"])(h["values"])) / 1e3
    least_s = least_bytes(
        rows / steps, int(shapes["kernel_window_bytes"]),
        int(shapes["truth_bytes_per_position"]),
    ) / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
