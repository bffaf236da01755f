"""A count program's share of its memory roofline.

The least the work could move for one window, whatever implements it: the
kernel window written once (by the put) and read once (by the check). The
program does far more today (the word view, the lane stage's gathers); this
says how far that is from what the chip's memory could do, not how well the
present algorithm runs. Memory bounds it: the program makes no matrix
product.
"""

from __future__ import annotations

import statistics

from bench.readers import histogram


def least_bytes(kernel_window_bytes: int) -> float:
    return 2.0 * kernel_window_bytes


def read(args: dict, sources: dict):
    h = histogram(sources["snapshot"], args["time_histogram"])
    peaks = sources["peaks"]
    if h is None or not h["values"] or peaks is None:
        return None
    seconds = float(getattr(statistics, args["stat"])(h["values"])) / 1e3
    least_s = least_bytes(
        int(sources["config"]["shapes"]["kernel_window_bytes"])
    ) / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
