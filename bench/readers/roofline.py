"""The fused window program's share of its memory roofline.

The least the program could move for one window: the packed token planes read
once, and the window's bytes written once (by resolve and assembly) and read
once (by the check). It does far more today; this says how far that is from
what the chip's memory could do, not how well the present algorithm runs.
"""

from __future__ import annotations

import statistics

from bench.readers import counter_sum, histogram


def least_bytes(token_bytes: float, kernel_window_bytes: int) -> float:
    return token_bytes + 2.0 * kernel_window_bytes


def read(args: dict, sources: dict):
    h = histogram(sources["snapshot"], args["time_histogram"])
    windows = counter_sum(sources["snapshot"], args["per_counter"])
    peaks = sources["peaks"]
    if h is None or not windows or peaks is None:
        return None
    token_bytes = counter_sum(
        sources["snapshot"], args["token_bytes_counter"]) / windows
    seconds = float(getattr(statistics, args["stat"])(h["values"])) / 1e3
    least_s = least_bytes(
        token_bytes, int(sources["config"]["shapes"]["kernel_window_bytes"])
    ) / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
