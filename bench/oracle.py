"""Answers from the generator's index alone (``bench/bamgen.write_bam``)."""

from __future__ import annotations

import numpy as np


def whole_file_count(index: dict) -> int:
    return len(index["record_starts"])


def flat_range(index: dict, start: int, end: int) -> tuple:
    """Flat ``[lo, hi)`` of the members whose compressed starts fall in
    ``[start, end)``: what a split of the reference's check path owns."""
    starts, flat = index["block_starts"], index["block_flat"]
    size = int(index["uncompressed_bytes"])

    def at(offset: int) -> int:
        i = int(np.searchsorted(starts, offset, side="left"))
        return int(flat[i]) if i < len(flat) else size

    lo = max(int(index["header_end"]), at(start))
    return lo, max(lo, at(end))


def ranged_count(index: dict, start: int, end: int) -> int:
    """Records that START inside ``flat_range(start, end)``."""
    lo, hi = flat_range(index, start, end)
    rs = index["record_starts"]
    return int(np.searchsorted(rs, hi, side="left")
               - np.searchsorted(rs, lo, side="left"))
