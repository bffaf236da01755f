"""What a filtered load must hand back, from the generator's index alone
(``bench/generators/shortread_genome.py``): no byte of the file is read.

A record passes iff it is mapped, lies on the named contig, overlaps the
interval by its ``pos`` and the span of its own CIGAR (``pos < end`` and
``start < pos + max(span, 1)``), and carries every required and no forbidden
flag: upstream's ``loadBam`` with intervals (CanLoadBam.scala:109-133) and the
flag masks callers add.
"""

from __future__ import annotations

import numpy as np

from bench import bamgen

UNMAPPED = 4
#: The fixed fields a row is compared by, as ``ReadBatch`` names them.
COLUMNS = ("block_size", "ref_id", "pos", "l_read_name", "mapq", "bin",
           "n_cigar", "flag", "l_seq", "next_ref_id", "next_pos", "tlen")


def interval_of(contig: str) -> tuple:
    """``(ref_id, 0, length)``: a whole contig of the header the generators
    write (``bamgen.GRCH38``)."""
    names = [name for name, _ in bamgen.GRCH38]
    ref = names.index(contig)
    return ref, 0, bamgen.GRCH38[ref][1]


def passing(index: dict, interval: tuple, flags_required: int = 0,
            flags_forbidden: int = 0) -> np.ndarray:
    """The mask of the index's records that pass."""
    ref, start, end = interval
    fixed = index["fixed"]
    flag = fixed["flag"].astype(np.int64)
    pos = fixed["pos"].astype(np.int64)
    reach = pos + np.maximum(index["ref_span"], 1)
    return (
        ((flag & UNMAPPED) == 0) & (fixed["ref_id"] == ref)
        & (pos < end) & (start < reach)
        & ((flag & flags_required) == flags_required)
        & ((flag & flags_forbidden) == 0))


def expected_rows(index: dict, interval: tuple, flags_required: int = 0,
                  flags_forbidden: int = 0) -> dict:
    """The rows in file order: ``starts`` (flat offsets), every fixed
    column (int64) and ``crc`` (CRC32 of each row's record bytes)."""
    keep = passing(index, interval, flags_required, flags_forbidden)
    fixed = index["fixed"][keep]
    return {
        "starts": index["record_starts"][keep].astype(np.int64),
        "crc": index["crc"][keep].astype(np.int64),
        **{name: fixed[name].astype(np.int64) for name in COLUMNS},
    }
