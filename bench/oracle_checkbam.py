"""check-bam's answer, and the wrong truth it is scored against, from the
generator's index alone (``bench/bamgen.write_bam``): no checker of the repo
has a hand in it.

The cell's truth is the file's record starts with a set D of them dropped
and a set A of positions added that start no record, both drawn from the
seed. An exact checker calls every record and nothing else, so against that
truth it must report ``tp = records - |D|``, a false positive at every
position of D (a record the truth lacks), a false negative at every position
of A, and ``positions`` = the file's uncompressed bytes.
"""

from __future__ import annotations

import numpy as np


def perturb(index: dict, seed: int, dropped: int, added: int,
            seam_drops: int, row_owned_bytes: int) -> tuple:
    """``(D, A)``, sorted flat offsets. ``seam_drops`` of D are the first
    record at or after ``k * row_owned_bytes``, k = 1.. (a row seam of the
    engine's plan: a seam is owned once), as far as the file reaches; the
    rest of D is drawn among the other records, A among the positions from
    the header's end on that start no record."""
    rng = np.random.default_rng([int(seed), 0xC4EC])
    records = index["record_starts"]
    seams = np.arange(1, seam_drops + 1, dtype=np.int64) * row_owned_bytes
    at = np.searchsorted(records, seams)
    at_seams = records[at[at < len(records)]]
    others = rng.choice(np.setdiff1d(records, at_seams),
                        dropped - len(at_seams), replace=False)
    free = np.setdiff1d(
        rng.integers(index["header_end"], index["uncompressed_bytes"],
                     4 * added), records)
    return (np.sort(np.concatenate([at_seams, others])),
            np.sort(rng.choice(free, added, replace=False)))


def truth(index: dict, dropped, added) -> np.ndarray:
    return np.sort(np.concatenate(
        [np.setdiff1d(index["record_starts"], dropped), added]))


def sidecar_text(index: dict, flats: np.ndarray) -> str:
    """``flats`` as a ``.records`` sidecar: ``blockPos,offset`` a line
    (upstream's IndexRecords.scala:149), the member's compressed start and
    the offset inside its payload."""
    b = np.searchsorted(index["block_flat"], flats, side="right") - 1
    rows = np.stack([index["block_starts"][b], flats - index["block_flat"][b]])
    return "\n".join(f"{p},{o}" for p, o in rows.T.tolist()) + "\n"


def expected(index: dict, dropped, added) -> dict:
    records, total = len(index["record_starts"]), index["uncompressed_bytes"]
    tp = records - len(dropped)
    return {
        "true_positives": tp,
        "false_positives": len(dropped),
        "false_negatives": len(added),
        "true_negatives": total - tp - len(dropped) - len(added),
        "positions": total,
        "false_positive_positions": [int(p) for p in dropped],
        "false_negative_positions": [int(p) for p in added],
    }
