"""The control of every cell: the same check with the halo dropped.

A configuration states ``every_position_checked`` and ``exact``. The step
that would tempt a later PR is to cut the halo (4 MiB of every 28 MiB window,
64 KiB of every 1 MiB serve row): windows are then counted each for itself,
as if each were a whole file, and a record that straddles a seam can no
longer be proven. This does exactly that with the program's own flag pass and
chain walk (``check.vectorized.check_flat``) over the host-inflated file, so
the answer it gives is what such a change would answer. It must NOT equal the
index: ``bench/tests/test_control.py`` holds that, and PERF.md records the
chip runs. The benchmark's own runs never call this.
"""

from __future__ import annotations

import numpy as np


def count_without_halo(path, lo: int, hi: int, window: int) -> int:
    """Record starts the check accepts in flat ``[lo, hi)`` when the file is
    cut into independent ``window``-byte pieces."""
    from spark_bam_tpu.bam.header import read_header
    from spark_bam_tpu.bgzf.flat import flatten_file
    from spark_bam_tpu.check.vectorized import check_flat

    data = flatten_file(path).data
    lengths = np.array(read_header(path).contig_lengths.lengths_list(),
                       dtype=np.int32)
    total = 0
    for s in range(0, hi, window):
        e = min(s + window, hi)
        if e <= lo:
            continue
        verdict = check_flat(data[s:e], lengths, at_eof=True).verdict
        total += int(verdict[max(lo - s, 0):].sum())
    return total
