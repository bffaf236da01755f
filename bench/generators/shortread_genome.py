"""A whole-genome slice of a 30x short-read sample: ``shortread``'s reads on
all 25 primary GRCh38 contigs, in header order, with duplicates marked.

Contig ``k`` of ``bamgen.GRCH38`` is one call of ``shortread.generate`` (the
configuration's ``params`` with ``contig`` = k, ``origin`` the ``k``-th of
``origins`` and a seed derived from ``seed`` and ``k``, as ``cohort.py``
makes files), cut to its share of the file: ``target_bytes`` times the
contig's share of the assembly's bases, by the rule ``shortread`` cuts a file
with (the records that start before that many bytes, so a contig's share is
right to within a record). ``shortread`` cannot draw a contig of under
``MIN_SLICE`` bytes; a smaller share is cut from a slice of that size. The
slices' records are joined in header order under the one header they all
wrote and written once (``bamgen.write_bam``), so the file is coordinate
sorted as a sample's is and chr20 is one run of 2.087% of it.

Before that, ``duplicate_share`` of the read names, drawn from ``seed``, get
0x400 set on every record that carries them (both mates of a pair; a mate
whose partner fell behind its slice's cut alone), in place.

Returns ``write_bam``'s index and what this generator wrote of every record,
in file order, read from the bytes it handed to ``write_bam`` and from
nothing else: ``fixed`` (the 36 fixed bytes, ``shortread.FIXED``),
``ref_span`` (the reference bases its own CIGAR consumes) and ``crc`` (CRC32
of the record's bytes, ``block_size`` included); ``contig_records`` counts
the records a contig. Nothing here imports the program.
"""

from __future__ import annotations

import contextlib
import zlib

import numpy as np

from bench import bamgen
from bench.generators import shortread

#: Fewest bytes ``shortread.generate`` is asked for: its reference must be
#: longer than its longest insert (1,000 bases), and is 2 x read_length /
#: coverage bases a fragment.
MIN_SLICE = 262144
DUPLICATE = 0x400
#: CIGAR operations that consume reference bases: M, D, N, =, X.
_REF_CONSUMING = (1 << 0) | (1 << 2) | (1 << 3) | (1 << 7) | (1 << 8)
_OWN = ("origins", "duplicate_share")


def slice_seed(seed: int, k: int) -> int:
    return int(np.random.default_rng([int(seed), 0x6E0E, k]).integers(2**31))


def shares(target_bytes: int) -> np.ndarray:
    """Bytes of each contig's slice: its share of the assembly's bases."""
    lengths = np.array([ln for _, ln in bamgen.GRCH38], dtype=np.float64)
    return np.floor(target_bytes * lengths / lengths.sum()).astype(np.int64)


@contextlib.contextmanager
def _kept_not_written():
    """``shortread.generate`` hands its header, records and their starts to
    ``bamgen.write_bam`` and keeps none of them; a slice is wanted as bytes,
    not as a file to inflate again. For the length of the block the writer
    is one that keeps what it is given (``kept``) and deflates nothing."""
    kept: list = []
    real = bamgen.write_bam

    def keep(path, header, records, record_starts, threads=8):
        kept.append((header, records, np.asarray(record_starts, np.int64)))
        return {}

    bamgen.write_bam = keep
    try:
        yield kept
    finally:
        bamgen.write_bam = real


def _name_ids(records: np.ndarray, starts: np.ndarray) -> np.ndarray:
    """For every record the number of its read name among the file's."""
    l_name = records[starts + 12].astype(np.int64)
    width = int(l_name.max())
    cols = np.arange(width)[None, :]
    at = np.minimum(starts[:, None] + 36 + cols, len(records) - 1)
    names = np.where(cols < l_name[:, None], records[at], 0).astype(np.uint8)
    _, ids = np.unique(
        np.ascontiguousarray(names).view(f"S{width}").ravel(),
        return_inverse=True)
    return ids


def _reference_spans(records: np.ndarray, starts: np.ndarray,
                     fixed: np.ndarray) -> np.ndarray:
    span = np.zeros(len(starts), dtype=np.int64)
    at = starts + 36 + fixed["l_read_name"].astype(np.int64)
    n_cigar = fixed["n_cigar"].astype(np.int64)
    for k in range(int(n_cigar.max())):
        has = n_cigar > k
        b = records[(at[has] + 4 * k)[:, None] + np.arange(4)[None, :]]
        op = (b[:, 0] | (b[:, 1].astype(np.int64) << 8)
              | (b[:, 2].astype(np.int64) << 16)
              | (b[:, 3].astype(np.int64) << 24))
        span[has] += np.where((_REF_CONSUMING >> (op & 0xF)) & 1, op >> 4, 0)
    return span


def generate(params: dict, seed: int, target_bytes: int, path) -> dict:
    base = {k: v for k, v in params.items() if k not in _OWN}
    parts, firsts, header, size = [], [], None, 0
    with _kept_not_written() as kept:
        for k, want in enumerate(shares(target_bytes).tolist()):
            shortread.generate(
                {**base, "contig": k, "origin": int(params["origins"][k])},
                slice_seed(seed, k), max(want, MIN_SLICE), path)
            header, records, starts = kept.pop()
            # ``shortread``'s own cut: through the first record that ends at
            # or past the slice's bytes.
            ends = np.append(starts[1:], len(records))
            keep = min(int(np.searchsorted(ends, want, side="left")) + 1,
                       len(starts))
            parts.append(records[: int(ends[keep - 1])])
            firsts.append(starts[:keep] + size)
            size += len(parts[-1])
    records = np.frombuffer(b"".join(parts), dtype=np.uint8).copy()
    del parts
    starts = np.concatenate(firsts)

    rng = np.random.default_rng([int(seed), 0xD0B1])
    ids = _name_ids(records, starts)
    marked = rng.random(int(ids.max()) + 1) < float(params["duplicate_share"])
    records[starts[marked[ids]] + 19] |= DUPLICATE >> 8  # flag's high byte

    fixed = np.ascontiguousarray(
        records[starts[:, None] + np.arange(36)[None, :]]
    ).view(shortread.FIXED).ravel()
    ends = np.append(starts[1:], len(records))
    view = memoryview(records)
    crc = np.fromiter(
        (zlib.crc32(view[s:e]) for s, e in zip(starts.tolist(),
                                               ends.tolist())),
        dtype=np.int64, count=len(starts))
    index = bamgen.write_bam(path, header, records.tobytes(), starts)
    index.update({
        "record_bytes_mean": float(len(records) / len(starts)),
        "fixed": fixed, "crc": crc,
        "ref_span": _reference_spans(records, starts, fixed),
        "contig_records": np.array([len(f) for f in firsts], dtype=np.int64),
    })
    return index
