"""The plain reference of the load: every record of a BAM decoded one at a
time, and the filter of ``loadBam`` with intervals applied to each.

Independent of the checker and the parser on purpose: the file is inflated
by the standard library (a BGZF file is a gzip file of many members), the
header and the records are walked with ``struct`` from the header's end by
each record's ``block_size``, the reference span is summed from the
record's own CIGAR, and a record passes iff it is mapped, lies on a named
contig, ``pos < end`` and ``start < pos + max(span, 1)`` of one of that
contig's intervals, and the flag masks hold (reference
CanLoadBam.scala:109-133; without loci the flag masks alone decide). The
tests hold ``load.tpu_load.stream_read_batches`` to it row for row.
"""

from __future__ import annotations

import gzip
import struct

import numpy as np

from spark_bam_tpu.load.intervals import LociSet

#: The fixed block after ``block_size``: ``struct`` format and field names.
_FIXED = struct.Struct("<iiBBHHHiiii")
_FIELDS = ("ref_id", "pos", "l_read_name", "mapq", "bin", "n_cigar", "flag",
           "l_seq", "next_ref_id", "next_pos", "tlen")
_REF_CONSUMING = frozenset((0, 2, 3, 7, 8))  # M D N = X


def read_header(flat: bytes) -> tuple:
    """``(contigs, end)``: the ``(name, length)`` of the dictionary in
    order, and the offset of the first record."""
    if flat[:4] != b"BAM\x01":
        raise ValueError("not a BAM")
    (l_text,) = struct.unpack_from("<i", flat, 4)
    at = 8 + l_text
    (n_ref,) = struct.unpack_from("<i", flat, at)
    at += 4
    contigs = []
    for _ in range(n_ref):
        (l_name,) = struct.unpack_from("<i", flat, at)
        name = flat[at + 4: at + 4 + l_name - 1].decode()
        (length,) = struct.unpack_from("<i", flat, at + 4 + l_name)
        contigs.append((name, length))
        at += 8 + l_name
    return contigs, at


def reference_span(flat: bytes, at: int, n_cigar: int) -> int:
    span = 0
    for (word,) in struct.iter_unpack("<I", flat[at: at + 4 * n_cigar]):
        if word & 0xF in _REF_CONSUMING:
            span += word >> 4
    return span


def load_rows(path, loci: str | None = None, flags_required: int = 0,
              flags_forbidden: int = 0) -> dict:
    """The rows of ``path`` that pass, in file order: ``starts`` (flat
    offsets, int64), every fixed field and ``ref_span`` (int64 arrays), and
    ``records`` (each row's bytes, ``block_size`` included)."""
    with open(path, "rb") as f:
        flat = gzip.decompress(f.read())
    contigs, at = read_header(flat)
    wanted = None
    if loci is not None:
        names = [name for name, _ in contigs]
        wanted = {}
        for name, ivs in LociSet.parse(loci).intervals.items():
            ref = names.index(name)
            wanted[ref] = ivs or [(0, contigs[ref][1])]
    out: dict = {k: [] for k in ("starts", "block_size", *_FIELDS,
                                 "ref_span", "records")}
    while at < len(flat):
        (block_size,) = struct.unpack_from("<i", flat, at)
        fields = dict(zip(_FIELDS, _FIXED.unpack_from(flat, at + 4)))
        span = reference_span(
            flat, at + 36 + fields["l_read_name"], fields["n_cigar"])
        flag, pos = fields["flag"], fields["pos"]
        passes = (flag & flags_required) == flags_required and not (
            flag & flags_forbidden)
        if passes and wanted is not None:
            passes = not flag & 4 and any(
                pos < end and start < pos + max(span, 1)
                for start, end in wanted.get(fields["ref_id"], ()))
        if passes:
            out["starts"].append(at)
            out["block_size"].append(block_size)
            for key, value in fields.items():
                out[key].append(value)
            out["ref_span"].append(span)
            out["records"].append(flat[at: at + 4 + block_size])
        at += 4 + block_size
    return {k: v if k == "records" else np.array(v, dtype=np.int64)
            for k, v in out.items()}
