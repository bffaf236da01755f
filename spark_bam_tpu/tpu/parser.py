"""Batched BAM record parsing on device.

Replaces per-record codec decoding (HTSJDK ``BAMRecordCodec`` in the
reference, RecordStream.scala:48-57) with columnar gathers: given a flat
uncompressed buffer and the record-start offsets the checker produced, every
fixed field of every record is extracted in one fused gather pass, and
interval/flag filters evaluate on-device so only surviving rows return to
the host (BASELINE.json: "returns parsed reads with interval/flag filters
already applied on-device").

Reference spans (for interval overlap) come from a bounded on-device cigar
scan: records with more than ``CIGAR_SCAN_CAP`` ops are flagged and finished
on host — the same escape-不-guess policy as the checker.

Two programs run these bodies. ``parse_records`` / ``interval_flag_filter``
take a buffer and the starts somebody found in it (``parse_flat_records``:
the whole-file load, the served ``batch`` op, the spills). The streaming
load parses where it checks: ``parse_lanes`` and ``rows_pass`` are the
``parse`` and ``filter`` scopes of ``checker.load_window``, at the lanes the
check accepted, reading the word view the check made (``checker._lane_rows``:
a record's fixed block is one fetched row, its CIGAR one more for every
sixteen operations), and ``unpack_rows`` is the host's end of that program.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

CIGAR_SCAN_CAP = 64  # ops scanned on device; beyond ⇒ host fallback

_I32 = jnp.int32

# Cigar ops that consume reference bases: M, D, N, =, X.
_REF_CONSUMING = (1 << 0) | (1 << 2) | (1 << 3) | (1 << 7) | (1 << 8)


def _next_pow2(n: int) -> int:
    return 1 << max(0, (max(n, 1) - 1).bit_length())


def _u32(p, idx):
    return (
        jnp.take(p, idx, mode="clip").astype(jnp.uint32)
        | (jnp.take(p, idx + 1, mode="clip").astype(jnp.uint32) << 8)
        | (jnp.take(p, idx + 2, mode="clip").astype(jnp.uint32) << 16)
        | (jnp.take(p, idx + 3, mode="clip").astype(jnp.uint32) << 24)
    )


def _i32(p, idx):
    return lax.bitcast_convert_type(_u32(p, idx), jnp.int32)


#: The fixed block as the nine little-endian words it is; ``fixed_columns``
#: names what is in them.
FIXED_WORDS = 9

#: Words a row of ``checker.load_window``'s table: the fixed block, the
#: record's position in its window and the reference span of its CIGAR.
ROW_WORDS = FIXED_WORDS + 2

#: CIGAR operations one fetched row of the word view holds behind any
#: position (``checker.WORD_REACH`` bytes of it).
_ROW_OPS = 16


def fixed_columns(words) -> dict:
    """The twelve fixed fields out of the fixed block's nine words (int32
    arrays, numpy's or jax's, of one shape)."""
    block_size, ref_id, pos, lnm, fnc, l_seq, next_ref_id, next_pos, tlen = (
        words)
    return {
        "block_size": block_size,
        "ref_id": ref_id,
        "pos": pos,
        "l_read_name": lnm & 0xFF,
        "mapq": (lnm >> 8) & 0xFF,
        "bin": (lnm >> 16) & 0xFFFF,
        "n_cigar": fnc & 0xFFFF,
        "flag": (fnc >> 16) & 0xFFFF,
        "l_seq": l_seq,
        "next_ref_id": next_ref_id,
        "next_pos": next_pos,
        "tlen": tlen,
    }


def _op_spans(ops, counted):
    """Reference bases the CIGAR words ``ops`` (int32) consume where
    ``counted``, summed over the last axis."""
    consumes = ((_I32(_REF_CONSUMING) >> (ops & 0xF)) & 1) == 1
    length = lax.shift_right_logical(ops, _I32(4))
    return jnp.sum(jnp.where(consumes & counted, length, 0), axis=-1,
                   dtype=_I32)


@functools.partial(jax.jit, static_argnames=("cigar_cap",))
def parse_records(
    padded: jnp.ndarray,   # (N+pad,) uint8 flat uncompressed bytes
    starts: jnp.ndarray,   # (M,) int32 record-start offsets (padding: -1)
    cigar_cap: int = CIGAR_SCAN_CAP,
):
    """Columnar fixed-field extraction for M records in one pass.

    Returns a dict of (M,) arrays; ``valid`` masks real rows, ``span_exact``
    marks rows whose reference span was fully resolved on device.
    """
    valid = starts >= 0
    s = jnp.maximum(starts, 0)
    cols = fixed_columns(
        [_i32(padded, s + 4 * k) for k in range(FIXED_WORDS)])

    # Bounded cigar scan: ref span = Σ len over ref-consuming ops.
    ks = jnp.arange(cigar_cap, dtype=_I32)
    cig_start = s + 36 + cols["l_read_name"]
    span = _op_spans(
        _i32(padded, cig_start[:, None] + 4 * ks[None, :]),
        ks[None, :] < cols["n_cigar"][:, None])
    return {
        "valid": valid,
        **cols,
        "name_offset": s + 36,
        "ref_span": span,
        "span_exact": cols["n_cigar"] <= cigar_cap,
    }


@jax.named_scope("parse")
def parse_lanes(U, pos, records):
    """``parse_records`` at the lanes of a block of ``checker.load_window``:
    ``(words, cols, span, span_exact)`` of the records at the window
    positions ``pos`` ((K,) int32; whatever stands where ``records`` is
    false is not one and costs no CIGAR scan).

    The nine words of the fixed block come out of ONE fetched row of the
    word view ``U`` (``checker._lane_words``) where ``_u32`` gathers 36
    bytes, and the CIGAR is scanned sixteen operations a fetched row, as
    many rows as the block's longest CIGAR asks for up to
    ``CIGAR_SCAN_CAP`` operations: one for short reads, where ``parse_records`` gathers 256
    bytes a record whatever its CIGAR."""
    from spark_bam_tpu.tpu.checker import WORD_ROW, _lane_rows, _lane_words

    words = _lane_words(U, pos, tuple(range(0, 4 * FIXED_WORDS, 4)))
    cols = fixed_columns(words)
    n_cigar = jnp.where(
        records, jnp.minimum(cols["n_cigar"], CIGAR_SCAN_CAP), 0)
    cig_start = pos + 36 + cols["l_read_name"]
    column = jnp.arange(WORD_ROW, dtype=_I32)[None, :]

    def scan_row(c, span):
        at = cig_start + c * (4 * _ROW_OPS)
        held, start, last = _lane_rows(U, at)
        # Byte offsets behind the row's first operation: one every four.
        rel = column - (jnp.clip(at, 0, last) - start)[:, None]
        here = (rel >= 0) & ((rel & 3) == 0) & (rel < 4 * _ROW_OPS) & (
            c * _ROW_OPS + (rel >> 2) < n_cigar[:, None])
        return span + _op_spans(held, here)

    rows = lax.div(jnp.max(n_cigar) + _I32(_ROW_OPS - 1), _I32(_ROW_OPS))
    span = lax.fori_loop(0, rows, scan_row, jnp.zeros(pos.shape, dtype=_I32))
    return words, cols, span, cols["n_cigar"] <= CIGAR_SCAN_CAP


def _overlaps(ref, pos, end, intervals):
    """A row ``[pos, end)`` on contig ``ref`` against (R, 3) rows of
    (ref_id, start, end): does it overlap one of them."""
    return (
        (ref[:, None] == intervals[:, 0][None, :])
        & (pos[:, None] < intervals[:, 2][None, :])
        & (intervals[:, 1][None, :] < end[:, None])
    ).any(axis=1)


def _flags_hold(flag, flags_required, flags_forbidden):
    return ((flag & flags_required) == flags_required) & (
        (flag & flags_forbidden) == 0)


@functools.partial(jax.jit, static_argnames=())
def interval_flag_filter(
    cols: dict,
    intervals: jnp.ndarray,      # (R, 3) int32 rows of (ref_id, start, end)
    flags_required: jnp.ndarray,  # () int32: all these bits must be set
    flags_forbidden: jnp.ndarray,  # () int32: none of these bits may be set
):
    """On-device record filter: genomic interval overlap + SAM flag masks.

    Unmapped reads never overlap an interval (reference loadBamIntervals
    region semantics, CanLoadBam.scala:109-133).
    """
    pos, ref, flag = cols["pos"], cols["ref_id"], cols["flag"]
    end = pos + jnp.maximum(cols["ref_span"], 1)
    mapped = (flag & 4) == 0
    return (
        cols["valid"] & mapped & (ref >= 0)
        & _overlaps(ref, pos, end, intervals)
        & _flags_hold(flag, flags_required, flags_forbidden))


class RowFilter(NamedTuple):
    """What a load keeps of the records it parses, as the operand of
    ``checker.load_window`` and as the host tests the rows it finishes
    itself (``passes``): ``interval_flag_filter``'s arguments, and whether
    loci were named at all (without them the flags alone decide, and an
    unmapped read passes unless a flag excludes it: ``_apply_filter``)."""

    intervals: np.ndarray        # (R, 3) int32 rows of (ref_id, start, end)
    has_loci: np.ndarray         # () bool
    flags_required: np.ndarray   # () int32
    flags_forbidden: np.ndarray  # () int32

    @classmethod
    def of(cls, intervals=None, flags_required: int = 0,
           flags_forbidden: int = 0) -> "RowFilter":
        """``intervals``: (R, 3) rows or None. The rows pad to a power of
        two with a contig no record names, so the window program compiles
        for a handful of table heights and not for every caller's."""
        given = 0 if intervals is None else len(intervals)
        rows = np.zeros((_next_pow2(max(given, 4)), 3), dtype=np.int32)
        rows[:, 0] = -2
        if given:
            rows[:given] = intervals
        return cls(rows, np.bool_(intervals is not None),
                   np.int32(flags_required), np.int32(flags_forbidden))

    def passes(self, cols: dict) -> np.ndarray:
        """The rows of host columns (``parse_flat_records``') that pass."""
        return _rows_pass(
            np, cols["valid"], cols, cols["ref_span"], cols["span_exact"],
            self)


def _rows_pass(xp, records, cols, span, span_exact, rows: RowFilter):
    pos, ref, flag = cols["pos"], cols["ref_id"], cols["flag"]
    end = xp.where(
        span_exact, pos + xp.maximum(span, 1), np.iinfo(np.int32).max)
    mapped = (flag & 4) == 0
    on_loci = mapped & (ref >= 0) & _overlaps(ref, pos, end, rows.intervals)
    return (
        records & (on_loci | ~rows.has_loci)
        & _flags_hold(flag, rows.flags_required, rows.flags_forbidden))


@jax.named_scope("filter")
def rows_pass(records, cols, span, span_exact, rows: RowFilter):
    """``interval_flag_filter`` at the lanes of a block of
    ``checker.load_window``, and the flag predicate alone where the caller
    named no loci (``_apply_filter``'s two halves). A row whose CIGAR the
    scan did not finish passes on what is known of it, as if it reached to
    the contig's end: the host finishes its span and tests it again
    (``unpack_rows``)."""
    return _rows_pass(jnp, records, cols, span, span_exact, rows)


def cigar_span(buf: np.ndarray, start: int, l_read_name: int,
               n_cigar: int) -> int:
    """The reference span of the record at ``start`` of host bytes, from
    its whole CIGAR."""
    at = start + 36 + l_read_name
    ops = np.frombuffer(
        np.ascontiguousarray(buf[at: at + 4 * n_cigar]), dtype="<u4")
    consumes = (_REF_CONSUMING >> (ops & 0xF)) & 1
    return int(((ops >> 4) * consumes).sum())


def unpack_rows(table: np.ndarray, buf: np.ndarray,
                rows: RowFilter) -> tuple:
    """``checker.load_window``'s rows as ``parse_flat_records``' columns:
    ``(columns, starts, fixups)``. ``table`` is the (ROW_WORDS, n) head of
    the program's table, ``buf`` the window's bytes, ``starts`` the rows'
    positions in it. A row with more than ``CIGAR_SCAN_CAP`` operations has its
    span finished here from the bytes and is tested again (``fixups`` counts
    them); whatever then fails is dropped."""
    table = np.asarray(table)
    cols = fixed_columns(table[:FIXED_WORDS])
    starts, span = table[FIXED_WORDS], table[FIXED_WORDS + 1].copy()
    over = np.flatnonzero(cols["n_cigar"] > CIGAR_SCAN_CAP)
    for i in over.tolist():
        span[i] = cigar_span(buf, int(starts[i]), int(cols["l_read_name"][i]),
                             int(cols["n_cigar"][i]))
    n = table.shape[1]
    columns = {
        "valid": np.ones(n, dtype=bool), **cols,
        "name_offset": starts + 36, "ref_span": span,
        "span_exact": np.ones(n, dtype=bool),
    }
    if len(over):
        kept = rows.passes(columns)
        if not kept.all():
            columns = {k: v[kept] for k, v in columns.items()}
            starts = starts[kept]
    return columns, starts, len(over)


_SEQ_CODES = "=ACMGRSVTWYHKDBN"


@dataclass
class ReadBatch:
    """Columnar batch of parsed records (host-side numpy views).

    Fixed fields live in ``columns``; variable-length payloads (name, seq,
    qual) materialize lazily from the flat buffer on demand.
    """

    columns: dict[str, np.ndarray]
    starts: np.ndarray
    buf: np.ndarray | None = None

    def __len__(self) -> int:
        return int(self.columns["valid"].sum())

    def __getitem__(self, key: str) -> np.ndarray:
        return self.columns[key][self.columns["valid"]]

    # ---- lazy variable-length payloads (row index is pre-filter) ----
    def name(self, i: int) -> str:
        off = int(self.columns["name_offset"][i])
        ln = int(self.columns["l_read_name"][i])
        return bytes(self.buf[off: off + ln - 1]).decode("latin-1")

    def seq(self, i: int) -> str:
        off = (
            int(self.columns["name_offset"][i])
            + int(self.columns["l_read_name"][i])
            + 4 * int(self.columns["n_cigar"][i])
        )
        n = int(self.columns["l_seq"][i])
        packed = self.buf[off: off + (n + 1) // 2]
        return "".join(
            _SEQ_CODES[(packed[k >> 1] >> (4 if k % 2 == 0 else 0)) & 0xF]
            for k in range(n)
        )

    def qual(self, i: int) -> bytes:
        n = int(self.columns["l_seq"][i])
        off = (
            int(self.columns["name_offset"][i])
            + int(self.columns["l_read_name"][i])
            + 4 * int(self.columns["n_cigar"][i])
            + (n + 1) // 2
        )
        return bytes(self.buf[off: off + n])


def parse_flat_records(
    buf: np.ndarray, starts: np.ndarray, pad: int = 300_000
) -> ReadBatch:
    """Host entry: pad the buffer, run the device parser, fix up any rows
    whose cigar exceeded the device scan cap.

    Both the buffer and the starts row count pad to powers of two so the
    jit sees at most log2 distinct shapes — without this, every streaming
    window's slightly-different size would trigger a fresh XLA compile
    (the same discipline as the checker's pow2 kernel windows). The
    bucket is ``pow2(len) + pad`` rather than ``pow2(len + pad)``: the
    same O(log) compile bound without nearly doubling the allocation and
    H2D transfer for pow2-sized windows."""
    padded = np.zeros(_next_pow2(len(buf)) + pad, dtype=np.uint8)
    padded[: len(buf)] = buf
    m = len(starts)
    starts_padded = np.full(_next_pow2(m), -1, dtype=np.int32)
    starts_padded[:m] = starts.astype(np.int32)
    cols = parse_records(jnp.asarray(padded), jnp.asarray(starts_padded))
    cols = {k: np.asarray(v)[:m] for k, v in cols.items()}
    inexact = np.flatnonzero(cols["valid"] & ~cols["span_exact"])
    if len(inexact):
        from spark_bam_tpu.bam.record import BamRecord

        for i in inexact:
            rec, _ = BamRecord.decode(buf, int(starts[i]))
            cols["ref_span"][i] = rec.reference_span()
        cols["span_exact"][inexact] = True
    return ReadBatch(cols, starts, buf=np.asarray(buf))
