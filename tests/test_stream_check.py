"""Streaming checker: tiled spans must reassemble the whole-file result."""

import numpy as np
import pytest

from spark_bam_tpu.bam.header import contig_lengths
from spark_bam_tpu.bam.index_records import read_records_index
from spark_bam_tpu.bgzf.flat import flatten_file
from spark_bam_tpu.check.vectorized import check_flat
from spark_bam_tpu.tpu.stream_check import count_reads_streaming, stream_verdicts


def reassemble(path, **kw) -> np.ndarray:
    flat = flatten_file(path)
    out = np.zeros(flat.size, dtype=bool)
    seen = np.zeros(flat.size, dtype=bool)
    for base, verdict in stream_verdicts(path, **kw):
        out[base: base + len(verdict)] |= verdict
        if len(verdict) > 1:
            assert not seen[base: base + len(verdict)].any(), "span overlap"
            seen[base: base + len(verdict)] = True
    assert seen.all(), "spans + pendings must tile the file"
    return out


def test_stream_matches_whole_file(bam2):
    # Small pipeline windows force many stitched buffers (numpy engine for
    # speed; the device path shares check_buffer and is covered elsewhere).
    got = reassemble(
        bam2, window_uncompressed=256 << 10, halo=64 << 10, use_device=False
    )
    flat = flatten_file(bam2)
    lens = np.array(contig_lengths(bam2).lengths_list(), dtype=np.int32)
    want = check_flat(flat.data, lens, at_eof=True).verdict
    np.testing.assert_array_equal(got, want)


def test_stream_longreads_with_pendings(tmp_path):
    """Chains (~10 × ~100 KB records) far exceed the 64 KB halo: pendings
    must carry across windows and still resolve exactly."""
    from tests.test_longreads import longread_bam  # fixture factory reuse

    # Build the same long-read file inline.
    import numpy as np

    from spark_bam_tpu.bam.header import BamHeader, ContigLengths
    from spark_bam_tpu.bam.record import BamRecord
    from spark_bam_tpu.bam.writer import write_bam
    from spark_bam_tpu.bam.index_records import index_records
    from spark_bam_tpu.core.pos import Pos

    rng = np.random.default_rng(9)
    path = tmp_path / "long.bam"
    header = BamHeader(
        ContigLengths({0: ("chr1", 200_000_000)}), Pos(0, 0), 0,
        "@HD\tVN:1.6\n@SQ\tSN:chr1\tLN:200000000\n",
    )

    def records():
        pos = 1000
        for i in range(30):
            n = int(rng.integers(60_000, 110_000))
            yield BamRecord(
                ref_id=0, pos=pos, mapq=60, bin=0, flag=0,
                next_ref_id=-1, next_pos=-1, tlen=0,
                read_name=f"lr/{i}", cigar=[(n, 0)],
                seq="A" * n, qual=bytes([30]) * n,
            )
            pos += n + 5

    write_bam(path, header, records())
    index_records(path)

    got = reassemble(
        path, window_uncompressed=256 << 10, halo=64 << 10, use_device=False
    )
    flat = flatten_file(path)
    want = check_flat(
        flat.data, np.array([200_000_000], dtype=np.int32), at_eof=True
    ).verdict
    np.testing.assert_array_equal(got, want)


def test_count_reads_streaming(bam1):
    assert (
        count_reads_streaming(
            bam1, window_uncompressed=256 << 10, halo=64 << 10, use_device=False
        )
        == 4917
    )


def test_count_reads_device_escapes_resolve_without_a_fallback(tmp_path):
    """Device count path with a halo far smaller than the chain span: the
    on-device escape counter trips in every window, the listed candidates
    resolve on the host from the following windows' bytes, and the true
    count comes back WITHOUT the whole-file spans fallback (ultra-long-read
    robustness at the cost of the escaped candidates, not of the file)."""
    import numpy as np

    from spark_bam_tpu.bam.header import BamHeader, ContigLengths
    from spark_bam_tpu.bam.record import BamRecord
    from spark_bam_tpu.bam.writer import write_bam
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.core.pos import Pos
    from spark_bam_tpu.tpu.stream_check import StreamChecker

    rng = np.random.default_rng(11)
    path = tmp_path / "long.bam"
    header = BamHeader(
        ContigLengths({0: ("chr1", 200_000_000)}), Pos(0, 0), 0,
        "@HD\tVN:1.6\n@SQ\tSN:chr1\tLN:200000000\n",
    )

    def records():
        pos = 1000
        for i in range(30):
            n = int(rng.integers(60_000, 110_000))
            yield BamRecord(
                ref_id=0, pos=pos, mapq=60, bin=0, flag=0,
                next_ref_id=-1, next_pos=-1, tlen=0,
                read_name=f"lr/{i}", cigar=[(n, 0)],
                seq="A" * n, qual=bytes([30]) * n,
            )
            pos += n + 5

    write_bam(path, header, records())

    checker = StreamChecker(
        path, Config(), window_uncompressed=256 << 10, halo=64 << 10
    )
    # Escapes must actually happen (guard against a future config change
    # silently un-exercising this path), and the fallback must not run.
    from tests.test_host_fed_count import _observed

    calls = []
    orig = StreamChecker._count_via_spans

    def spy(self):
        calls.append(1)
        return orig(self)

    StreamChecker._count_via_spans = spy
    try:
        got, counters, _spans = _observed(checker.count_reads)
    finally:
        StreamChecker._count_via_spans = orig
    assert got == 30
    assert not calls, "the whole-file fallback ran"
    assert (counters["check.escape_candidates"]
            == counters["check.escape_resolved"] >= 20)  # of 30 records
    assert not counters.get("check.count_escape_retries")


def test_count_reads_flush_chunks(bam1):
    """The chunked device-accumulator flush (int32-overflow guard) must
    partition the stream without losing or double-counting windows."""
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.tpu.stream_check import StreamChecker

    checker = StreamChecker(
        bam1, Config(), window_uncompressed=128 << 10, halo=32 << 10
    )
    checker.flush_every = 2  # force many flush boundaries (incl. mid-chunk EOF)
    assert checker.count_reads() == 4917


def test_full_spans_match_whole_file(bam1):
    """Streaming full-check spans must reassemble the whole-file fail_mask
    and reads_before exactly (flags for every position, O(window) memory)."""
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.tpu.stream_check import StreamChecker

    flat = flatten_file(bam1)
    lens = np.array(contig_lengths(bam1).lengths_list(), dtype=np.int32)

    got_fm = np.full(flat.size, -1, dtype=np.int32)
    got_rb = np.full(flat.size, -1, dtype=np.int32)
    checker = StreamChecker(
        bam1, window_uncompressed=256 << 10, halo=64 << 10
    )
    for base, fm, rb in checker.full_spans():
        got_fm[base: base + len(fm)] = fm
        got_rb[base: base + len(rb)] = rb
    assert (got_fm >= 0).all(), "spans must tile the file"

    want = check_flat(flat.data, lens, at_eof=True)
    np.testing.assert_array_equal(got_fm, want.fail_mask)
    np.testing.assert_array_equal(got_rb, want.reads_before)


def test_full_check_summary_streaming_matches_in_memory(bam1):
    """The streaming full-check aggregations must equal the in-memory
    computation the CLI performs (per-flag totals, critical/two-check
    buckets — reference FullCheck.scala:112-417 semantics)."""
    from spark_bam_tpu.check.flags import BIT, FLAG_NAMES
    from spark_bam_tpu.tpu.stream_check import full_check_summary_streaming

    got = full_check_summary_streaming(
        bam1, window_uncompressed=256 << 10, halo=64 << 10
    )

    flat = flatten_file(bam1)
    lens = np.array(contig_lengths(bam1).lengths_list(), dtype=np.int32)
    res = check_flat(flat.data, lens, at_eof=True)
    bit0 = BIT["tooFewFixedBlockBytes"]
    considered = (res.fail_mask != 0) & ~(
        (res.fail_mask == bit0) & (res.reads_before == 0)
    )
    masked = res.fail_mask[considered]
    for i, name in enumerate(FLAG_NAMES):
        assert got["per_flag"][name] == int(((masked >> i) & 1).sum()), name
    assert got["considered"] == int(considered.sum())

    popcount = np.zeros(flat.size, dtype=np.int32)
    for i in range(len(FLAG_NAMES)):
        popcount += (res.fail_mask >> i) & 1
    nf = popcount + (res.reads_before > 0)
    np.testing.assert_array_equal(
        np.sort(got["critical_positions"]),
        np.flatnonzero(considered & (nf == 1)),
    )
    np.testing.assert_array_equal(
        np.sort(got["two_check_positions"]),
        np.flatnonzero(considered & (nf == 2)),
    )
    assert got["positions"] == flat.size


def test_full_spans_longread_deferrals_exact(tmp_path):
    """full_spans with chains far exceeding the halo: deferred lanes must
    re-emit with COMPLETE masks — a deferral that re-checks the same
    truncated bytes would yield buffer-edge flags instead of the truth."""
    rng = np.random.default_rng(13)
    path = tmp_path / "long.bam"

    from spark_bam_tpu.bam.header import BamHeader, ContigLengths
    from spark_bam_tpu.bam.record import BamRecord
    from spark_bam_tpu.bam.writer import write_bam
    from spark_bam_tpu.core.pos import Pos
    from spark_bam_tpu.tpu.stream_check import StreamChecker

    header = BamHeader(
        ContigLengths({0: ("chr1", 200_000_000)}), Pos(0, 0), 0,
        "@HD\tVN:1.6\n@SQ\tSN:chr1\tLN:200000000\n",
    )

    def records():
        pos = 1000
        for i in range(30):
            n = int(rng.integers(60_000, 110_000))
            yield BamRecord(
                ref_id=0, pos=pos, mapq=60, bin=0, flag=0,
                next_ref_id=-1, next_pos=-1, tlen=0,
                read_name=f"lr/{i}", cigar=[(n, 0)],
                seq="A" * n, qual=bytes([30]) * n,
            )
            pos += n + 5

    write_bam(path, header, records())
    flat = flatten_file(path)
    lens = np.array([200_000_000], dtype=np.int32)

    got_fm = np.full(flat.size, -1, dtype=np.int64)
    got_rb = np.full(flat.size, -1, dtype=np.int64)
    deferrals = 0
    frontier = 0  # window spans tile forward; re-emissions land behind it
    checker = StreamChecker(
        path, window_uncompressed=256 << 10, halo=64 << 10
    )
    for base, fm, rb in checker.full_spans():
        if base < frontier:
            deferrals += 1
        else:
            frontier = base + len(fm)
        got_fm[base: base + len(fm)] = fm
        got_rb[base: base + len(rb)] = rb

    assert deferrals > 0, "scenario must force deferred full-check lanes"
    want = check_flat(flat.data, lens, at_eof=True)
    np.testing.assert_array_equal(got_fm, want.fail_mask)
    np.testing.assert_array_equal(got_rb, want.reads_before)
