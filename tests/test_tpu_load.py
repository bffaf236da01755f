"""TPU end-to-end load path vs golden counts and the sequential loader."""

import numpy as np
import pytest

from spark_bam_tpu.bam.index_records import read_records_index
from spark_bam_tpu.load.tpu_load import (
    count_reads_tpu,
    load_reads_columnar,
    record_starts,
)


@pytest.fixture(scope="module")
def bam2(bam2_like):
    return bam2_like.path


def test_count_reads_tpu(bam2_like, request):
    """The reference's two files at their golden counts; a generated one at
    what its sidecar holds."""
    if bam2_like.records is not None:
        assert count_reads_tpu(request.getfixturevalue("bam1")) == 4917
    golden = bam2_like.records or len(
        read_records_index(str(bam2_like.path) + ".records"))
    assert count_reads_tpu(bam2_like.path) == golden > 500


def test_record_starts_match_index(bam2):
    result = record_starts(bam2)
    golden = read_records_index(str(bam2) + ".records")
    assert result.positions() == golden


def test_load_reads_columnar_interval(bam2_like):
    from spark_bam_tpu.load import plain

    loci = f"{bam2_like.contig}:0-100000"
    batch = load_reads_columnar(bam2_like.path, loci=loci)
    # The golden interval count; of a generated file, the plain reference's.
    golden = bam2_like.on_contig or len(
        plain.load_rows(bam2_like.path, loci=loci)["records"])
    assert len(batch) == golden > 0
    assert (batch["flag"] & 4).sum() == 0  # no unmapped rows survive


def test_load_reads_columnar_flags(bam2):
    batch = load_reads_columnar(bam2, flags_required=0x1)
    assert (batch["flag"] & 1).all()


def test_stream_read_batches_match_whole_file(bam2):
    """Per-window columnar batches must reassemble the whole-file columnar
    load exactly (fixed fields, in order)."""
    import numpy as np

    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.load.tpu_load import load_reads_columnar, stream_read_batches

    whole = load_reads_columnar(bam2)
    cfg = Config(window_size=256 << 10, halo_size=64 << 10)
    got = {k: [] for k in ("ref_id", "pos", "flag", "l_seq")}
    n_rows = 0
    for base, batch in stream_read_batches(bam2, cfg):
        assert base >= 0  # no spills on short-read data
        for k in got:
            got[k].append(batch[k])
        n_rows += len(batch)
    assert n_rows == len(whole) > 500
    for k in got:
        np.testing.assert_array_equal(np.concatenate(got[k]), whole[k])


def test_stream_read_batches_longread_spills(tmp_path):
    """Records longer than the window lookahead must spill to the exact
    seekable-decode batch, never parse truncated bytes."""
    import numpy as np

    from spark_bam_tpu.bam.header import BamHeader, ContigLengths
    from spark_bam_tpu.bam.record import BamRecord
    from spark_bam_tpu.bam.writer import write_bam
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.core.pos import Pos
    from spark_bam_tpu.load.tpu_load import stream_read_batches

    rng = np.random.default_rng(21)
    path = tmp_path / "long.bam"
    header = BamHeader(
        ContigLengths({0: ("chr1", 200_000_000)}), Pos(0, 0), 0,
        "@HD\tVN:1.6\n@SQ\tSN:chr1\tLN:200000000\n",
    )
    want_pos = []

    def records():
        p = 1000
        for i in range(20):
            n = int(rng.integers(60_000, 110_000))
            want_pos.append(p)
            yield BamRecord(
                ref_id=0, pos=p, mapq=60, bin=0, flag=0,
                next_ref_id=-1, next_pos=-1, tlen=0,
                read_name=f"lr/{i}", cigar=[(n, 0)],
                seq="A" * n, qual=bytes([30]) * n,
            )
            p += n + 5

    write_bam(path, header, records())

    cfg = Config(window_size=256 << 10, halo_size=64 << 10)
    all_pos = []
    spilled = 0
    for base, batch in stream_read_batches(path, cfg):
        if base == -1:
            spilled = len(batch)
        all_pos.extend(batch["pos"].tolist())
    assert spilled > 0, "scenario must force spills (records > halo)"
    assert sorted(all_pos) == want_pos


def test_stream_read_batches_interval_flag_filter(bam2_like):
    """Per-window on-device interval filtering must agree with the
    whole-file columnar load for the same loci."""
    import numpy as np

    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.load.tpu_load import load_reads_columnar, stream_read_batches

    bam2 = bam2_like.path
    loci = f"{bam2_like.contig}:13000-17000"
    whole = load_reads_columnar(bam2, loci=loci)
    cfg = Config(window_size=256 << 10, halo_size=64 << 10)
    got_pos = []
    for base, batch in stream_read_batches(bam2, cfg, loci=loci):
        got_pos.extend(batch["pos"].tolist())
    assert len(got_pos) == len(whole) > 0
    np.testing.assert_array_equal(np.sort(got_pos), np.sort(whole["pos"]))


def test_flag_only_filter_keeps_unmapped(tmp_path):
    """Flag-only filtering is a pure flag predicate: unmapped reads must
    pass unless a flag bit excludes them (no hidden interval semantics)."""
    import numpy as np

    from spark_bam_tpu.bam.header import BamHeader, ContigLengths
    from spark_bam_tpu.bam.record import BamRecord
    from spark_bam_tpu.bam.writer import write_bam
    from spark_bam_tpu.core.pos import Pos
    from spark_bam_tpu.load.tpu_load import load_reads_columnar

    path = tmp_path / "mix.bam"
    header = BamHeader(
        ContigLengths({0: ("chr1", 1_000_000)}), Pos(0, 0), 0,
        "@HD\tVN:1.6\n@SQ\tSN:chr1\tLN:1000000\n",
    )

    def records():
        for i in range(20):
            mapped = i % 2 == 0
            dup = i % 4 == 1  # only unmapped reads get the dup bit here
            flag = (0 if mapped else 4) | (0x400 if dup else 0)
            yield BamRecord(
                ref_id=0 if mapped else -1, pos=100 + i if mapped else -1,
                mapq=60 if mapped else 0, bin=0, flag=flag,
                next_ref_id=-1, next_pos=-1, tlen=0,
                read_name=f"m{i}", cigar=[(20, 0)] if mapped else [],
                seq="A" * 20, qual=bytes([30]) * 20,
            )

    write_bam(path, header, records())

    batch = load_reads_columnar(path, flags_forbidden=0x400)
    flags = batch["flag"]
    # 20 reads − 5 duplicates (i % 4 == 1) = 15 survivors, incl. unmapped.
    assert len(batch) == 15
    assert int(((flags & 4) != 0).sum()) == 5  # unmapped non-dups retained
