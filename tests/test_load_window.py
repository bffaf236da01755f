"""The streaming load (``stream_read_batches`` → ``jit_load_window``) held to
the plain reference (``load/plain.py``: gzip + struct, a record at a time)
row for row, on seeded generated files at a small size on the CPU: starts,
every fixed column, the reference span and the records' bytes; filtered and
unfiltered, across seams, over the CIGAR cap and past the halo. And what the
path must not do: put a window twice, or read back anything as wide as the
window's positions."""

import functools
import json
from pathlib import Path

import numpy as np
import pytest

from spark_bam_tpu.bam.header import BamHeader, ContigLengths
from spark_bam_tpu.bam.record import BamRecord
from spark_bam_tpu.bam.writer import write_bam
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.core.pos import Pos
from spark_bam_tpu.load import plain
from spark_bam_tpu.load.tpu_load import count_reads_tpu, stream_read_batches
from spark_bam_tpu.tpu.parser import CIGAR_SCAN_CAP, ROW_WORDS

from tests.bam_factories import random_bam
from tests.test_host_fed_count import _observed

ROOT = Path(__file__).resolve().parents[1]
COLUMNS = ("block_size", "ref_id", "pos", "l_read_name", "mapq", "bin",
           "n_cigar", "flag", "l_seq", "next_ref_id", "next_pos", "tlen",
           "ref_span")
#: Twelve windows of a 3 MiB file, and records across every seam.
SEAMS = Config(window_size=256 << 10, halo_size=32 << 10)


def genome_bam(path, seed: int = 2 ** 31 + 23) -> dict:
    """The benchmark's 25-contig file at its rehearsal size (a seed whose
    chr20 holds unmapped mates: three of 187 records)."""
    from bench.generators import shortread_genome

    config = json.loads(
        (ROOT / "bench" / "configs" / "wgs-short-load.json").read_text())
    return shortread_genome.generate(
        config["params"], seed, config["rehearsal"]["uncompressed_bytes"],
        path)


@pytest.fixture(scope="module")
def genome(tmp_path_factory):
    path = tmp_path_factory.mktemp("genome") / "genome.bam"
    return path, genome_bam(path)


@pytest.fixture(scope="module")
def fuzz(tmp_path_factory):
    path = tmp_path_factory.mktemp("fuzz") / "fuzz.bam"
    random_bam(path, 5, contigs=(("chr1", 5_000_000), ("chr2", 3_000_000)),
               dup_rate=0.1, n_records=(700, 800))
    return path


def handmade(path, records) -> None:
    header = BamHeader(
        ContigLengths({0: ("chr1", 200_000_000), 1: ("chr2", 100_000_000)}),
        Pos(0, 0), 0,
        "@HD\tVN:1.6\n@SQ\tSN:chr1\tLN:200000000\n@SQ\tSN:chr2\tLN:100000000\n")
    write_bam(path, header, records)


def rows_of(path, config, **kw) -> dict:
    """What ``stream_read_batches`` hands back, as ``plain.load_rows`` names
    it; ``spilled`` counts the rows of the batches without flat offsets
    (``starts`` is -1 there)."""
    out: dict = {k: [] for k in ("starts", *COLUMNS)}
    records, spilled = [], 0
    for base, batch in stream_read_batches(path, config, **kw):
        valid = batch.columns["valid"]
        starts = np.asarray(batch.starts)[valid]
        assert batch.columns["span_exact"][valid].all()
        if base < 0:
            spilled += len(starts)
        out["starts"].append(
            base + starts if base >= 0 else np.full(len(starts), -1))
        for k in COLUMNS:
            out[k].append(batch[k])
        records += [bytes(batch.buf[s: s + 4 + n]) for s, n in
                    zip(starts.tolist(), batch["block_size"].tolist())]
    rows = {k: np.concatenate(v).astype(np.int64) if v
            else np.empty(0, np.int64) for k, v in out.items()}
    return {**rows, "records": records, "spilled": spilled}


def assert_rows_equal(got: dict, want: dict) -> None:
    assert got["records"] == want["records"]
    for k in ("starts", *COLUMNS):
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


CASES = {
    "unfiltered": ("fuzz", SEAMS, {}),
    "two_intervals_flags_required": (
        "fuzz", SEAMS, {"loci": "chr1:1000-200000,chr2:5-150000",
                        "flags_required": 0x400}),
    "flags_only": ("fuzz", SEAMS, {"flags_forbidden": 0x404}),
    "genome_unfiltered_ten_seams": ("genome", SEAMS, {}),
    "genome_chr20_1796": ("genome", Config(),
                          {"loci": "chr20", "flags_forbidden": 1796}),
    "genome_chr20_1796_ten_seams": (
        "genome", SEAMS, {"loci": "chr20", "flags_forbidden": 1796}),
}


@pytest.mark.parametrize("case", CASES)
def test_the_load_equals_the_plain_reference(case, request):
    name, config, kw = CASES[case]
    path = request.getfixturevalue(name)
    path = path[0] if isinstance(path, tuple) else path
    want = plain.load_rows(path, **kw)
    got = rows_of(path, config, **kw)
    assert len(want["records"]) > 20 and got["spilled"] == 0
    assert_rows_equal(got, want)


def test_the_generated_genome_has_what_the_filter_drops(genome):
    """The chr20 case above is not vacuous: the file holds duplicates and
    unmapped mates ON chr20, and records on the contigs around it."""
    path, index = genome
    every = plain.load_rows(path)
    on20 = every["ref_id"] == 19
    assert 100 < on20.sum() < len(on20) / 10
    assert ((every["flag"][on20] & 0x400) != 0).sum() > 3
    assert ((every["flag"][on20] & 4) != 0).sum() >= 1
    assert {18, 20} <= set(every["ref_id"].tolist())
    kept = plain.load_rows(path, loci="chr20", flags_forbidden=1796)
    assert 0 < len(kept["records"]) < on20.sum()
    # The generator's own account of every record is the reference's.
    np.testing.assert_array_equal(index["record_starts"], every["starts"])
    np.testing.assert_array_equal(index["ref_span"], every["ref_span"])


@pytest.fixture(scope="module")
def long_cigars(tmp_path_factory):
    """Short reads, every seventh with a CIGAR of 70 operations (over the
    device scan's 64), whose span decides whether it reaches the interval."""
    path = tmp_path_factory.mktemp("cigar") / "cigar70.bam"

    def records():
        for i in range(300):
            ops = 70 if i % 7 == 0 else 1 + i % 3
            # 1M 1D 1M 1D ...: a span of ``ops`` bases for ``ops // 2 + 1``
            # read bases or so.
            cigar = [(1, 0 if k % 2 == 0 else 2) for k in range(ops)]
            n = sum(ln for ln, op in cigar if op == 0)
            yield BamRecord(
                ref_id=0, pos=1000 + 10 * i, mapq=60, bin=0, flag=0,
                next_ref_id=-1, next_pos=-1, tlen=0, read_name=f"c{i}",
                cigar=cigar, seq="A" * n, qual=bytes([30]) * n)

    handmade(path, records())
    return path


@pytest.mark.parametrize("loci", (None, "chr1:1040-1069,chr1:3000-3500"))
def test_a_cigar_over_the_scan_cap_is_finished_on_the_host(long_cigars, loci):
    """The row at pos 1000 has 70 operations and a span of 70: it reaches
    [1040, 1069) by its last thirty bases, which the device's 64 operations
    do not see. The rows after it with one or two operations end before
    1040. Counted, not demoted."""
    kw = {} if loci is None else {"loci": loci}
    want = plain.load_rows(long_cigars, **kw)
    got, counters, _ = _observed(
        lambda: rows_of(long_cigars, Config(), **kw))
    assert_rows_equal(got, want)
    over = int((want["n_cigar"] > CIGAR_SCAN_CAP).sum())
    assert over > 0 and counters["load.cigar_host_fixups"] >= over
    assert 1000 in want["pos"].tolist() or loci is None
    assert counters.get("check.fused_demotions", 0) == 0


@pytest.fixture(scope="module")
def whale(tmp_path_factory):
    """Twenty records of 60-110 KB among short ones, under a halo of 64 KiB:
    longer than the lookahead, so they escape and are decoded off the
    stream."""
    path = tmp_path_factory.mktemp("whale") / "long.bam"
    rng = np.random.default_rng(21)

    def records():
        p = 1000
        for i in range(60):
            n = int(rng.integers(60_000, 110_000)) if i % 3 == 0 else 100
            yield BamRecord(
                ref_id=i % 2, pos=p, mapq=60, bin=0,
                flag=0x400 if i % 5 == 0 else 0,
                next_ref_id=-1, next_pos=-1, tlen=0, read_name=f"lr/{i}",
                cigar=[(n, 0)], seq="A" * n, qual=bytes([30]) * n)
            p += n + 5

    handmade(path, records())
    return path


@pytest.mark.parametrize("kw", ({}, {"loci": "chr2", "flags_forbidden": 0x400}))
def test_a_record_longer_than_the_halo_is_decoded_from_the_stream(whale, kw):
    config = Config(window_size=256 << 10, halo_size=64 << 10)
    want = plain.load_rows(whale, **kw)
    got, counters, _ = _observed(lambda: rows_of(whale, config, **kw))
    assert got["spilled"] > 0 == counters.get("check.fused_demotions", 0)
    assert counters["load.spilled_records"] >= got["spilled"]
    assert counters["check.escape_candidates"] == (
        counters["check.escape_resolved"])
    # Spilled rows come last and without flat offsets: same rows, by bytes.
    order = np.argsort([r[36:48] for r in got["records"]], kind="stable")
    worder = np.argsort([r[36:48] for r in want["records"]], kind="stable")
    assert [got["records"][i] for i in order] == [
        want["records"][i] for i in worder]
    for k in COLUMNS:
        np.testing.assert_array_equal(got[k][order], want[k][worder], k)
    placed = got["starts"] >= 0
    assert set(got["starts"][placed]) <= set(want["starts"])


def test_a_window_whose_escapes_outnumber_the_list_is_done_on_the_host(
        whale, monkeypatch):
    """With two slots where the stream asks for 64, a window ahead of a long
    record cannot list its escapes: that window alone is checked and parsed
    on the host, and says so; the rows are the same."""
    from spark_bam_tpu.tpu import checker

    monkeypatch.setattr(checker, "make_load_window", functools.partial(
        checker.make_load_window, escapes=2))
    config = Config(window_size=256 << 10, halo_size=64 << 10)
    want = plain.load_rows(whale)
    got, counters, _ = _observed(lambda: rows_of(whale, config))
    assert counters["check.fused_demotions"] > 0
    assert sorted(got["records"]) == sorted(want["records"])
    assert counters["load.records_parsed"] + counters[
        "load.spilled_records"] >= len(want["records"])


def test_the_numpy_engine_walks_the_same_windows(fuzz):
    """``use_device=False``: every window on the host, the same rows."""
    from spark_bam_tpu.load.tpu_load import _interval_table
    from spark_bam_tpu.tpu.parser import RowFilter
    from spark_bam_tpu.tpu.stream_check import StreamChecker

    checker = StreamChecker(fuzz, SEAMS, use_device=False)
    rows = RowFilter.of(_interval_table(checker.header, "chr2"), 0, 0x400)
    want = plain.load_rows(fuzz, loci="chr2", flags_forbidden=0x400)
    pos = np.concatenate(
        [batch["pos"] for _base, batch in checker.read_batches(rows)])
    np.testing.assert_array_equal(pos, want["pos"])


def test_one_put_a_window_and_nothing_position_wide_comes_back(genome):
    """A load pass puts what a count pass puts, byte for byte; what it reads
    back is 28 bytes a window and the table's head: 44 bytes a column, the
    rows rounded up to a power of two (256 at the least) in each window
    that has any. Every record is parsed, as the count counts them."""
    path, index = genome
    n, counted, _ = _observed(lambda: count_reads_tpu(path, SEAMS))
    assert n == len(index["record_starts"])
    for kw in ({}, {"loci": "chr20", "flags_forbidden": 1796}):
        got, counters, hists = _observed(lambda: rows_of(path, SEAMS, **kw))
        rows = len(got["records"])
        windows = counters["check.windows"]
        assert windows == counted["check.windows"] >= 10
        assert counters["inflate.h2d_bytes"] == counted["inflate.h2d_bytes"]
        assert hists["inflate.h2d"] == windows
        assert counters["load.records_parsed"] == n
        assert counters["load.rows_out"] == rows
        assert rows == (n if not kw else len(
            plain.load_rows(path, **kw)["records"]))
        assert counters["load.d2h_bytes"] <= (
            2 * 4 * ROW_WORDS * rows + windows * (28 + 4 * ROW_WORDS * 256))
        assert counters["funnel.survivors"] == counted["funnel.survivors"]
        assert counters["funnel.lanes"] == counted["funnel.lanes"]
        for name in ("check.fused_demotions", "load.cigar_host_fixups",
                     "load.spilled_records"):
            assert counters.get(name, 0) == 0, name
        assert counters["load.passes"] == 1 == hists["load.reads"]
        assert hists["load.head_ms"] == hists["load.drain_ms"] == 1
        assert hists["load.device_ms"] == windows


def test_a_contig_the_header_does_not_name_is_an_error(fuzz):
    from spark_bam_tpu.load.intervals import BadLociError

    with pytest.raises(BadLociError, match="'1'"):
        list(stream_read_batches(fuzz, SEAMS, loci="1:0-100"))


def test_the_spans_path_books_the_lanes_its_program_ran(fuzz):
    """``funnel.lanes`` of ``spans()`` is what ``check_window`` returns,
    blocks sized by each window's survivors, not the windows' capacity."""
    from spark_bam_tpu.tpu.checker import lane_block, lane_capacity
    from spark_bam_tpu.tpu.stream_check import StreamChecker

    checker = StreamChecker(
        fuzz, Config(), window_uncompressed=128 << 10, halo=32 << 10)
    _, counters, _ = _observed(lambda: list(checker.spans()))
    block = lane_block(checker.kernel_window)
    windows = counters["check.windows"]
    assert counters["funnel.lanes"] % block == 0
    assert counters["funnel.survivors"] <= counters["funnel.lanes"] < (
        counters["funnel.survivors"] + windows * block)
    assert counters["funnel.lanes"] < windows * lane_capacity(
        checker.kernel_window)
