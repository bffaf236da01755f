"""Long-read (PacBio-class) corpus through the scale paths.

The regime where hadoop-bam demonstrably broke — records spanning dozens
of BGZF blocks, some larger than any window halo (reference
docs/benchmarks.md:24-38 GiaB PacBio incorrect-split/false-negative rates;
seqdoop/.../Checker.scala:40-43 maxBytesToRead truncation) — must flow
through this repo's escape/deferral machinery and still resolve exactly:

- every ultra record (~4.5 MB encoded) exceeds the test halo, so the
  sharded mesh pass *must* report escapes and fall back, and the
  single-device streaming pass *must* defer and re-emit — nonzero escapes
  that all resolve, zero miscalls (VERDICT r4 item 3's acceptance);
- the `.records` ground truth (an independent length-prefix walk) pins the
  confusion matrix at every position;
- split resolution (find-block-start → find-record-start) lands identical
  positions through the native scan and the Python oracle, with the native
  path winning by orders of magnitude exactly here (boundaries are ~100 KB
  apart, so the Python checker's per-position scan runs long).
"""

import time

import numpy as np
import pytest

from spark_bam_tpu.bam.header import read_header
from spark_bam_tpu.bam.index_records import index_records
from spark_bam_tpu.benchmarks.synth import ensure_longread_bam
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.load.splits import file_splits
from spark_bam_tpu.tpu.stream_check import StreamChecker

# Window/halo chosen so the ~4.5 MB ultra records cannot fit any halo:
# escapes are guaranteed, which is the point.
WINDOW = 8 << 20
HALO = 1 << 20


@pytest.fixture(scope="module")
def corpus():
    path, manifest = ensure_longread_bam(32 << 20)
    records_path = str(path) + ".records"
    index_records(path, records_path)
    return str(path), manifest, records_path


def test_streaming_count_defers_and_resolves(corpus):
    path, manifest, _ = corpus
    checker = StreamChecker(
        path, Config(), window_uncompressed=WINDOW, halo=HALO
    )
    # The fused count path must detect the escapes and re-run exactly.
    assert checker.count_reads() == manifest["reads"]


def test_spans_deferral_coverage(corpus):
    """The spans contract under ultra reads: deferred re-emissions (spans
    landing behind the tiling frontier) exist (the escape path engaged),
    and the union of True positions is exactly the record-start set."""
    path, manifest, _ = corpus
    checker = StreamChecker(
        path, Config(), window_uncompressed=WINDOW, halo=HALO
    )
    he = checker.header_end_abs
    starts = []
    re_emissions = 0
    frontier = 0  # window spans tile forward; re-emissions land behind it
    for base, verdict in checker.spans():
        if base < frontier:
            re_emissions += 1
        else:
            frontier = base + len(verdict)
        idx = base + np.flatnonzero(verdict)
        starts.extend(idx[idx >= he].tolist())
    assert re_emissions > 0, "ultra records must force deferrals"
    assert len(starts) == len(set(starts)) == manifest["reads"]


def test_sharded_count_escapes_then_exact(corpus):
    """Ultra chains escape the device pass; the escaped steps re-derive
    exactly on host (escape-localized patch) while every clean step's
    device total stands — no whole-file fallback."""
    path, manifest, _ = corpus
    from spark_bam_tpu.parallel.stream_mesh import count_reads_sharded

    stats = {}
    n = count_reads_sharded(
        path, Config(), window_uncompressed=WINDOW, halo=HALO,
        stats_out=stats,
    )
    assert n == manifest["reads"]
    assert stats["escapes"] > 0, stats
    assert stats["patched_steps"] > 0 and not stats["fallback"], stats


def test_sharded_check_bam_zero_miscalls(corpus):
    path, manifest, records_path = corpus
    from spark_bam_tpu.parallel.stream_mesh import check_bam_sharded

    stats = check_bam_sharded(
        path, Config(), records_path=records_path,
        window_uncompressed=WINDOW, halo=HALO,
    )
    assert stats["false_positives"] == 0
    assert stats["false_negatives"] == 0
    assert stats["true_positives"] == manifest["reads"]
    assert stats["positions"] == manifest["uncompressed_bytes"]


def test_split_resolution_native_equals_python_and_wins(corpus):
    path, manifest, _ = corpus
    from spark_bam_tpu.load.api import _resolve_split_start

    header = read_header(path)
    splits = file_splits(path, 8 << 20)
    t0 = time.perf_counter()
    native = [
        _resolve_split_start(path, s, header, Config()) for s in splits
    ]
    t_native = time.perf_counter() - t0
    t0 = time.perf_counter()
    python = [
        _resolve_split_start(path, s, header, Config(backend="python"))
        for s in splits
    ]
    t_python = time.perf_counter() - t0
    assert native == python
    # Long-read data is where the native scan matters: boundaries are far
    # apart, so the Python oracle walks tens of thousands of positions per
    # split. Assert a conservative floor.
    assert t_python > 3 * t_native, (t_python, t_native)


def test_truncated_corpus_differential(corpus, tmp_path):
    """A block-aligned truncation (mid-record): the streaming deferral path
    must agree exactly with the in-memory native oracle over the whole
    truncated file — the hadoop-bam failure shape, resolved differentially.
    (Both lose the trailing starts whose ``reads_to_check`` chains the cut
    severed — that is the *correct* eager semantics, the same ``fn`` shape
    the noise-window dryrun pins — so the two engines must lose the SAME
    ones.)"""
    path, manifest, _ = corpus
    import pytest as _pytest

    from spark_bam_tpu.bam.iterators import PosStream
    from spark_bam_tpu.bgzf.flat import flatten_file
    from spark_bam_tpu.bgzf.index_blocks import blocks_metadata
    from spark_bam_tpu.core.channel import open_channel
    from spark_bam_tpu.native.build import eager_check_native

    metas = list(blocks_metadata(path))
    cut_block = metas[int(len(metas) * 0.7)]
    cut = cut_block.start  # block boundary, almost surely mid-record
    trunc = tmp_path / "trunc.bam"
    with open(path, "rb") as f:
        trunc.write_bytes(f.read(cut))

    walked = 0
    s = PosStream.open(open_channel(trunc))
    try:
        for _ in s:
            walked += 1
    except EOFError:
        pass  # cut through a length prefix — tolerated, like IndexRecords
    finally:
        s.close()

    checker = StreamChecker(
        str(trunc), Config(), window_uncompressed=WINDOW, halo=HALO
    )
    counted = checker.count_reads()

    flat = flatten_file(trunc)
    header = read_header(str(trunc))
    lens = np.array(header.contig_lengths.lengths_list(), dtype=np.int32)
    out = eager_check_native(
        flat.data, np.arange(flat.size, dtype=np.int64), lens
    )
    if out is None:
        _pytest.skip("native library unavailable")
    native_count = int(out[header.uncompressed_size:].sum())

    assert counted == native_count, (counted, native_count)
    # The cut severs the trailing starts' chains: strictly fewer starts
    # pass than records the tolerant walk stepped over, and far fewer than
    # the full corpus.
    assert 0 < counted <= walked < manifest["reads"]


def test_compare_splits_reproduces_hadoop_bam_longread_failure(tmp_path):
    """The founding-problem demonstration on our own corpus (reference
    docs/benchmarks.md:24-38: hadoop-bam's guesser fails on GiaB PacBio
    long reads): on a long-read BAM, every split start our engine
    produces is a true record start, while the seqdoop emulation —
    bounded to its upstream 256 KB guess window — loses split points
    inside ultra records (the incorrect-split/false-negative class).
    Also pins the native CLI splits path == the vectorized whole-file
    path."""
    from spark_bam_tpu.benchmarks.synth import synth_longread_bam
    from spark_bam_tpu.bgzf.flat import flatten_file
    from spark_bam_tpu.check.vectorized import check_flat
    from spark_bam_tpu.cli.app import CheckerContext
    from spark_bam_tpu.cli.splits_util import spark_bam_splits
    from spark_bam_tpu.load.hadoop import hadoop_bam_splits

    p = tmp_path / "lr.bam"
    synth_longread_bam(p, target_bytes=8 << 20, seed=3, ultra_seq_len=600_000)
    flat = flatten_file(p)
    hdr = read_header(p)
    lens = np.array(hdr.contig_lengths.lengths_list(), dtype=np.int32)
    truth = set(
        np.flatnonzero(check_flat(flat.data, lens, at_eof=True).verdict)
        .tolist()
    )

    def start_flat(s):
        return int(flat.flat_of_pos(s.start.block_pos, s.start.offset))

    cfg = Config()
    ours = spark_bam_splits(CheckerContext(p, cfg), 512 << 10)
    assert all(start_flat(s) in truth for s in ours)

    theirs = hadoop_bam_splits(p, 512 << 10, config=cfg)
    missed = {start_flat(s) for s in ours} - {start_flat(s) for s in theirs}
    assert missed, "emulated guesser must lose split points on ultra reads"

    # Native per-boundary path == vectorized whole-file path (vacuous
    # without the native library — both sides would take the fallback).
    from spark_bam_tpu.native.build import load_native

    if load_native() is None:
        pytest.skip("native library unavailable")
    ours_py = spark_bam_splits(
        CheckerContext(p, Config(backend="python")), 512 << 10
    )
    assert ours == ours_py


def test_exact_row_positions_match_truth(corpus):
    """The escape-localized patch primitive: every row's exact positions
    (native tri-state over a grown buffer) must equal the whole-file
    engine's record starts restricted to that row's owned span."""
    import jax

    from spark_bam_tpu.bgzf.flat import flatten_file
    from spark_bam_tpu.check.vectorized import check_flat
    from spark_bam_tpu.parallel.mesh import make_mesh
    from spark_bam_tpu.parallel.stream_mesh import (
        _exact_row_true_positions,
        _ShardedStream,
    )

    from spark_bam_tpu.core.channel import open_channel
    from spark_bam_tpu.native.build import load_native

    if load_native() is None:
        pytest.skip("native library unavailable")
    path, manifest, _ = corpus
    st = _ShardedStream(
        path, Config(), make_mesh(jax.devices("cpu")[:8]), WINDOW, HALO,
        None,
    )
    flat = flatten_file(path)
    header = read_header(path)
    lens = np.array(header.contig_lengths.lengths_list(), dtype=np.int32)
    truth = np.flatnonzero(check_flat(flat.data, lens, at_eof=True).verdict)

    seen = 0
    with open_channel(path) as ch:
        for g in range(len(st.groups)):
            lo = max(int(st.flat_starts[g]), st.header_end)
            hi = int(st.flat_starts[g]) + int(st.sizes[g])
            want = truth[(truth >= lo) & (truth < hi)]
            got = _exact_row_true_positions(st, g, st.header_end, ch)
            assert got is not None
            np.testing.assert_array_equal(got, want)
            seen += len(got)
    assert seen == manifest["reads"]
