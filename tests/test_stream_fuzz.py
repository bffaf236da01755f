"""Property fuzz: streaming projections == whole-file engine on random BAMs.

Randomized record sets (lengths, flags, mapped/unmapped mixes) packed at
randomized block payloads, checked through deliberately tiny windows/halos
so every streaming mechanism (halo carry, deferral, spill decode) gets
exercised; each projection must equal the single-pass whole-file engine
bit-for-bit.
"""

import numpy as np
import pytest

from spark_bam_tpu.bam.header import read_header
from spark_bam_tpu.bgzf.flat import flatten_file
from spark_bam_tpu.check.vectorized import check_flat
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.tpu.stream_check import StreamChecker

from tests.bam_factories import random_bam

CFG = dict(window_uncompressed=128 << 10, halo=32 << 10)


@pytest.mark.parametrize("seed", range(5))
def test_streaming_projections_match_whole_file(tmp_path, seed):
    path = tmp_path / f"fuzz{seed}.bam"
    random_bam(path, seed, contigs=(("chr1", 5_000_000), ("chr2", 3_000_000)), dup_rate=0.1)

    flat = flatten_file(path)
    hdr = read_header(path)
    lens = np.array(hdr.contig_lengths.lengths_list(), dtype=np.int32)
    want = check_flat(flat.data, lens, at_eof=True)
    he = hdr.uncompressed_size

    checker = StreamChecker(path, Config(), **CFG)

    # count_reads == whole-file verdict count past the header.
    assert checker.count_reads() == int(want.verdict[he:].sum())

    # spans reassemble the verdict array.
    got_v = np.zeros(flat.size, dtype=bool)
    for base, v in StreamChecker(path, Config(), **CFG).spans():
        got_v[base: base + len(v)] |= v
    np.testing.assert_array_equal(got_v, want.verdict)

    # full spans reassemble masks + reads_before.
    got_fm = np.full(flat.size, -1, dtype=np.int64)
    got_rb = np.full(flat.size, -1, dtype=np.int64)
    for base, fm, rb in StreamChecker(path, Config(), **CFG).full_spans():
        got_fm[base: base + len(fm)] = fm
        got_rb[base: base + len(rb)] = rb
    np.testing.assert_array_equal(got_fm, want.fail_mask)
    np.testing.assert_array_equal(got_rb, want.reads_before)

    # streamed batches cover exactly the true record starts.
    rows = 0
    for base, batch in StreamChecker(path, Config(), **CFG).read_batches():
        rows += len(batch)
    assert rows == int(want.verdict[he:].sum())


@pytest.mark.parametrize("seed", range(5))
def test_sharded_count_matches_whole_file(tmp_path, seed):
    """The mesh streaming count agrees with the whole-file oracle on the
    same adversarial random BAMs (tiny windows/halos force multi-batch
    assembly, seam carries, and — at halo=32K — occasional escapes)."""
    import jax

    from spark_bam_tpu.parallel.mesh import make_mesh
    from spark_bam_tpu.parallel.stream_mesh import count_reads_sharded

    path = tmp_path / f"fuzz{seed}.bam"
    random_bam(
        path, seed, contigs=(("chr1", 5_000_000), ("chr2", 3_000_000)),
        dup_rate=0.1,
    )
    flat = flatten_file(path)
    hdr = read_header(path)
    lens = np.array(hdr.contig_lengths.lengths_list(), dtype=np.int32)
    want = check_flat(flat.data, lens, at_eof=True)
    he = hdr.uncompressed_size

    mesh = make_mesh(jax.devices("cpu")[:8])
    got = count_reads_sharded(path, Config(), mesh=mesh, **CFG)
    assert got == int(want.verdict[he:].sum())


def test_sharded_check_bam_matches_whole_file(tmp_path):
    """check_bam_sharded's truth alignment (block→flat mapping via
    searchsorted against the sidecar) must reproduce the whole-file
    confusion exactly on a random BAM."""
    import jax

    from spark_bam_tpu.bam.index_records import index_records
    from spark_bam_tpu.parallel.mesh import make_mesh
    from spark_bam_tpu.parallel.stream_mesh import check_bam_sharded

    from spark_bam_tpu.core.pos import Pos

    path = tmp_path / "fuzz_cb.bam"
    random_bam(
        path, 7, contigs=(("chr1", 5_000_000), ("chr2", 3_000_000)),
        dup_rate=0.1,
    )
    index_records(path)

    flat = flatten_file(path)
    hdr = read_header(path)
    lens = np.array(hdr.contig_lengths.lengths_list(), dtype=np.int32)
    want = check_flat(flat.data, lens, at_eof=True)
    truth = np.zeros(flat.size, dtype=bool)
    he = hdr.uncompressed_size
    truth_idx = np.flatnonzero(want.verdict)
    truth[truth_idx[truth_idx >= he]] = True  # sidecar == real starts

    # Perturb: one bogus truth entry at a non-boundary position, so the
    # false-negative accounting is actually exercised (fn must come out 1,
    # not merely 0 == 0).
    bogus = int(truth_idx[len(truth_idx) // 2]) + 1
    assert not truth[bogus]
    truth[bogus] = True
    sidecar = tmp_path / "tampered.records"
    lines = [
        f"{b},{o}"
        for b, o in zip(*flat.pos_of_flat_many(np.flatnonzero(truth)))
    ]
    sidecar.write_text("\n".join(lines) + "\n")

    stats = check_bam_sharded(
        path, Config(), mesh=make_mesh(jax.devices("cpu")[:8]),
        records_path=sidecar, **CFG
    )
    tp = int((want.verdict & truth).sum())
    fp = int((want.verdict & ~truth).sum())
    fn = int((~want.verdict & truth).sum())
    assert fn == 1  # the perturbation is visible, not vacuous
    assert stats["true_positives"] == tp
    assert stats["false_positives"] == fp
    assert stats["false_negatives"] == fn
    assert stats["positions"] == flat.size
    assert stats["true_negatives"] == flat.size - tp - fp - fn


@pytest.mark.parametrize("seed", range(3))
def test_truncation_fuzz_device_vs_numpy_engines(tmp_path, seed):
    """Random cuts through a random BAM: the device and NumPy engines must
    agree byte-for-byte through the identical streaming control flow —
    same count when the cut reads cleanly, same error class when it
    doesn't (the pinned truncation semantics)."""
    rng = np.random.default_rng(1000 + seed)
    path = tmp_path / f"t{seed}.bam"
    random_bam(path, seed, contigs=(("chr1", 5_000_000),), dup_rate=0.05)
    data = path.read_bytes()

    for cut in sorted(rng.integers(100, len(data), 4).tolist()):
        trunc = tmp_path / f"t{seed}_{cut}.bam"
        trunc.write_bytes(data[:cut])

        def run(use_device):
            try:
                return StreamChecker(
                    trunc, Config(), use_device=use_device, **CFG
                ).count_reads()
            except (EOFError, IOError) as e:
                return type(e).__name__

        dev, host = run(True), run(False)
        assert dev == host, (cut, dev, host)


@pytest.mark.parametrize("seed", range(2))
def test_subrecord_window_projections_match_whole_file(tmp_path, seed):
    """Windows far smaller than one record: every owned position defers
    (the regime where ungated flags-path resolution was O(span^2) and
    re-emissions were per-position). The gated, run-batched deferral
    path must still reassemble every projection bit-for-bit."""
    from spark_bam_tpu.benchmarks.synth import synth_longread_bam

    path = tmp_path / f"lrfuzz{seed}.bam"
    synth_longread_bam(
        path, target_bytes=2 << 20, seed=seed,
        read_lens=(60_000, 140_000), ultra_seq_len=200_000,
    )
    cfg = dict(window_uncompressed=64 << 10, halo=32 << 10)

    flat = flatten_file(path)
    hdr = read_header(path)
    lens = np.array(hdr.contig_lengths.lengths_list(), dtype=np.int32)
    want = check_flat(flat.data, lens, at_eof=True)
    he = hdr.uncompressed_size

    got_v = np.zeros(flat.size, dtype=bool)
    for base, v in StreamChecker(path, Config(), **cfg).spans():
        got_v[base: base + len(v)] |= v
    np.testing.assert_array_equal(got_v, want.verdict)

    got_fm = np.full(flat.size, -1, dtype=np.int64)
    got_rb = np.full(flat.size, -1, dtype=np.int64)
    for base, fm, rb in StreamChecker(path, Config(), **cfg).full_spans():
        got_fm[base: base + len(fm)] = fm
        got_rb[base: base + len(rb)] = rb
    np.testing.assert_array_equal(got_fm, want.fail_mask)
    np.testing.assert_array_equal(got_rb, want.reads_before)

    assert StreamChecker(path, Config(), **cfg).count_reads() == int(
        want.verdict[he:].sum()
    )
