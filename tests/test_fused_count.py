"""The fully device-resident count path (stream_check._count_reads_fused
+ checker.count_window_tokens): packed tokens in, scalars out, carry
chained in HBM. Differential against the classic host-inflate streaming
count — same files, same Config surface, byte-exact counts."""

import pytest

from spark_bam_tpu.core.config import Config
from spark_bam_tpu.native.build import load_native
from spark_bam_tpu.tpu.stream_check import StreamChecker

from tests.bam_factories import random_bam

pytestmark = pytest.mark.skipif(
    load_native() is None, reason="native runtime unavailable"
)

CFG = dict(window_uncompressed=128 << 10, halo=32 << 10)


def _host_count(path, **cfg):
    return StreamChecker(
        path, Config(device_inflate=False, fused_count=False), **cfg
    ).count_reads()


@pytest.mark.parametrize("seed", range(3))
def test_fused_count_matches_host(tmp_path, seed):
    path = tmp_path / f"f{seed}.bam"
    random_bam(path, seed, contigs=(("chr1", 5_000_000),), dup_rate=0.05)
    want = _host_count(path, **CFG)
    ck = StreamChecker(path, Config(device_inflate=True), **CFG)
    assert ck.pipeline.device_copy  # explicit True wins on the CPU backend
    got = ck._count_reads_fused()
    assert got == want


def test_count_reads_routes_to_fused(tmp_path):
    """``count_reads`` must take the fused route whenever the device
    inflate resolves on (fused_count auto), and produce the same count."""
    path = tmp_path / "route.bam"
    random_bam(path, 11, contigs=(("chr1", 5_000_000),), dup_rate=0.05)
    want = _host_count(path, **CFG)
    calls = []
    ck = StreamChecker(path, Config(device_inflate=True), **CFG)
    orig = ck._count_reads_fused
    ck._count_reads_fused = lambda: calls.append(1) or orig()
    assert ck.count_reads() == want
    assert calls  # the fused path actually ran


def test_fused_count_off_switch(tmp_path):
    """``fused_count=False`` pins the classic loop even with the device
    inflate on."""
    path = tmp_path / "off.bam"
    random_bam(path, 12, contigs=(("chr1", 5_000_000),), dup_rate=0.05)
    want = _host_count(path, **CFG)
    ck = StreamChecker(
        path, Config(device_inflate=True, fused_count=False), **CFG
    )
    ck._count_reads_fused = lambda: (_ for _ in ()).throw(
        AssertionError("fused path must not run")
    )
    assert ck.count_reads() == want


def test_fused_count_funnel_off(tmp_path):
    path = tmp_path / "fo.bam"
    random_bam(path, 13, contigs=(("chr1", 5_000_000),), dup_rate=0.05)
    want = _host_count(path, **CFG)
    got = StreamChecker(
        path, Config(device_inflate=True, funnel="off"), **CFG
    ).count_reads()
    assert got == want


def test_fused_count_multi_contig_and_carry(tmp_path):
    """Small windows force many carry seams; two contigs exercise the
    contig-length table through the fused kernel."""
    path = tmp_path / "mc.bam"
    random_bam(
        path, 14, contigs=(("chr1", 5_000_000), ("chr2", 3_000_000)),
        dup_rate=0.1,
    )
    cfg = dict(window_uncompressed=64 << 10, halo=16 << 10)
    want = _host_count(path, **cfg)
    got = StreamChecker(path, Config(device_inflate=True), **cfg).count_reads()
    assert got == want


def test_fused_count_escape_falls_back_exact(tmp_path):
    """Chains beyond the halo (long reads vs a tiny halo) must escape to
    the exact spans path — never a wrong count."""
    from spark_bam_tpu.benchmarks.synth import synth_longread_bam

    path = tmp_path / "lr.bam"
    synth_longread_bam(
        path, target_bytes=2 << 20, seed=0,
        read_lens=(60_000, 140_000), ultra_seq_len=200_000,
    )
    cfg = dict(window_uncompressed=256 << 10, halo=16 << 10)
    want = _host_count(path, **cfg)
    got = StreamChecker(path, Config(device_inflate=True), **cfg).count_reads()
    assert got == want


def test_fused_demotes_without_tokenizer(tmp_path, monkeypatch):
    """Tokenizer unavailable ⇒ _count_reads_fused returns None and
    count_reads lands the classic loop's exact count."""
    path = tmp_path / "demote.bam"
    random_bam(path, 15, contigs=(("chr1", 5_000_000),), dup_rate=0.05)
    want = _host_count(path, **CFG)
    import spark_bam_tpu.native.build as nb

    ck = StreamChecker(path, Config(device_inflate=True), **CFG)
    monkeypatch.setattr(nb, "load_native", lambda *a, **k: None)
    assert ck._count_reads_fused() is None
    assert ck.count_reads() == want


def test_fused_funnel_stats_populated(tmp_path):
    path = tmp_path / "fs.bam"
    random_bam(path, 16, contigs=(("chr1", 5_000_000),), dup_rate=0.05)
    ck = StreamChecker(path, Config(device_inflate=True), **CFG)
    ck.count_reads()
    assert ck.funnel_stats is not None
    assert ck.funnel_stats["screened"] > 0
    assert 0 < ck.funnel_stats["survivors"] <= ck.funnel_stats["screened"]


def test_resident_chunk_bytes_cap(tmp_path):
    """The resident-chunk HBM cap (the device-memory budget) must bound the
    chunk size without changing the count."""
    path = tmp_path / "cap.bam"
    random_bam(path, 17, contigs=(("chr1", 5_000_000),), dup_rate=0.05)
    want = _host_count(path, **CFG)
    got = StreamChecker(
        path, Config(resident_chunk_bytes=1 << 20), **CFG
    ).count_reads_resident(chunk_windows=64, first_chunk_windows=2)
    assert got == want
    # And the knob flows through the generic config surface.
    cfg = Config.from_dict({"spark.bam.resident.chunk.bytes": "64MB"})
    assert cfg.resident_chunk_bytes == 64 << 20
