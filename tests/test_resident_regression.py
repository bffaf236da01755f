"""Regression coverage for the resident-mode chunk budget:
``count_reads_resident`` must complete and match the streaming count,
in-process on the CPU backend, at any ``resident_chunk_bytes``."""

import pytest

from spark_bam_tpu.core.config import Config
from spark_bam_tpu.native.build import load_native
from spark_bam_tpu.tpu.stream_check import StreamChecker

from tests.bam_factories import random_bam

pytestmark = pytest.mark.skipif(
    load_native() is None, reason="native runtime unavailable"
)

CFG = dict(window_uncompressed=128 << 10, halo=32 << 10)


def _streaming_count(path, **cfg):
    return StreamChecker(path, Config(), **cfg).count_reads()


def test_resident_matches_streaming_in_process(tmp_path):
    path = tmp_path / "r.bam"
    random_bam(path, 21, contigs=(("chr1", 5_000_000),), dup_rate=0.05)
    want = _streaming_count(path, **CFG)
    got = StreamChecker(path, Config(), **CFG).count_reads_resident(
        chunk_windows=4, first_chunk_windows=2
    )
    assert got == want


def test_resident_tiny_chunk_cap_still_exact(tmp_path):
    """A pathologically small ``resident_chunk_bytes`` (the device-memory
    knob at its floor) degrades chunk size, never correctness."""
    path = tmp_path / "tiny.bam"
    random_bam(path, 22, contigs=(("chr1", 5_000_000),), dup_rate=0.05)
    want = _streaming_count(path, **CFG)
    got = StreamChecker(
        path, Config(resident_chunk_bytes=1), **CFG
    ).count_reads_resident(chunk_windows=256)
    assert got == want


def test_resident_chunk_bytes_cap(tmp_path):
    """The resident-chunk HBM cap (the device-memory budget) must bound the
    chunk size without changing the count."""
    path = tmp_path / "cap.bam"
    random_bam(path, 17, contigs=(("chr1", 5_000_000),), dup_rate=0.05)
    want = _streaming_count(path, **CFG)
    got = StreamChecker(
        path, Config(resident_chunk_bytes=1 << 20), **CFG
    ).count_reads_resident(chunk_windows=64, first_chunk_windows=2)
    assert got == want
    # And the knob flows through the generic config surface.
    cfg = Config.from_dict({"spark.bam.resident.chunk.bytes": "64MB"})
    assert cfg.resident_chunk_bytes == 64 << 20
