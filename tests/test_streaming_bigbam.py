"""Big-BAM streaming equality: the product path == native CPU == manifest.

The streaming device path (``count_reads_tpu`` → ``StreamChecker``) is the
same code chip_smoke.py drives; this test pins its count against two
independent sources on a multi-window synthesized BAM: the native C++
eager checker over the whole flat file, and the synthesis manifest's exact
read count. Scale via ``SB_BIG_BAM_TEST_BYTES`` (driver/bench runs use
≥1 GB; CI default keeps the CPU-backend kernel affordable).
"""

import os

import numpy as np
import pytest

from spark_bam_tpu.bam.header import read_header
from spark_bam_tpu.benchmarks.synth import synth_bam
from spark_bam_tpu.bgzf.flat import flatten_file
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.load.tpu_load import count_reads_tpu, record_starts_streaming

TARGET = int(os.environ.get("SB_BIG_BAM_TEST_BYTES", str(32 << 20)))
# Small windows force many stitched windows + halo carries.
CFG = Config(window_size=8 << 20, halo_size=1 << 20)


@pytest.fixture(scope="module")
def big_bam(tmp_path_factory):
    out = tmp_path_factory.mktemp("bigbam") / "big.bam"
    manifest = synth_bam(out, TARGET)
    return out, manifest


def test_streaming_count_three_way(big_bam):
    path, manifest = big_bam
    assert count_reads_tpu(path, CFG) == manifest["reads"]

    from spark_bam_tpu.native.build import eager_check_native

    flat = flatten_file(path)
    hdr = read_header(path)
    lens = np.array(hdr.contig_lengths.lengths_list(), dtype=np.int32)
    out = eager_check_native(
        flat.data, np.arange(flat.size, dtype=np.int64), lens
    )
    if out is None:
        pytest.skip("native library unavailable")
    native_count = int(out[hdr.uncompressed_size:].sum())
    assert native_count == manifest["reads"]


def test_streaming_starts_match_native(big_bam):
    path, manifest = big_bam
    from spark_bam_tpu.native.build import eager_check_native

    flat = flatten_file(path)
    hdr = read_header(path)
    lens = np.array(hdr.contig_lengths.lengths_list(), dtype=np.int32)
    out = eager_check_native(
        flat.data, np.arange(flat.size, dtype=np.int64), lens
    )
    if out is None:
        pytest.skip("native library unavailable")
    want = np.flatnonzero(out)
    want = want[want >= hdr.uncompressed_size]

    got = np.sort(np.concatenate(list(record_starts_streaming(path, CFG))))
    np.testing.assert_array_equal(got, want)
    assert len(got) == manifest["reads"]
