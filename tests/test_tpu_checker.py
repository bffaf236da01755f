"""TPU (JAX) checker engine vs the NumPy engine and ground truth.

Runs on the CPU backend (conftest forces JAX_PLATFORMS=cpu with 8 virtual
devices); the kernel is identical on real TPU.
"""

import numpy as np
import pytest

from spark_bam_tpu.bam.header import contig_lengths
from spark_bam_tpu.bam.index_records import read_records_index
from spark_bam_tpu.bgzf.flat import flatten_file
from spark_bam_tpu.check.vectorized import check_flat
from spark_bam_tpu.tpu.checker import TpuChecker


@pytest.fixture(scope="module")
def flat2(bam2):
    return flatten_file(bam2)


@pytest.fixture(scope="module")
def lengths2(bam2):
    return np.array(contig_lengths(bam2).lengths_list(), dtype=np.int32)


def test_tpu_matches_numpy_single_window(bam2, flat2, lengths2):
    # Window bigger than the file: one kernel call, at_eof inside.
    checker = TpuChecker(lengths2, window=2 << 20, halo=1 << 20)
    res = checker.check_buffer(flat2.data, at_eof=True)
    ref = check_flat(flat2.data, lengths2, at_eof=True)
    np.testing.assert_array_equal(res.verdict, ref.verdict)
    np.testing.assert_array_equal(res.fail_mask, ref.fail_mask)
    np.testing.assert_array_equal(res.reads_parsed, ref.reads_parsed)
    np.testing.assert_array_equal(res.reads_before, ref.reads_before)


def test_tpu_windowed_matches_truth(bam2, flat2, lengths2):
    # Small windows force multi-window execution with halo hand-off.
    checker = TpuChecker(lengths2, window=1 << 19, halo=1 << 17)
    res = checker.check_buffer(flat2.data, at_eof=True)
    records = read_records_index(str(bam2) + ".records")
    truth = np.zeros(flat2.size, dtype=bool)
    for pos in records:
        truth[flat2.flat_of_pos(pos.block_pos, pos.offset)] = True
    np.testing.assert_array_equal(res.verdict, truth)
    assert not res.escaped.any()


def test_tpu_windowed_flags_match_numpy(bam1):
    flat = flatten_file(bam1)
    lens = np.array(contig_lengths(bam1).lengths_list(), dtype=np.int32)
    checker = TpuChecker(lens, window=1 << 19, halo=1 << 17)
    res = checker.check_buffer(flat.data, at_eof=True)
    ref = check_flat(flat.data, lens, at_eof=True)
    np.testing.assert_array_equal(res.verdict, ref.verdict)
    np.testing.assert_array_equal(res.fail_mask, ref.fail_mask)
    np.testing.assert_array_equal(res.reads_before, ref.reads_before)
