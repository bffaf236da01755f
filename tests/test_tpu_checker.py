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


def test_count_scan_matches_per_window_kernel(bam1):
    """count_scan over packed rows must equal count_window per row. Rows
    are filled to exactly n == w (the contract edge): at a packed stride
    of w the scan's PAD lookahead would read the NEXT row's bytes instead
    of the zeros check_window requires — the regression this pins is
    silent verdict corruption near row tails (stride must be w+PAD)."""
    import jax.numpy as jnp

    from spark_bam_tpu.bam.header import contig_lengths
    from spark_bam_tpu.tpu.checker import (
        PAD,
        make_count_scan,
        make_count_window,
    )

    flat = flatten_file(bam1)
    lens_arr = np.array(contig_lengths(bam1).lengths_list(), dtype=np.int32)
    lens = np.zeros(1024, dtype=np.int32)
    lens[: len(lens_arr)] = lens_arr
    nc = jnp.int32(len(lens_arr))

    w = 1 << 18
    halo = 1 << 16
    # Halo-carry rows over the real stream, every interior row exactly w
    # bytes (n == w) so row tails abut the next slot.
    rows = []
    base = 0
    while base < flat.size:
        buf = flat.data[base: base + w]
        at_eof = base + w >= flat.size
        own = len(buf) if at_eof else len(buf) - halo
        rows.append((buf, at_eof, 0 if base else 104, own))  # 104 ≈ header
        base += own
    # Reference: the trusted per-window kernel, each row zero-padded alone.
    ref_kernel = make_count_window(w, 10)
    want = 0
    for buf, ae, lo, own in rows:
        padded = np.zeros(w + PAD, dtype=np.uint8)
        padded[: len(buf)] = buf
        out = ref_kernel(
            jnp.asarray(padded), jnp.asarray(lens), nc,
            jnp.int32(len(buf)), jnp.bool_(ae), jnp.int32(lo), jnp.int32(own),
        )
        want += int(out["count"])

    stride = w + PAD
    kp = len(rows)
    chunk = np.zeros(kp * stride, dtype=np.uint8)
    ns = np.zeros(kp, dtype=np.int32)
    aes = np.zeros(kp, dtype=bool)
    los = np.zeros(kp, dtype=np.int32)
    owns = np.zeros(kp, dtype=np.int32)
    for j, (buf, ae, lo, own) in enumerate(rows):
        chunk[j * stride: j * stride + len(buf)] = buf
        ns[j], aes[j], los[j], owns[j] = len(buf), ae, lo, own
    scan_kernel = make_count_scan(w, 10)
    out = scan_kernel(
        jnp.asarray(chunk), jnp.asarray(lens), nc,
        jnp.asarray(np.arange(kp, dtype=np.int32) * stride),
        jnp.asarray(ns), jnp.asarray(aes), jnp.asarray(los),
        jnp.asarray(owns),
    )
    assert int(out["esc_count"]) == 0  # full halos; no escapes expected
    assert int(out["count"]) == want
