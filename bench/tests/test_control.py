"""The control must come out as not correct: the same check with the halo
dropped (``bench/control.py``) does not answer what the index holds, at a
size a test run can hold. The chip runs at the cells' own size are in PERF.md."""

import pytest

from bench import control, oracle
from bench.tests.conftest import generate

SEEDS = (2 ** 31 + 1, 77, 123456789)


def make(name: str, seed: int, tmp_path):
    path = tmp_path / f"{name}.bam"
    return path, generate(name, seed, path)[0]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", ("wgs-short", "longread-hifi"))
def test_whole_file_without_halo_miscounts(name, seed, tmp_path):
    path, index = make(name, seed, tmp_path)
    size = int(index["uncompressed_bytes"])
    # Three windows to the file, as the cell's 64 MiB has at 24 MiB.
    got = control.count_without_halo(path, 0, size, window=size * 3 // 8)
    assert got != oracle.whole_file_count(index)
    # With the window as large as the file there is no seam to lose.
    assert control.count_without_halo(path, 0, size, window=size) == (
        oracle.whole_file_count(index))


@pytest.mark.parametrize("seed", SEEDS)
def test_a_served_range_without_halo_miscounts(seed, tmp_path):
    path, index = make("wgs-short", seed, tmp_path)
    start = int(index["block_starts"][2])
    end = start + int(index["compressed_bytes"]) // 2
    lo, hi = oracle.flat_range(index, start, end)
    got = control.count_without_halo(path, lo, hi, window=1 << 20)
    assert got != oracle.ranged_count(index, start, end)
