"""The generators: the same seed gives the same bytes, and the index they
return is what a host-zlib walk of the file finds."""

import struct
import zlib

import numpy as np
import pytest

from bench.tests.conftest import generate

CONFIGS = ("wgs-short", "longread-hifi")
SEED = 2 ** 31 + 12345  # the driver's seeds are this large


def make(name: str, seed: int, tmp_path, tag: str):
    path = tmp_path / f"{name}-{tag}.bam"
    index, config = generate(name, seed, path)
    return path, index, config


def walk(path):
    """Member starts and sizes, and the flat bytes, by zlib alone."""
    raw = path.read_bytes()
    starts, flats, out, at = [], [], [], 0
    flat = 0
    while at < len(raw):
        assert raw[at: at + 4] == b"\x1f\x8b\x08\x04"
        (bsize,) = struct.unpack_from("<H", raw, at + 16)
        body = raw[at + 18: at + bsize + 1 - 8]
        crc, isize = struct.unpack_from("<II", raw, at + bsize + 1 - 8)
        data = zlib.decompress(body, -15)
        assert len(data) == isize and zlib.crc32(data) == crc
        starts.append(at)
        flats.append(flat)
        out.append(data)
        flat += isize
        at += bsize + 1
    assert out[-1] == b""  # the EOF member
    return np.array(starts[:-1]), np.array(flats[:-1]), b"".join(out)


@pytest.mark.parametrize("name", CONFIGS)
def test_same_seed_same_bytes(name, tmp_path):
    a, ia, _ = make(name, SEED, tmp_path, "a")
    b, ib, _ = make(name, SEED, tmp_path, "b")
    c, _ic, _ = make(name, SEED + 1, tmp_path, "c")
    assert a.read_bytes() == b.read_bytes()
    assert a.read_bytes() != c.read_bytes()
    assert np.array_equal(ia["record_starts"], ib["record_starts"])


@pytest.mark.parametrize("name", CONFIGS)
def test_index_matches_a_zlib_walk(name, tmp_path):
    path, index, config = make(name, SEED, tmp_path, "w")
    starts, flats, flat = walk(path)
    assert np.array_equal(starts, index["block_starts"])
    assert np.array_equal(flats, index["block_flat"])
    assert len(flat) == index["uncompressed_bytes"]
    assert path.stat().st_size == index["compressed_bytes"]
    # The BAM header, then records by their length prefixes.
    assert flat[:4] == b"BAM\x01"
    (l_text,) = struct.unpack_from("<i", flat, 4)
    (n_ref,) = struct.unpack_from("<i", flat, 8 + l_text)
    at = 12 + l_text
    for _ in range(n_ref):
        (l_name,) = struct.unpack_from("<i", flat, at)
        at += 8 + l_name
    assert at == index["header_end"]
    found = []
    while at < len(flat):
        found.append(at)
        (size,) = struct.unpack_from("<i", flat, at)
        assert size >= 32
        at += 4 + size
    assert at == len(flat)
    assert found == index["record_starts"].tolist()
    sizes = np.diff(np.append(found, len(flat)))
    halo = config["shapes"]["halo_bytes"]
    assert sizes.max() < halo  # the configuration states this
    if name == "wgs-short":
        assert 340 < sizes.mean() < 420
        assert 4.0 < index["ratio"] < 5.5
    else:
        assert 15_000 <= sizes.min() and sizes.max() <= 38_500


@pytest.mark.parametrize("name", CONFIGS)
def test_the_program_reads_it(name, tmp_path):
    """Records parse with the program's own reader: names, positions sorted,
    CIGARs that consume the read."""
    from spark_bam_tpu.load.api import load_bam

    path, index, _ = make(name, SEED, tmp_path, "p")
    records = load_bam(str(path)).collect()
    assert len(records) == len(index["record_starts"])
    positions = [r.pos for r in records]
    assert positions == sorted(positions)
    for r in records[:50]:
        consumed = sum(n for n, op in r.cigar if op in (0, 1, 4, 7, 8))
        assert consumed in (0, len(r.seq))
