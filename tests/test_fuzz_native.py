"""Fuzz: native decoders against zlib ground truth and corrupted input.

The native DEFLATE inflater and rANS decoder parse untrusted bytes in
process; these tests hammer them with (a) every zlib strategy/level
combination — the inflater every window goes through
(``bgzf/flat.inflate_blocks``) must agree with zlib byte-for-byte — and
(b) random truncations/corruptions, which must produce a Python exception,
never a crash, a hang or wrong bytes.
"""

import io
import zlib

import numpy as np
import pytest

from spark_bam_tpu.bgzf.block import Metadata
from spark_bam_tpu.bgzf.flat import inflate_blocks
from spark_bam_tpu.compress.huffman import bgzf_member
from spark_bam_tpu.core.channel import FileStreamChannel
from spark_bam_tpu.core.guard import INPUT_ERRORS
from spark_bam_tpu.cram import rans
from spark_bam_tpu.native.build import load_native, rans_decompress_native

pytestmark = pytest.mark.skipif(
    load_native() is None, reason="native runtime unavailable"
)


def _inflate_one(comp: bytes, out_len: int) -> bytes:
    """One raw-DEFLATE stream framed as a BGZF member of ``out_len`` bytes,
    through the native inflater (an in-memory channel: the bulk-read
    branch, where ``tests/test_fast_inflate.py`` takes the mmap one)."""
    member = bgzf_member(comp, 0, out_len)
    with FileStreamChannel(io.BytesIO(member), len(member)) as ch:
        view = inflate_blocks(ch, [Metadata(0, len(member), out_len)])
    return view.data.tobytes()


def _corpus():
    rng = np.random.default_rng(99)
    motifs = rng.integers(0, 256, (4, 48), dtype=np.uint8)
    structured = np.concatenate(
        [motifs[i] for i in rng.integers(0, 4, 400)]
    ).tobytes()
    return [
        b"",
        b"\x00" * 3000,
        b"abc" * 7000,
        structured,
        bytes(rng.integers(0, 256, 30_000, dtype=np.uint8)),
        bytes(rng.integers(65, 70, 60_000, dtype=np.uint8)),
    ]


def test_inflater_agrees_with_zlib_across_strategies():
    strategies = [
        zlib.Z_DEFAULT_STRATEGY, zlib.Z_FILTERED, zlib.Z_HUFFMAN_ONLY,
        zlib.Z_RLE, zlib.Z_FIXED,
    ]
    for data in _corpus():
        for level in (0, 1, 6, 9):
            for strategy in strategies:
                co = zlib.compressobj(level, zlib.DEFLATED, -15, 8, strategy)
                comp = co.compress(data) + co.flush()
                assert _inflate_one(comp, len(data)) == data, (
                    level, strategy, len(data),
                )


def test_inflater_multi_deflate_block_streams():
    # Z_FULL_FLUSH forces mid-stream block boundaries (and window resets),
    # exercising the multi-block loop and stored/dynamic interleavings.
    rng = np.random.default_rng(5)
    parts = [
        bytes(rng.integers(0, 256, n, dtype=np.uint8))
        for n in (1, 500, 10_000)
    ] + [b"run" * 4000]
    co = zlib.compressobj(6, zlib.DEFLATED, -15)
    comp = b""
    for part in parts:
        comp += co.compress(part) + co.flush(zlib.Z_FULL_FLUSH)
    comp += co.flush()
    data = b"".join(parts)
    assert _inflate_one(comp, len(data)) == data


def test_inflater_never_crashes_or_lies_on_corrupt_streams():
    rng = np.random.default_rng(17)
    base = zlib.compress(b"corpus " * 3000)[2:-4]  # the raw deflate body
    assert _inflate_one(base, 21_000) == b"corpus " * 3000
    refused = 0
    for trial in range(200):
        blob = bytearray(base)
        kind = trial % 3
        if kind == 0:
            blob = blob[: rng.integers(0, len(blob))]
        elif kind == 1 and len(blob):
            for _ in range(int(rng.integers(1, 8))):
                blob[int(rng.integers(0, len(blob)))] ^= int(rng.integers(1, 256))
        else:
            blob = bytearray(rng.integers(0, 256, 300, dtype=np.uint8).tobytes())
        try:
            got = _inflate_one(bytes(blob), 21_000)
        except INPUT_ERRORS:
            refused += 1  # rejection is the expected outcome
            continue
        # Accepted: then zlib reads the same 21,000 bytes out of it.
        assert got == zlib.decompressobj(-15).decompress(bytes(blob))
    assert refused > 150


def test_rans_never_crashes_on_corrupt_streams():
    rng = np.random.default_rng(23)
    for order in (0, 1):
        base = rans.compress(b"payload!" * 2000, order)
        for trial in range(200):
            blob = bytearray(base)
            kind = trial % 3
            if kind == 0:
                blob = blob[: rng.integers(0, len(blob))]
            elif kind == 1:
                for _ in range(int(rng.integers(1, 8))):
                    blob[int(rng.integers(0, len(blob)))] ^= int(
                        rng.integers(1, 256)
                    )
            else:
                blob = bytearray(
                    rng.integers(0, 256, 64, dtype=np.uint8).tobytes()
                )
            if len(blob) < 9:
                continue
            out_sz = int.from_bytes(blob[5:9], "little")
            if out_sz > 1 << 22:
                continue  # cap the fuzz allocation, not a decoder input limit
            try:
                rans_decompress_native(bytes(blob), out_sz)
            except IOError:
                pass
