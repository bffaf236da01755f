"""Differential tests for the native fast DEFLATE decoder.

The fast path must be byte-exact with zlib on every stream it accepts and
must cleanly reject (→ zlib fallback) anything it can't decode. Fuzzing
covers all compression levels (level 1 = match-heavy fast-Huffman output,
level 9 = deep matches, level 0 = stored blocks), random and structured
payloads, and corrupted/truncated inputs.
"""

import zlib

import numpy as np
import pytest

from spark_bam_tpu import obs
from spark_bam_tpu.native.build import (
    inflate_blocks_fast_into,
    load_native,
)

pytestmark = pytest.mark.skipif(
    load_native() is None, reason="native library unavailable"
)


def _roundtrip(payloads: list[bytes], level: int) -> None:
    comps = []
    for p in payloads:
        c = zlib.compressobj(level, zlib.DEFLATED, -15)
        comps.append(c.compress(p) + c.flush())
    comp = np.frombuffer(b"".join(comps), dtype=np.uint8)
    offsets = np.zeros(len(comps), dtype=np.int64)
    lengths = np.array([len(c) for c in comps], dtype=np.int64)
    np.cumsum(lengths[:-1], out=offsets[1:])
    out_lengths = np.array([len(p) for p in payloads], dtype=np.int64)
    out_offsets = np.zeros(len(payloads), dtype=np.int64)
    np.cumsum(out_lengths[:-1], out=out_offsets[1:])
    total = int(out_lengths.sum())
    out = np.zeros(total + 8, dtype=np.uint8)
    assert inflate_blocks_fast_into(
        comp, offsets, lengths, out, out_offsets, out_lengths
    )
    assert out[:total].tobytes() == b"".join(payloads)


def test_levels_and_shapes():
    rng = np.random.default_rng(0)
    payloads = [
        b"",
        b"a",
        b"abc" * 10_000,                      # deep RLE-ish matches
        bytes(rng.integers(0, 256, 65_535, dtype=np.uint8)),   # incompressible
        bytes(rng.integers(65, 70, 65_535, dtype=np.uint8)),   # tiny alphabet
        (b"read_name_" + bytes(range(256))) * 200,
    ]
    for level in (0, 1, 2, 6, 9):
        _roundtrip(payloads, level)


def test_structured_bam_like_data():
    # Real fixture bytes exercise the actual symbol statistics.
    from pathlib import Path

    from spark_bam_tpu.bgzf.flat import flatten_file

    flat = flatten_file(Path("/root/reference/test_bams/src/main/resources/2.bam"))
    data = flat.data.tobytes()
    chunks = [data[i: i + 60_000] for i in range(0, len(data), 60_000)]
    for level in (1, 6):
        _roundtrip(chunks, level)


def test_fuzz_random_slices():
    rng = np.random.default_rng(7)
    base = bytes(rng.integers(0, 256, 200_000, dtype=np.uint8))
    struct = (b"ATCGATCG" * 64 + bytes(range(64))) * 500
    payloads = []
    for _ in range(50):
        src = base if rng.random() < 0.5 else struct
        a = int(rng.integers(0, len(src) - 1))
        b = min(len(src), a + int(rng.integers(1, 66_000)))
        payloads.append(src[a:b])
    for level in (1, 6, 9):
        _roundtrip(payloads, level)


def test_corrupt_input_falls_back_to_zlib_error():
    # A corrupted stream must not crash or mis-decode: the wrapper retries
    # it through zlib, which raises.
    payload = b"hello world " * 1000
    c = zlib.compressobj(6, zlib.DEFLATED, -15)
    comp_b = bytearray(c.compress(payload) + c.flush())
    comp_b[len(comp_b) // 2] ^= 0xFF
    comp = np.frombuffer(bytes(comp_b), dtype=np.uint8)
    out = np.zeros(len(payload) + 8, dtype=np.uint8)
    with pytest.raises(Exception):
        inflate_blocks_fast_into(
            comp,
            np.array([0], dtype=np.int64),
            np.array([len(comp)], dtype=np.int64),
            out,
            np.array([0], dtype=np.int64),
            np.array([len(payload)], dtype=np.int64),
        )


def test_truncated_input_rejected():
    payload = bytes(np.random.default_rng(3).integers(0, 256, 50_000, dtype=np.uint8))
    c = zlib.compressobj(1, zlib.DEFLATED, -15)
    comp_full = c.compress(payload) + c.flush()
    comp = np.frombuffer(comp_full[: len(comp_full) // 2], dtype=np.uint8)
    out = np.zeros(len(payload) + 8, dtype=np.uint8)
    with pytest.raises(Exception):
        inflate_blocks_fast_into(
            comp,
            np.array([0], dtype=np.int64),
            np.array([len(comp)], dtype=np.int64),
            out,
            np.array([0], dtype=np.int64),
            np.array([len(payload)], dtype=np.int64),
        )


def test_pipeline_depth_fanout(tmp_path):
    # depth=2 pipeline yields identical windows to depth=1.
    from spark_bam_tpu.benchmarks.synth import synth_bam
    from spark_bam_tpu.tpu.inflate import InflatePipeline

    out = tmp_path / "mid.bam"
    synth_bam(out, 2 << 20)
    w = 1 << 20
    one = [v.data.tobytes() for v in InflatePipeline(out, w, depth=1)]
    two = [v.data.tobytes() for v in InflatePipeline(out, w, depth=3)]
    assert one == two
    assert b"".join(one) == b"".join(two)


# ----------------------------------------------------- DEFLATE edge cases
# The inflater every window and row now goes through
# (``bgzf/flat.inflate_blocks``), on both of its engines, held to the
# streams the retired tokenizers were held to: right bytes or the typed
# error (``INPUT_ERRORS``), never wrong bytes.

class _BitWriter:
    """LSB-first DEFLATE bit emitter for hand-built edge-case streams."""

    def __init__(self):
        self.bits: list[int] = []

    def put(self, value: int, n: int):           # LSB-first fields
        self.bits.extend((value >> i) & 1 for i in range(n))
        return self

    def put_code(self, code: int, n: int):       # Huffman codes: MSB-first
        self.bits.extend((code >> i) & 1 for i in reversed(range(n)))
        return self

    def fixed(self, sym: int):
        """RFC 1951 §3.2.6 fixed litlen code for ``sym``."""
        if sym < 144:
            return self.put_code(0x30 + sym, 8)
        if sym < 256:
            return self.put_code(0x190 + (sym - 144), 9)
        if sym < 280:
            return self.put_code(sym - 256, 7)
        return self.put_code(0xC0 + (sym - 280), 8)

    def bytes(self) -> bytes:
        bits = self.bits + [0] * (-len(self.bits) % 8)
        return bytes(
            sum(b << j for j, b in enumerate(bits[i: i + 8]))
            for i in range(0, len(bits), 8)
        )


def _deflate(data: bytes, level: int = 6,
             strategy: int = zlib.Z_DEFAULT_STRATEGY) -> bytes:
    co = zlib.compressobj(level, zlib.DEFLATED, -15, 8, strategy)
    return co.compress(data) + co.flush()


def _skewed(n: int = 20_000) -> bytes:
    rng = np.random.default_rng(11)
    return bytes(rng.choice([32, 101, 116, 97, 10, 200], size=n,
                            p=[.3, .25, .2, .15, .05, .05]).astype(np.uint8))


def _dynamic_with_cl_runs():
    """A skewed alphabet at level 9: a dynamic block whose code-length
    header uses the 16/17/18 run codes."""
    data = _skewed()
    comp = _deflate(data, level=9)
    assert (comp[0] >> 1) & 3 == 2  # the first block really is dynamic
    return [(comp, data)]


def _stored():
    data = np.random.default_rng(1).integers(
        0, 256, 40_000, dtype=np.uint8).tobytes()
    comp = _deflate(data, level=0)
    assert (comp[0] >> 1) & 3 == 0
    return [(comp, data)]


def _fixed():
    data = b"fixed huffman " * 200
    comp = _deflate(data, strategy=zlib.Z_FIXED)
    assert (comp[0] >> 1) & 3 == 1
    return [(comp, data)]


def _zero_length_final_stored_block():
    """A fixed block, then an empty stored BFINAL block (what BGZF writers
    emit): the stored block contributes nothing."""
    w = _BitWriter().put(0, 1).put(1, 2)
    for ch in b"abc":
        w.fixed(ch)
    w.fixed(256).put(1, 1).put(0, 2)
    return [(w.bytes() + b"\x00\x00\xff\xff", b"abc")]


def _no_distance_codes():
    """RFC 1951 §3.2.7: a match-free dynamic block may declare one distance
    code of zero bits (libdeflate in htslib emits this shape). Litlen
    lengths {65: 1, 256: 1}, data "AA"."""
    w = _BitWriter().put(1, 1).put(2, 2).put(0, 5).put(0, 5).put(14, 4)
    # Code-length code lengths in the order 16,17,18,0,8,7,...,1:
    # {0: 2, 1: 2, 17: 2, 18: 2}, canonical codes 00, 01, 10, 11.
    for cl_len in [0, 2, 2, 2] + [0] * 13 + [2]:
        w.put(cl_len, 3)
    cl = {0: 0, 1: 1, 17: 2, 18: 3}
    w.put_code(cl[18], 2).put(65 - 11, 7)    # 65 zeros
    w.put_code(cl[1], 2)                     # 'A' → length 1
    w.put_code(cl[18], 2).put(138 - 11, 7)   # 66..255: 138 + 52 zeros
    w.put_code(cl[18], 2).put(52 - 11, 7)
    w.put_code(cl[1], 2)                     # end-of-block → length 1
    w.put_code(cl[0], 2)                     # the one distance code: 0
    w.put_code(0, 1).put_code(0, 1).put_code(1, 1)  # 'A' 'A' EOB
    return [(w.bytes(), b"AA")]


def _overlapping(distance: int):
    """A copy whose source overlaps its destination, across nearly a whole
    member."""
    def make():
        data = bytes(range(65, 65 + distance)) * (65_535 // distance)
        return [(_deflate(data), data)]
    return make


def _mixed_batch():
    rng = np.random.default_rng(2)
    datas = [b"x", b"x" * 100, b"x" * 65_535,
             rng.integers(0, 256, 10_000, dtype=np.uint8).tobytes(),
             b"payload " * 512, b"tail", b""]
    return [(_deflate(d), d) for d in datas]


def _invalid_litlen(sym: int):
    """286 and 287 have fixed-Huffman codes but are no litlen symbols."""
    def make():
        w = _BitWriter().put(1, 1).put(1, 2).fixed(ord("A")).fixed(sym)
        return [(w.bytes() + b"\x00" * 4, None, 1)]
    return make


def _distance_before_start():
    """Length 3 at distance 4 from output position 1: accepting it would
    fabricate bytes."""
    w = _BitWriter().put(1, 1).put(1, 2).fixed(ord("A")).fixed(257)
    w.put_code(3, 5).fixed(256)
    return [(w.bytes() + b"\x00" * 4, None, 4)]


def _truncated():
    comp = _deflate(b"hello world" * 50)
    return [(comp[: len(comp) // 2], None, 550)]


def _size_mismatch():
    """The footer lies about the size by one byte."""
    return [(_deflate(b"hello world" * 50), None, 549)]


EDGE_CASES = {
    "dynamic-cl-runs": _dynamic_with_cl_runs,
    "stored": _stored,
    "fixed": _fixed,
    "deep-rle-distance-1": lambda: [(_deflate(b"a" * 65_535), b"a" * 65_535)],
    "empty-payload": lambda: [(_deflate(b""), b"")],
    "zero-length-final-stored": _zero_length_final_stored_block,
    "no-distance-codes": _no_distance_codes,
    "overlap-1": _overlapping(1),
    "overlap-2": _overlapping(2),
    "overlap-3": _overlapping(3),
    "overlap-7": _overlapping(7),
    "mixed-batch": _mixed_batch,
    "full-65536-member": lambda: [(_deflate(_skewed(65_536)),
                                   _skewed(65_536))],
    "invalid-litlen-286": _invalid_litlen(286),
    "invalid-litlen-287": _invalid_litlen(287),
    "distance-before-start": _distance_before_start,
    "truncated": _truncated,
    "size-mismatch": _size_mismatch,
}


@pytest.mark.parametrize("engine", ["native", "zlib"])
@pytest.mark.parametrize("case", sorted(EDGE_CASES))
def test_inflate_edge_cases(case, engine, tmp_path, monkeypatch):
    """Each member is a ``(body, data)`` pair, or ``(body, None, declared
    size)`` for a stream that must be refused."""
    from spark_bam_tpu.bgzf.block import Metadata
    from spark_bam_tpu.bgzf.flat import inflate_blocks
    from spark_bam_tpu.compress.huffman import bgzf_member
    from spark_bam_tpu.core.channel import open_channel
    from spark_bam_tpu.core.guard import INPUT_ERRORS
    from spark_bam_tpu.native import build

    members = EDGE_CASES[case]()
    want = [m[1] for m in members]
    if None not in want:  # the stream really is valid, and really is that
        assert [zlib.decompress(m[0], -15) for m in members] == want
    blob, metas = b"", []
    for body, data, *declared in members:
        size = len(data) if data is not None else declared[0]
        member = bgzf_member(body, zlib.crc32(data or b""), size)
        metas.append(Metadata(len(blob), len(member), size))
        blob += member
    path = tmp_path / "members.bgzf"
    path.write_bytes(blob)
    if engine == "zlib":
        monkeypatch.setattr(build, "_LIB_CACHE", [None])
    obs.shutdown()
    reg = obs.configure()
    try:
        with open_channel(path) as ch:
            if None in want:
                with pytest.raises(INPUT_ERRORS):
                    inflate_blocks(ch, metas)
                return
            view = inflate_blocks(ch, metas)
        spans = [e for e in reg.events() if e["name"] == "inflate.window"]
    finally:
        obs.shutdown()
    assert view.data.tobytes() == b"".join(want)
    assert view.size == sum(map(len, want))
    assert view.block_flat.tolist() == [
        sum(map(len, want[:i])) for i in range(len(want))]
    assert [s["attrs"]["engine"] for s in spans] == [engine]
