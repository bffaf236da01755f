"""Two-phase device inflate: host entropy tokenize + device LZ77 resolution.

Differential tests against zlib — the permanent correctness oracle
(SURVEY.md §7 hard-part #1: "keep host-zlib as the correctness fallback").
Covers all three DEFLATE block types (stored / fixed / dynamic Huffman),
deep overlapping-copy chains (RLE), multi-block streams, and a whole
reference BAM.
"""

import zlib

import numpy as np
import pytest

from spark_bam_tpu.bgzf.flat import flatten_file
from spark_bam_tpu.native.build import load_native, tokenize_deflate_native
from spark_bam_tpu.tpu.inflate import (
    STRIDE,
    inflate_blocks_device,
    inflate_file_device,
)

pytestmark = pytest.mark.skipif(
    load_native() is None, reason="native runtime unavailable"
)


def _deflate(data: bytes, level: int = 6, strategy: int = zlib.Z_DEFAULT_STRATEGY):
    co = zlib.compressobj(level, zlib.DEFLATED, -15, 8, strategy)
    return co.compress(data) + co.flush()


def _roundtrip_one(data: bytes, **kw) -> None:
    comp = np.frombuffer(_deflate(data, **kw), dtype=np.uint8)
    out = inflate_blocks_device(
        comp,
        np.array([0], dtype=np.int64),
        np.array([len(comp)], dtype=np.int64),
        np.array([len(data)], dtype=np.int64),
    )
    assert out is not None
    assert out.tobytes() == data


def test_dynamic_huffman_roundtrip():
    rng = np.random.default_rng(0)
    # Compressible but non-trivial: repeated 64-byte motifs + noise.
    motifs = rng.integers(0, 256, (8, 64), dtype=np.uint8)
    picks = rng.integers(0, 8, 500)
    data = np.concatenate([motifs[p] for p in picks]).tobytes()
    _roundtrip_one(data)


def test_stored_blocks():
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, 40_000, dtype=np.uint8).tobytes()
    _roundtrip_one(data, level=0)


def test_fixed_huffman():
    _roundtrip_one(b"fixed huffman " * 200, strategy=zlib.Z_FIXED)


def test_deep_rle_chains():
    # dist=1 overlapping copies: every byte's chain points at the single
    # root literal through a ~64K-deep chain — the pointer-doubling
    # worst case.
    _roundtrip_one(b"a" * (STRIDE - 1))


def test_empty_payload():
    _roundtrip_one(b"")


def test_batched_blocks_roundtrip():
    rng = np.random.default_rng(2)
    datas = [
        b"x" * striped
        for striped in (1, 100, 65_535)
    ] + [rng.integers(0, 256, 10_000, dtype=np.uint8).tobytes()]
    comps = [np.frombuffer(_deflate(d), dtype=np.uint8) for d in datas]
    offsets = np.zeros(len(comps), dtype=np.int64)
    lengths = np.array([len(c) for c in comps], dtype=np.int64)
    np.cumsum(lengths[:-1], out=offsets[1:])
    out = inflate_blocks_device(
        np.concatenate(comps),
        offsets,
        lengths,
        np.array([len(d) for d in datas], dtype=np.int64),
    )
    assert out.tobytes() == b"".join(datas)


def test_no_distance_codes_stream():
    # RFC 1951 §3.2.7: a match-free block may declare a single distance
    # code of zero bits. Real encoders (libdeflate in htslib) emit this
    # shape; the tokenizer must accept it. Hand-assembled: dynamic block,
    # litlen lens {65:1, 256:1}, one zero-length dist code, data "AA".
    bits = []

    def put(value, n):  # LSB-first field
        bits.extend((value >> k) & 1 for k in range(n))

    def put_code(code, n):  # Huffman code, MSB-first
        bits.extend((code >> (n - 1 - k)) & 1 for k in range(n))

    put(1, 1)   # BFINAL
    put(2, 2)   # BTYPE = dynamic
    put(0, 5)   # HLIT  = 257 codes
    put(0, 5)   # HDIST = 1 code
    put(14, 4)  # HCLEN = 18 entries
    # Code-length code lens in the fixed order 16,17,18,0,8,7,...,1:
    # {0:2, 1:2, 17:2, 18:2}, canonical codes 00,01,10,11.
    for cl_len in [0, 2, 2, 2] + [0] * 13 + [2]:
        put(cl_len, 3)
    cl = {0: (0, 2), 1: (1, 2), 17: (2, 2), 18: (3, 2)}

    def put_cl(sym):
        put_code(*cl[sym])

    put_cl(18); put(65 - 11, 7)    # 65 zeros
    put_cl(1)                      # symbol 65 ('A') → len 1
    put_cl(18); put(138 - 11, 7)   # 138 zeros
    put_cl(18); put(52 - 11, 7)    # 52 zeros  (66..255 = 190 total)
    put_cl(1)                      # symbol 256 (EOB) → len 1
    put_cl(0)                      # the single dist code: len 0
    # Payload: 'A' 'A' EOB with litlen codes {65: 0, 256: 1}.
    put_code(0, 1); put_code(0, 1); put_code(1, 1)

    raw = bytearray()
    for i in range(0, len(bits), 8):
        raw.append(sum(b << k for k, b in enumerate(bits[i: i + 8])))
    raw = bytes(raw)
    assert zlib.decompress(raw, -15) == b"AA"  # the stream really is valid

    out = inflate_blocks_device(
        np.frombuffer(raw, dtype=np.uint8),
        np.array([0], dtype=np.int64),
        np.array([len(raw)], dtype=np.int64),
        np.array([2], dtype=np.int64),
    )
    assert out.tobytes() == b"AA"


def test_tokenizer_rejects_truncated_stream():
    comp = np.frombuffer(_deflate(b"hello world" * 50), dtype=np.uint8)
    with pytest.raises(IOError):
        inflate_blocks_device(
            comp[: len(comp) // 2],
            np.array([0], dtype=np.int64),
            np.array([len(comp) // 2], dtype=np.int64),
            np.array([550], dtype=np.int64),
        )


def test_size_mismatch_raises():
    comp = np.frombuffer(_deflate(b"hello world" * 50), dtype=np.uint8)
    with pytest.raises(IOError):
        inflate_blocks_device(
            comp,
            np.array([0], dtype=np.int64),
            np.array([len(comp)], dtype=np.int64),
            np.array([549], dtype=np.int64),  # footer lies about the size
        )


def test_tokenize_shapes():
    data = b"shape check " * 32
    comp = np.frombuffer(_deflate(data), dtype=np.uint8)
    lit, dist, out_lens = tokenize_deflate_native(
        comp,
        np.array([0], dtype=np.int64),
        np.array([len(comp)], dtype=np.int64),
        stride=STRIDE,
    )
    assert lit.shape == (1, STRIDE) and dist.shape == (1, STRIDE)
    assert dist.dtype == np.uint16  # 3 wire bytes per output byte total
    assert out_lens[0] == len(data)
    # Padded tail must be dist=0 identities.
    assert not dist[0, len(data):].any()
    # The repeated motif must actually produce back-references (dist>0)
    # whose implied parents point strictly backwards.
    used = dist[0, : len(data)].astype(np.int64)
    assert used.max() > 0
    idx = np.arange(len(data), dtype=np.int64)
    assert ((idx - used) >= 0).all()


def test_pipeline_device_copy_matches_host(bam2):
    from spark_bam_tpu.tpu.inflate import InflatePipeline

    host = flatten_file(bam2)
    views = list(InflatePipeline(bam2, window_uncompressed=256 << 10,
                                 device_copy=True))
    assert len(views) > 1  # multiple windows actually exercised
    got = np.concatenate([v.data for v in views])
    assert np.array_equal(got, host.data)
    assert views[-1].at_eof


def test_whole_bam_matches_host_inflate(bam2):
    host = flatten_file(bam2)
    dev = inflate_file_device(bam2)
    assert dev is not None
    assert np.array_equal(dev.data, host.data)
    assert np.array_equal(dev.block_starts, host.block_starts)
    assert np.array_equal(dev.block_flat, host.block_flat)
    assert dev.at_eof


def test_resolve_early_exit_rounds():
    """The early-exit resolve reports rounds-to-convergence: a literal-only
    batch costs exactly one gather (the convergence test itself), a
    block-spanning distance-1 run needs the full log2(64 Ki) doubling."""
    from spark_bam_tpu.tpu.inflate import _DOUBLING_ROUNDS, resolve_lz77

    data = b"a" * (STRIDE - 1)
    comp = np.frombuffer(_deflate(data), dtype=np.uint8)
    lit, dist, _ = tokenize_deflate_native(
        comp, np.array([0], dtype=np.int64),
        np.array([len(comp)], dtype=np.int64), stride=STRIDE,
    )
    deep, rounds_deep = resolve_lz77(lit, dist)
    assert bytes(np.asarray(deep)[0, : len(data)]) == data
    assert int(rounds_deep) == _DOUBLING_ROUNDS == 16

    lits_only, rounds_lit = resolve_lz77(lit, np.zeros_like(dist))
    assert np.array_equal(np.asarray(lits_only), np.asarray(lit))
    assert int(rounds_lit) == 1


def test_pack_unpack_roundtrip():
    """The packed single-buffer H2D layout must resolve identically to the
    two-array path (and the u16 dist plane must survive the bitcast)."""
    from spark_bam_tpu.tpu.inflate import (
        _resolve_packed, pack_tokens, resolve_lz77,
    )

    rng = np.random.default_rng(7)
    datas = [b"ab" * 20_000, rng.integers(0, 256, 5_000, dtype=np.uint8).tobytes()]
    comps = [np.frombuffer(_deflate(d), dtype=np.uint8) for d in datas]
    offsets = np.zeros(len(comps), dtype=np.int64)
    lengths = np.array([len(c) for c in comps], dtype=np.int64)
    np.cumsum(lengths[:-1], out=offsets[1:])
    lit, dist, _ = tokenize_deflate_native(
        np.concatenate(comps), offsets, lengths, stride=STRIDE,
    )
    want, rounds_a = resolve_lz77(lit, dist)
    got, rounds_b = _resolve_packed(pack_tokens(lit, dist))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert int(rounds_a) == int(rounds_b)


def test_pallas_lz77_parity():
    """The fused Pallas kernel (interpret mode on this backend) must agree
    with the XLA resolve bit-for-bit, early exit included."""
    import jax.numpy as jnp

    from spark_bam_tpu.tpu.inflate import resolve_lz77
    from spark_bam_tpu.tpu.pallas_kernels import lz77_resolve_pallas

    rng = np.random.default_rng(8)
    datas = [
        b"a" * (STRIDE - 1),             # max-depth distance-1 chain
        b"xy" * 10_000,                  # distance-2 overlaps
        rng.integers(0, 256, 30_000, dtype=np.uint8).tobytes(),
        b"hello world " * 400,
    ]
    comps = [np.frombuffer(_deflate(d), dtype=np.uint8) for d in datas]
    offsets = np.zeros(len(comps), dtype=np.int64)
    lengths = np.array([len(c) for c in comps], dtype=np.int64)
    np.cumsum(lengths[:-1], out=offsets[1:])
    lit, dist, _ = tokenize_deflate_native(
        np.concatenate(comps), offsets, lengths, stride=STRIDE,
    )
    want, rounds_xla = resolve_lz77(lit, dist)
    got, rounds_pl = lz77_resolve_pallas(
        jnp.asarray(lit), jnp.asarray(dist), interpret=True
    )
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert int(rounds_pl) == int(rounds_xla)


@pytest.mark.parametrize("distance", [1, 2, 3, 7])
def test_overlapping_copy_distances(distance):
    """Overlapping copies at tiny distances (copy source overlaps its own
    destination — the serial-inflate special case) across a near-block-
    sized run."""
    motif = bytes(range(65, 65 + distance))
    reps = (STRIDE - 1) // distance
    _roundtrip_one(motif * reps)


def test_zero_length_final_block():
    """A batch whose FINAL block inflates to zero bytes (BGZF writers emit
    empty blocks mid-stream and the EOF sentinel is one): the zero-length
    row must occupy no output range."""
    datas = [b"payload " * 512, b"tail", b""]
    comps = [np.frombuffer(_deflate(d), dtype=np.uint8) for d in datas]
    offsets = np.zeros(len(comps), dtype=np.int64)
    lengths = np.array([len(c) for c in comps], dtype=np.int64)
    np.cumsum(lengths[:-1], out=offsets[1:])
    out = inflate_blocks_device(
        np.concatenate(comps), offsets, lengths,
        np.array([len(d) for d in datas], dtype=np.int64),
    )
    assert out.tobytes() == b"".join(datas)


def test_fuzz_mutant_corpus_never_wrong_bytes():
    """fuzz-decode's structure-aware mutator over compressed payloads:
    whatever a mutant does, the device inflate must return bytes identical
    to host zlib's decode or raise cleanly — NEVER wrong bytes. (The
    out_lengths footer is the original's, so mutants that change the
    decoded size must be rejected by the size check.)"""
    from spark_bam_tpu.tools.fuzz_decode import _Rng, _mutate

    rng = np.random.default_rng(9)
    bases = [
        b"the quick brown fox " * 200,
        rng.integers(0, 256, 8_000, dtype=np.uint8).tobytes(),
        b"z" * 50_000,
    ]
    checked = 0
    agreed = 0
    for bi, data in enumerate(bases):
        comp = _deflate(data)
        for i in range(60):
            r = _Rng(1000 * bi + i)
            mutant = _mutate(comp, r.below(len(comp)), r)
            try:
                host = zlib.decompress(mutant, -15)
            except zlib.error:
                host = None
            try:
                out = inflate_blocks_device(
                    np.frombuffer(mutant, dtype=np.uint8),
                    np.array([0], dtype=np.int64),
                    np.array([len(mutant)], dtype=np.int64),
                    np.array([len(data)], dtype=np.int64),
                )
            except (IOError, ValueError):
                out = "rejected"
            checked += 1
            if isinstance(out, np.ndarray):
                # Device accepted: zlib must agree byte-for-byte.
                assert host is not None and out.tobytes() == host, (
                    f"device inflate returned wrong bytes for mutant "
                    f"base={bi} i={i}"
                )
                agreed += 1
    assert checked == 180
    assert agreed > 0  # identity/benign mutants must flow through


def test_count_reads_with_device_inflate_config(bam1):
    """spark.bam.device.inflate=true must flow through the config surface
    into the streaming pipeline and still count exactly."""
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.load.tpu_load import count_reads_tpu

    cfg = Config.from_dict({"spark.bam.device.inflate": True})
    assert cfg.device_inflate is True
    assert count_reads_tpu(bam1, cfg) == 4917


def test_device_inflate_auto_resolution():
    """Default is auto (None), which is host inflate on every backend; an
    explicit setting is what it says, for host-only consumers too."""
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.tpu.inflate import resolve_device_inflate

    cfg = Config()
    assert cfg.device_inflate is None
    assert resolve_device_inflate(cfg) is False
    assert resolve_device_inflate(Config(device_inflate=True)) is True
    assert resolve_device_inflate(Config(device_inflate=False)) is False
    assert Config.from_dict({"spark.bam.device.inflate": "auto"}).device_inflate is None
    assert Config.from_dict({"spark.bam.device.inflate": "false"}).device_inflate is False
