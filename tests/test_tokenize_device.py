"""Device-resident DEFLATE tokenization: the in-kernel bit-reader
(tpu/tokenize_device.py + the Pallas form) differentially tested against
the native host tokenizer and zlib — the permanent correctness oracles.

The contract under test (docs/design.md "Device-resident tokenization"):
byte-identical to the host entropy phase on every stream both accept, and
NEVER wrong bytes on a stream only one side takes — the device may only
reject (demote), not disagree. Plus the donation-flatness regression the
window ring relies on, the ``Config.inflate`` spec surface, and the
demote-to-host-zlib parity path.
"""

import zlib
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_bam_tpu.native.build import load_native, tokenize_deflate_native
from spark_bam_tpu.tpu.tokenize_device import STRIDE, tokenize_planes

pytestmark = pytest.mark.tokenize


def _deflate(data: bytes, level: int = 6,
             strategy: int = zlib.Z_DEFAULT_STRATEGY) -> bytes:
    co = zlib.compressobj(level, zlib.DEFLATED, -15, 8, strategy)
    return co.compress(data) + co.flush()


def _stage(comps: list[bytes], c_pad: int | None = None,
           b_pad: int | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The ``stage_run_payloads`` convention: pow2-padded rows with ≥ 8
    bytes of tail slack so the kernel's 4-byte bit reads stay in-row."""
    longest = max((len(c) for c in comps), default=0)
    if c_pad is None:
        c_pad = max(1 << max(longest + 8 - 1, 0).bit_length(), 1024)
    if b_pad is None:
        b_pad = max(1 << max(len(comps) - 1, 0).bit_length(), 1)
    staged = np.zeros((b_pad, c_pad), dtype=np.uint8)
    clens = np.zeros(b_pad, dtype=np.int32)
    for i, c in enumerate(comps):
        staged[i, : len(c)] = np.frombuffer(c, dtype=np.uint8)
        clens[i] = len(c)
    return jnp.asarray(staged), jnp.asarray(clens)


def _native_one(comp: bytes):
    """Host-oracle planes for one stream, or None when it rejects."""
    try:
        return tokenize_deflate_native(
            np.frombuffer(comp, dtype=np.uint8),
            np.array([0], dtype=np.int64),
            np.array([len(comp)], dtype=np.int64),
            stride=STRIDE,
        )
    except IOError:
        return None


def _zlib_one(comp: bytes) -> bytes | None:
    """zlib's verdict on one raw stream: decoded bytes, or None. Uses a
    decompressobj so trailing garbage after BFINAL (which the tokenizers
    ignore, like the BGZF framing does) is not itself a rejection."""
    d = zlib.decompressobj(-15)
    try:
        out = d.decompress(comp)
    except zlib.error:
        return None
    return out if d.eof else None


class _BitWriter:
    """LSB-first DEFLATE bit emitter for hand-built edge-case streams."""

    def __init__(self):
        self.bits: list[int] = []

    def put(self, value: int, n: int):           # LSB-first fields
        for i in range(n):
            self.bits.append((value >> i) & 1)

    def put_code(self, code: int, n: int):       # Huffman codes: MSB-first
        for i in reversed(range(n)):
            self.bits.append((code >> i) & 1)

    def align(self):
        while len(self.bits) % 8:
            self.bits.append(0)

    def bytes(self) -> bytes:
        self.align()
        out = bytearray()
        for i in range(0, len(self.bits), 8):
            v = 0
            for j, b in enumerate(self.bits[i: i + 8]):
                v |= b << j
            out.append(v)
        return bytes(out)


def _fixed_lit_code(sym: int) -> tuple[int, int]:
    """RFC 1951 §3.2.6 fixed litlen code for ``sym`` → (code, nbits)."""
    if sym < 144:
        return 0x30 + sym, 8
    if sym < 256:
        return 0x190 + (sym - 144), 9
    if sym < 280:
        return sym - 256, 7
    return 0xC0 + (sym - 280), 8


# ------------------------------------------------------- plane parity


@pytest.mark.skipif(load_native() is None,
                    reason="native runtime unavailable")
def test_planes_match_native_tokenizer():
    """All three block types, all strategies: the device bit-reader must
    emit the native tokenizer's planes bit-for-bit (tails included)."""
    rng = np.random.default_rng(3)
    datas = [
        b"the quick brown fox " * 200,                       # fixed/dynamic
        rng.integers(0, 256, 8_000, dtype=np.uint8).tobytes(),  # stored-ish
        b"z" * 50_000,                                       # deep RLE
        b"tail",
        b"",                                                 # empty stream
    ]
    comps = [_deflate(d) for d in datas]
    comps.append(_deflate(datas[0], level=0))                # stored blocks
    comps.append(_deflate(datas[0], level=9, strategy=zlib.Z_FIXED))
    datas.append(datas[0])
    datas.append(datas[0])
    staged, clens = _stage(comps)
    lit, dist, olens, ok = map(np.asarray, tokenize_planes(staged, clens))
    for i, (d, c) in enumerate(zip(datas, comps)):
        n_lit, n_dist, n_olens = _native_one(c)
        assert bool(ok[i]) and int(olens[i]) == len(d) == int(n_olens[0])
        assert np.array_equal(lit[i], n_lit[0]), f"lit plane differs row {i}"
        assert np.array_equal(dist[i], n_dist[0]), f"dist plane differs row {i}"
    # Batch-pad rows (clen == 0) are vacuously rejected, never garbage.
    assert not ok[len(comps):].any() and not olens[len(comps):].any()


def test_dynamic_huffman_with_cl_runs():
    """A skewed alphabet at level 9 forces a dynamic-Huffman block whose
    code-length header uses the 16/17/18 run codes; the kernel's canonical
    rebuild + run expansion must reproduce the exact stream."""
    rng = np.random.default_rng(11)
    data = bytes(rng.choice([32, 101, 116, 97, 10, 200], size=20_000,
                            p=[.3, .25, .2, .15, .05, .05]).astype(np.uint8))
    comp = _deflate(data, level=9)
    assert (comp[0] >> 1) & 3 == 2  # first block really is dynamic
    staged, clens = _stage([comp])
    lit, dist, olens, ok = map(np.asarray, tokenize_planes(staged, clens))
    assert bool(ok[0]) and int(olens[0]) == len(data)
    from spark_bam_tpu.tpu.inflate import resolve_lz77

    resolved, _ = resolve_lz77(lit, dist)
    assert bytes(np.asarray(resolved)[0, : len(data)]) == data


@pytest.mark.parametrize("sym", [286, 287])
def test_invalid_litlen_symbols_rejected(sym):
    """286/287 have fixed-Huffman codes but are invalid litlen symbols
    (RFC 1951 §3.2.6) — the kernel must reject, exactly like zlib."""
    w = _BitWriter()
    w.put(1, 1)            # BFINAL
    w.put(1, 2)            # BTYPE = fixed
    w.put_code(*_fixed_lit_code(ord("A")))
    w.put_code(*_fixed_lit_code(sym))
    comp = w.bytes() + b"\x00" * 4
    assert _zlib_one(comp) is None
    staged, clens = _stage([comp])
    _, _, _, ok = tokenize_planes(staged, clens)
    assert not bool(np.asarray(ok)[0])


def test_distance_before_stream_rejected():
    """A match whose distance reaches before output position 0 is corrupt;
    accepting it would fabricate bytes."""
    w = _BitWriter()
    w.put(1, 1)
    w.put(1, 2)                          # fixed
    w.put_code(*_fixed_lit_code(ord("A")))
    w.put_code(*_fixed_lit_code(257))    # length 3
    w.put_code(3, 5)                     # dist sym 3 → distance 4 > pos 1
    w.put_code(*_fixed_lit_code(256))
    comp = w.bytes() + b"\x00" * 4
    assert _zlib_one(comp) is None
    staged, clens = _stage([comp])
    _, _, _, ok = tokenize_planes(staged, clens)
    assert not bool(np.asarray(ok)[0])


def test_zero_length_final_stored_block():
    """BGZF writers emit zero-length members and stored empty final
    blocks; a fixed block followed by an empty stored BFINAL block must
    tokenize with the stored block contributing nothing."""
    w = _BitWriter()
    w.put(0, 1)            # non-final
    w.put(1, 2)            # fixed
    for ch in b"abc":
        w.put_code(*_fixed_lit_code(ch))
    w.put_code(*_fixed_lit_code(256))
    w.put(1, 1)            # BFINAL
    w.put(0, 2)            # stored
    w.align()
    comp = w.bytes() + b"\x00\x00\xff\xff"      # LEN=0, NLEN=~0
    assert _zlib_one(comp) == b"abc"
    staged, clens = _stage([comp])
    lit, dist, olens, ok = map(np.asarray, tokenize_planes(staged, clens))
    assert bool(ok[0]) and int(olens[0]) == 3
    assert bytes(lit[0, :3]) == b"abc" and not dist[0].any()
    # The canonical empty stream (deflate of b"") is a zero-length final
    # block too — fixed-Huffman EOB only.
    staged, clens = _stage([_deflate(b"")])
    _, _, olens, ok = map(np.asarray, tokenize_planes(staged, clens))
    assert bool(ok[0]) and int(olens[0]) == 0


# ------------------------------------------------------- fuzz differential


def test_fuzz_differential_never_wrong_bytes():
    """fuzz-decode's structure-aware mutator over compressed payloads, the
    same 180-mutant corpus the host-path fuzz test walks: whatever a
    mutant does, the device tokenizer must either reject it or produce
    planes that resolve to zlib's exact bytes — NEVER wrong bytes. Where
    the native tokenizer also accepts, the planes must be identical."""
    from spark_bam_tpu.tools.fuzz_decode import _Rng, _mutate
    from spark_bam_tpu.tpu.inflate import resolve_lz77

    rng = np.random.default_rng(9)
    bases = [
        b"the quick brown fox " * 200,
        rng.integers(0, 256, 8_000, dtype=np.uint8).tobytes(),
        b"z" * 50_000,
    ]
    have_native = load_native() is not None
    checked = agreed = 0
    for bi, data in enumerate(bases):
        comp = _deflate(data)
        mutants = []
        for i in range(60):
            r = _Rng(1000 * bi + i)
            mutants.append(_mutate(comp, r.below(len(comp)), r))
        # One staged batch per base, padded to a SHARED shape so the jit
        # compiles once for the whole corpus.
        staged, clens = _stage(mutants, c_pad=16384, b_pad=64)
        lit, dist, olens, ok = tokenize_planes(staged, clens)
        resolved, _ = resolve_lz77(lit, dist)
        lit, dist, olens, ok, resolved = map(
            np.asarray, (lit, dist, olens, ok, resolved)
        )
        for i, mut in enumerate(mutants):
            checked += 1
            host = _zlib_one(mut)
            if not bool(ok[i]):
                continue                      # clean demote — always safe
            # Device accepted: zlib must agree byte-for-byte.
            assert host is not None and int(olens[i]) == len(host), (
                f"device tokenizer accepted a stream zlib rejects "
                f"(base={bi} i={i})"
            )
            assert bytes(resolved[i, : len(host)]) == host, (
                f"device tokenizer produced wrong bytes (base={bi} i={i})"
            )
            agreed += 1
            if have_native:
                nat = _native_one(mut)
                if nat is not None:
                    assert np.array_equal(lit[i], nat[0][0])
                    assert np.array_equal(dist[i], nat[1][0])
    assert checked == 180
    assert agreed > 0                         # benign mutants flow through


# ------------------------------------------------------- pallas parity


def test_pallas_interpret_parity():
    """The Pallas bit-reader (interpret mode on this backend) must agree
    with the XLA vmap form on planes, lengths, and verdicts."""
    from spark_bam_tpu.tpu.pallas_kernels import tokenize_pallas

    comps = [
        _deflate(b"abcabcabc repeat " * 4),
        _deflate(b""),
        _deflate(b"q" * 300),
        b"\x07" + b"\x00" * 8,               # garbage: must reject in both
    ]
    staged, clens = _stage(comps)
    want = [np.asarray(a) for a in tokenize_planes(staged, clens)]
    got = [np.asarray(a) for a in tokenize_pallas(staged, clens,
                                                  interpret=True)]
    for w, g, name in zip(want, got, ("lit", "dist", "olens", "ok")):
        assert np.array_equal(w, g), f"pallas {name} differs"


# ------------------------------------------------------- config surface


def test_inflate_config_parse():
    from spark_bam_tpu.core.inflate_config import InflateConfig

    cfg = InflateConfig.parse("")
    assert (cfg.tokenize, cfg.kernel, cfg.donate) == ("auto", "auto", "on")
    assert InflateConfig.parse("device").tokenize == "device"     # bare token
    assert InflateConfig.parse("host").tokenize == "host"
    full = InflateConfig.parse("tokenize=device,kernel=pallas,donate=off")
    assert full.tokenize == "device" and full.kernel == "pallas"
    assert not full.donate_enabled
    assert InflateConfig.parse("") is InflateConfig.parse("")     # lru cache
    # auto is host on every backend (measured); an explicit pin wins.
    assert InflateConfig.parse("").resolve_tokenize() == "host"
    assert full.resolve_tokenize() == "device"                    # pinned
    with pytest.raises(ValueError):
        InflateConfig.parse("tokenize=maybe")
    with pytest.raises(ValueError):
        InflateConfig.parse("bogus_knob=1")


# ------------------------------------------------------- pipeline seams


@pytest.fixture
def synth_path(tmp_path) -> Path:
    from spark_bam_tpu.benchmarks.synth import synth_bam

    path = tmp_path / "synth.bam"
    synth_bam(path, 96 << 10)
    return path


@pytest.fixture
def reg():
    from spark_bam_tpu import obs

    obs.shutdown()
    r = obs.configure()
    yield r
    obs.shutdown()


def _pipeline_bytes(path, **kw) -> np.ndarray:
    from spark_bam_tpu.tpu.inflate import InflatePipeline

    views = list(InflatePipeline(path, window_uncompressed=32 << 10,
                                 device_copy=True, **kw))
    assert views[-1].at_eof
    return np.concatenate([v.data for v in views])


def test_pipeline_device_tokenize_matches_host(synth_path, reg):
    """End-to-end: raw payloads H2D, in-kernel tokenize, donated resolve —
    byte-identical to the host zlib flatten, with the re-scoped
    attribution series populated."""
    from spark_bam_tpu import obs
    from spark_bam_tpu.bgzf.flat import flatten_file

    host = flatten_file(synth_path)
    got = _pipeline_bytes(synth_path,
                          inflate_spec="tokenize=device,kernel=xla")
    assert np.array_equal(got, host.data)
    assert obs.counter("inflate.tokenize_blocks").value > 0
    assert obs.counter("inflate.tokenize_demotions").value == 0


def test_demote_parity_on_kernel_reject(synth_path, reg, monkeypatch):
    """A kernel that disavows every row (ok=False) must demote cleanly to
    host zlib at the materialize sync — bytes still exact, demotions
    counted. The never-wrong-bytes contract's last line of defense."""
    from spark_bam_tpu import obs
    from spark_bam_tpu.bgzf.flat import flatten_file
    from spark_bam_tpu.tpu import tokenize_device

    def reject_all(staged, clens):
        b = staged.shape[0]
        return (jnp.zeros((b, STRIDE), jnp.uint8),
                jnp.zeros((b, STRIDE), jnp.uint16),
                jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.bool_))

    monkeypatch.setattr(tokenize_device, "tokenize_planes", reject_all)
    host = flatten_file(synth_path)
    got = _pipeline_bytes(synth_path,
                          inflate_spec="tokenize=device,kernel=xla")
    assert np.array_equal(got, host.data)
    assert obs.counter("inflate.tokenize_demotions").value > 0


def test_kernel_raise_propagates(synth_path, monkeypatch):
    """A kernel that throws (a compiler refusal stand-in) is NOT an input
    error: the pipeline raises it instead of hiding the device behind
    host zlib. Only rows the kernel disavows demote (the test above)."""
    from spark_bam_tpu.tpu import tokenize_device

    def boom(staged, clens):
        raise RuntimeError("mosaic said no")

    monkeypatch.setattr(tokenize_device, "tokenize_planes", boom)
    with pytest.raises(RuntimeError, match="mosaic said no"):
        _pipeline_bytes(synth_path,
                        inflate_spec="tokenize=device,kernel=xla")


def test_donation_keeps_steady_state_allocations_flat(tmp_path):
    """The donated window ring's regression assert (ISSUE tentpole #2):
    with ``donate=on`` the resolve reuses the lit plane's buffer, so live
    device allocations must be FLAT across ≥ 8 steady-state windows — any
    upward drift means donation silently stopped aliasing."""
    from spark_bam_tpu.benchmarks.synth import synth_bam
    from spark_bam_tpu.bgzf.index_blocks import blocks_metadata
    from spark_bam_tpu.core.channel import open_channel
    from spark_bam_tpu.tpu.inflate import dispatch_group_device, window_plan

    path = tmp_path / "ring.bam"
    synth_bam(path, 192 << 10)
    groups = window_plan(list(blocks_metadata(path)), 16 << 10)
    assert len(groups) >= 8, "need ≥ 8 windows to see the steady state"
    counts = []
    datas = []
    # Drive the dispatch → materialize cycle synchronously (no producer
    # thread racing the measurement) — the live-array census after each
    # materialize IS the window ring's footprint.
    with open_channel(path) as ch:
        for g in groups:
            view = dispatch_group_device(
                ch, g, inflate_spec="tokenize=device,kernel=xla"
            ).materialize()
            datas.append(np.asarray(view.data).copy())
            counts.append(len(jax.live_arrays()))
    steady = counts[2:]        # first windows pay compile-cache warmup
    assert max(steady) - min(steady) == 0, (
        f"device allocations drift across windows: {counts}"
    )
    from spark_bam_tpu.bgzf.flat import flatten_file

    host = flatten_file(path)
    assert np.array_equal(np.concatenate(datas), host.data)


@pytest.mark.slow
def test_fused_raw_count_matches_host(tmp_path):
    """The fused count kernel fed raw payloads (count_window_raw) must
    agree with the classic host-tokenize count exactly."""
    from spark_bam_tpu.benchmarks.synth import synth_bam
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.tpu.stream_check import StreamChecker

    path = tmp_path / "count.bam"
    synth_bam(path, 128 << 10)
    host = StreamChecker(
        path, Config(), window_uncompressed=64 << 10
    ).count_reads()
    dev = StreamChecker(
        path,
        Config(device_inflate=True, inflate="tokenize=device,kernel=xla"),
        window_uncompressed=64 << 10,
    ).count_reads()
    assert dev == host
