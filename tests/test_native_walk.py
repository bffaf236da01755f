"""The whole-file member walk, ``bgzf.stream.scan_metadata``: the native
walk (``sbt_walk_members``) against ``list(MetadataStream(ch))``, the loop
it replaces at the head of every pass. Every answer the Python walk gives,
a table, an exception with its text or a silent stop, the helper gives."""

import contextlib
import random
import struct
import zlib

import pytest

from spark_bam_tpu import obs
from spark_bam_tpu.bgzf import stream
from spark_bam_tpu.bgzf.flat import flatten_file
from spark_bam_tpu.bgzf.header import HeaderParseException
from spark_bam_tpu.bgzf.index_blocks import blocks_metadata
from spark_bam_tpu.bgzf.stream import MetadataStream, scan_metadata
from spark_bam_tpu.core import channel
from spark_bam_tpu.core.channel import ByteChannel, open_channel
from spark_bam_tpu.core.faults import chaos
from spark_bam_tpu.core.guard import StructurallyInvalid
from spark_bam_tpu.native.build import load_native

from bam_factories import random_bam

needs_native = pytest.mark.skipif(
    load_native() is None, reason="no native library on this machine"
)


def member(payload: bytes, before: bytes = b"", after: bytes = b"",
           isize: int | None = None) -> bytes:
    """One BGZF member around ``payload``, with whole extra subfields
    ``before`` / ``after`` the BC subfield inside ``XLEN``."""
    deflate = zlib.compressobj(6, zlib.DEFLATED, -15)
    data = deflate.compress(payload) + deflate.flush()
    xlen = 6 + len(before) + len(after)
    size = 12 + xlen + len(data) + 8
    return (
        b"\x1f\x8b\x08\x04" + bytes(6) + struct.pack("<H", xlen)
        + before + b"BC\x02\x00" + struct.pack("<H", size - 1) + after
        + data
        + struct.pack("<II", zlib.crc32(payload),
                      len(payload) if isize is None else isize)
    )


EOF_SENTINEL = member(b"")
SUBFIELD = b"XY\x02\x00ab"
MEMBERS = [
    member(random.Random(i).randbytes(500 + 300 * i)) for i in range(5)]


def patched(blob: bytes, at: int, value: bytes) -> bytes:
    return blob[:at] + value + blob[at + len(value):]


def at_member(k: int) -> int:
    return sum(len(m) for m in MEMBERS[:k])


BODY = b"".join(MEMBERS)

FILES = {
    "plain": BODY + EOF_SENTINEL,
    "no_sentinel": BODY,
    "sentinel_in_the_middle":
        b"".join(MEMBERS[:2]) + EOF_SENTINEL + b"".join(MEMBERS[2:]),
    "only_sentinel": EOF_SENTINEL,
    "empty": b"",
    "subfield_after_bc":
        MEMBERS[0] + member(b"q" * 900, after=SUBFIELD) + MEMBERS[1]
        + EOF_SENTINEL,
    "subfield_before_bc":
        MEMBERS[0] + member(b"q" * 900, before=SUBFIELD) + MEMBERS[1],
    "empty_payload_long_header":
        MEMBERS[0] + member(b"", after=SUBFIELD) + MEMBERS[1] + EOF_SENTINEL,
    "cut_in_header": BODY + MEMBERS[0][:11],
    "cut_after_header": BODY + MEMBERS[0][:18],
    "cut_in_payload": BODY + MEMBERS[0][:200],
    "cut_in_footer": BODY + MEMBERS[0][:-2],
    "cut_in_sentinel": BODY + EOF_SENTINEL[:-1],
    "short_tail_of_garbage": BODY + b"\x00" * 7,
    "bad_magic_first_member": patched(BODY, 0, b"@"),
    "bad_magic_member_3": patched(BODY + EOF_SENTINEL, at_member(3), b"\x00"),
    "bad_flag_member_2": patched(BODY, at_member(2) + 3, b"\x00"),
    "bad_bc_member_1": patched(BODY, at_member(1) + 13, b"D"),
    "bad_subfield_length_member_4": patched(BODY, at_member(4) + 14, b"\x03"),
    "xlen_too_short_member_2":
        patched(BODY, at_member(2) + 10, struct.pack("<H", 5)),
    "bsize_too_small_member_1":
        patched(BODY, at_member(1) + 16, struct.pack("<H", 24)),
    "bsize_less_than_long_header":
        MEMBERS[0] + patched(
            member(b"q" * 90, after=SUBFIELD), 16, struct.pack("<H", 30)),
    "isize_reads_negative":
        MEMBERS[0] + member(b"z" * 40, isize=0xFFFFFFFF) + MEMBERS[1],
    "isize_larger_than_a_block":
        MEMBERS[0] + member(b"z" * 40, isize=70000) + EOF_SENTINEL,
}


def outcome(walk, path):
    """What a walk over ``path`` gives: its table and where it left the
    channel, or the exception's type and text."""
    with open_channel(path) as ch:
        try:
            metas = walk(ch)
        except (EOFError, StructurallyInvalid) as e:
            return type(e), str(e)
        return metas, ch.position()


def python_walk(ch):
    return list(MetadataStream(ch))


@pytest.fixture
def registry():
    obs.shutdown()
    reg = obs.configure()
    try:
        yield reg
    finally:
        obs.shutdown()


def counters(reg) -> dict:
    return {c["name"]: c["value"] for c in reg.snapshot()["counters"]}


def traced_walk(reg, path, walk=scan_metadata):
    """``walk`` under the span its callers open: its outcome, the two
    counters, and what the span was told."""
    with obs.span("bgzf.read", kind="metadata_scan", path=str(path)):
        got = outcome(walk, path)
    c = counters(reg)
    (event,) = [e for e in reg.events() if e["name"] == "bgzf.read"]
    return (got, c.get("bgzf.blocks_scanned", 0),
            c.get("bgzf.blocks_scanned_native", 0),
            event["attrs"].get("walk"))


@pytest.fixture
def generated(tmp_path):
    path = tmp_path / "generated.bam"
    random_bam(path, seed=52, n_records=(300, 301), block_payload=(900, 5000))
    return path


@needs_native
@pytest.mark.parametrize("name", FILES)
def test_native_walk_is_the_python_walk(tmp_path, name):
    path = tmp_path / f"{name}.bgzf"
    path.write_bytes(FILES[name])
    assert outcome(scan_metadata, path) == outcome(python_walk, path)


@needs_native
@pytest.mark.parametrize("seed", range(6))
def test_mutated_files_walk_alike(tmp_path, seed):
    """Bytes of the headers and footers overwritten at random, the file cut
    at random: whatever the Python walk makes of each, the helper makes."""
    rng = random.Random(seed)
    clean = member(b"q" * 900, after=SUBFIELD) + BODY + EOF_SENTINEL
    starts = [0]
    path = tmp_path / "mutated.bgzf"
    with open_channel(_write(path, clean)) as ch:
        starts += [m.start + m.compressed_size for m in MetadataStream(ch)]
    for _ in range(120):
        blob = bytearray(clean)
        for _ in range(rng.randint(1, 3)):
            at = rng.choice(starts) + rng.randint(-8, 19)
            if 0 <= at < len(blob):
                blob[at] = rng.choice((0, 1, 2, 6, 31, 66, 255, rng.randrange(256)))
        if rng.random() < 0.3:
            del blob[rng.randrange(len(blob)):]
        _write(path, bytes(blob))
        assert outcome(scan_metadata, path) == outcome(python_walk, path)


def _write(path, blob: bytes):
    path.write_bytes(blob)
    return path


@needs_native
def test_what_the_python_walk_does_with_each_file(tmp_path):
    """The cases above compare two walks; this holds the reference to what
    each file was built to provoke."""
    want = {
        "plain": 5, "no_sentinel": 5, "sentinel_in_the_middle": 2,
        "only_sentinel": 0, "empty": 0, "subfield_after_bc": 3,
        "empty_payload_long_header": 1,  # any empty member is the sentinel
        "subfield_before_bc": "Position 12: 88 != 66",
        "cut_in_header": 5, "cut_after_header": EOFError,
        "cut_in_payload": EOFError, "cut_in_footer": EOFError,
        "cut_in_sentinel": EOFError, "short_tail_of_garbage": 5,
        "bad_magic_first_member": "Position 0: 64 != 31",
        "bad_magic_member_3": "Position 0: 0 != 31",
        "bad_flag_member_2": "Position 3: 0 != 4",
        "bad_bc_member_1": "Position 13: 68 != 67",
        "bad_subfield_length_member_4": "Position 14: 3 != 2",
        "xlen_too_short_member_2": "BGZF XLEN 5 < 6: no BC subfield",
        "bsize_too_small_member_1": "BGZF BSIZE 24 too small",
        "bsize_less_than_long_header": "BGZF BSIZE 30 too small",
        "isize_reads_negative": 3, "isize_larger_than_a_block": 2,
    }
    assert set(want) == set(FILES)
    for name, expected in want.items():
        path = tmp_path / f"{name}.bgzf"
        path.write_bytes(FILES[name])
        got = outcome(scan_metadata, path)
        if isinstance(expected, int):
            assert len(got[0]) == expected, name
        elif expected is EOFError:
            assert got[0] is EOFError, name
        else:
            assert expected in got[1], name
            if expected.startswith("Position"):
                assert got[0] is HeaderParseException, name
    metas, _ = outcome(scan_metadata, tmp_path / "isize_reads_negative.bgzf")
    assert metas[1].uncompressed_size == -1


@needs_native
@pytest.mark.parametrize("fixture", ["generated", "bam1", "bam2", "bam5k"])
def test_native_walk_on_bams(request, registry, fixture):
    path = request.getfixturevalue(fixture)
    want = outcome(python_walk, path)
    n = len(want[0])
    got, scanned, native, walk = traced_walk(registry, path)
    assert got == want and n > 3
    assert (scanned, native, walk) == (2 * n, n, "native")


@needs_native
@pytest.mark.parametrize("room", [1, 2, 3, 7])
def test_arrays_smaller_than_the_file_resume_the_call(
        monkeypatch, generated, room):
    want = outcome(python_walk, generated)
    calls = []
    one_call = stream.walk_members_native

    def counted(lib, data, start, capacity):
        calls.append(capacity)
        return one_call(lib, data, start, capacity)

    monkeypatch.setattr(stream, "WALK_CHUNK", room)
    monkeypatch.setattr(stream, "walk_members_native", counted)
    assert outcome(scan_metadata, generated) == want
    # The last call finds the sentinel; a table that fills the arrays
    # exactly takes one more to learn it.
    assert len(calls) == len(want[0]) // room + 1
    assert max(calls) == calls[0] == room


@needs_native
def test_a_rejected_member_after_a_resumed_call(monkeypatch, tmp_path):
    path = tmp_path / "bad.bgzf"
    path.write_bytes(FILES["bad_magic_member_3"])
    monkeypatch.setattr(stream, "WALK_CHUNK", 2)
    assert outcome(scan_metadata, path) == outcome(python_walk, path)


class BytesChannel(ByteChannel):
    """A channel that is not the local mapping: what a remote scheme's
    factory hands out."""

    def __init__(self, data: bytes):
        super().__init__()
        self.data = data

    def _read_at(self, pos: int, n: int) -> bytes:
        return self.data[pos: pos + n]

    @property
    def size(self) -> int:
        return len(self.data)


class MappedElsewhere(channel.MMapChannel):
    """A subclass may read its bytes some other way: not the plain mapping."""


@contextlib.contextmanager
def no_library(monkeypatch, path):
    monkeypatch.setattr(stream, "load_native", lambda: None)
    yield path


@contextlib.contextmanager
def chaos_wrapped(monkeypatch, path):
    with chaos("7:latency=0.0"):
        yield path


@contextlib.contextmanager
def remote_scheme(monkeypatch, path):
    data = path.read_bytes()
    monkeypatch.setitem(
        channel._SCHEMES, "walktest", lambda url: BytesChannel(data))
    yield "walktest://bucket/generated.bam"


@contextlib.contextmanager
def subclass(monkeypatch, path):
    monkeypatch.setattr(channel, "MMapChannel", MappedElsewhere)
    yield path


@pytest.mark.parametrize(
    "setup", [no_library, chaos_wrapped, remote_scheme, subclass],
    ids=lambda f: f.__name__)
def test_anything_but_the_plain_local_mapping_walks_in_python(
        monkeypatch, registry, generated, setup):
    want = outcome(python_walk, generated)
    n = len(want[0])
    with setup(monkeypatch, generated) as path:
        got, scanned, native, walk = traced_walk(registry, path)
    assert got == want
    assert (scanned, native, walk) == (2 * n, 0, "python")


def test_a_cached_channel_walks_in_python(registry, generated):
    want = outcome(python_walk, generated)
    n = len(want[0])
    with open_channel(generated, cached=True) as ch, obs.span("bgzf.read"):
        assert scan_metadata(ch) == want[0]
    c = counters(registry)
    assert c["bgzf.blocks_scanned"] == 2 * n
    assert "bgzf.blocks_scanned_native" not in c


@needs_native
@pytest.mark.parametrize("name, native, walk", [
    ("plain", 5, "native"),
    ("no_sentinel", 5, "native"),
    ("sentinel_in_the_middle", 2, "native"),
    ("cut_in_header", 5, "python"),
    ("bad_magic_member_3", 3, "python"),
    ("bad_magic_first_member", 0, "python"),
])
def test_counters_and_span_say_who_walked(
        tmp_path, registry, name, native, walk):
    path = tmp_path / f"{name}.bgzf"
    path.write_bytes(FILES[name])
    _, scanned, scanned_native, told = traced_walk(registry, path)
    assert (scanned, scanned_native, told) == (native, native, walk)


@needs_native
def test_the_counter_is_added_once_a_walk(monkeypatch, registry, generated):
    added = []
    count = obs.count
    monkeypatch.setattr(
        stream.obs, "count", lambda name, n=1: (added.append((name, n)),
                                                count(name, n)))
    with open_channel(generated) as ch:
        n = len(scan_metadata(ch))
    assert added == [
        ("bgzf.blocks_scanned", n), ("bgzf.blocks_scanned_native", n)]


@needs_native
def test_the_walk_outside_a_span_and_without_a_registry(generated):
    assert not obs.enabled()
    assert outcome(scan_metadata, generated) == outcome(python_walk, generated)


@needs_native
@pytest.mark.parametrize("front_end", ["blocks_metadata", "flatten_file"])
def test_the_front_ends_walk_natively(registry, generated, front_end):
    want, _ = outcome(python_walk, generated)
    if front_end == "blocks_metadata":
        with obs.span("bgzf.read", kind="metadata_scan"):
            assert list(blocks_metadata(generated)) == want
    else:
        view = flatten_file(generated)
        assert view.block_starts.tolist() == [m.start for m in want]
    c = counters(registry)
    n = len(want)
    assert c["bgzf.blocks_scanned"] == 2 * n
    assert c["bgzf.blocks_scanned_native"] == n
    (event,) = [e for e in registry.events() if e["name"] == "bgzf.read"]
    assert event["attrs"]["walk"] == "native"
