"""BGZF block streams and uncompressed-byte views.

Host-side equivalents of the reference's block layer
(bgzf/.../block/{Stream,MetadataStream,UncompressedBytes,PosIterator}.scala):

- ``BlockStream``            — iterate decompressed ``Block``s (zlib raw-deflate)
- ``SeekableBlockStream``    — adds ``seek`` + an LRU cache of 100 blocks
- ``MetadataStream``         — iterate ``Metadata`` without decompressing
- ``scan_metadata``          — the whole walk at once, in the native library
  where the channel is the plain local mapping
- ``UncompressedBytes``      — linear byte-channel view over the blocks
- ``SeekableUncompressedBytes`` — virtual-position addressable variant
- ``pos_iterator``           — all candidate ``Pos`` of a block

The TPU hot path does not use these per-byte views; it inflates whole windows
of blocks into flat buffers (``spark_bam_tpu.tpu.inflate``). These streams
serve header parsing, indexing, oracles and golden tests.
"""

from __future__ import annotations

import zlib
from collections import OrderedDict
from typing import Iterator, Optional

from spark_bam_tpu import obs
from spark_bam_tpu.bgzf.block import Block, Metadata, FOOTER_SIZE, check_isize
from spark_bam_tpu.bgzf.header import EXPECTED_HEADER_SIZE, Header
from spark_bam_tpu.core import guard
from spark_bam_tpu.core.channel import ByteChannel, MMapChannel
from spark_bam_tpu.core.faults import (
    BlockCorruptionError,
    BlockGapError,
    ShortReadError,
)
from spark_bam_tpu.core.guard import MalformedInputError
from spark_bam_tpu.core.pos import Pos
from spark_bam_tpu.native.build import (
    WALK_FULL,
    WALK_REJECTED,
    load_native,
    walk_members_native,
)


def inflate_block_payload(comp: bytes | memoryview, uncompressed_size: int) -> bytes:
    """Raw-DEFLATE inflate of one block payload (reference Stream.scala:49-54)."""
    try:
        data = zlib.decompress(
            bytes(comp), wbits=-15, bufsize=max(uncompressed_size, 1)
        )
    except zlib.error as e:
        raise BlockCorruptionError(f"BGZF payload inflate failed: {e}") from e
    if len(data) != uncompressed_size:
        raise BlockCorruptionError(
            f"Expected {uncompressed_size} decompressed bytes, found {len(data)}"
        )
    return data


def read_block(ch: ByteChannel) -> Optional[Block]:
    """Read + inflate the block at the channel position; None at EOF sentinel/EOF.

    The ISIZE length check and CRC32 verification classify damaged payloads
    as ``BlockCorruptionError`` (unrecoverable — retrying re-reads the same
    bytes), distinct from the retryable transport-level errors.
    """
    start = ch.position()
    try:
        header = Header.read(ch)
    except EOFError:
        return None
    remaining = header.compressed_size - header.size
    payload = ch.read_fully(remaining)
    data_length = remaining - FOOTER_SIZE
    uncompressed_size = check_isize(
        int.from_bytes(payload[-4:], "little"), start
    )
    if data_length == 2:
        # 28-byte empty terminator block (reference Stream.scala:56-58)
        return None
    # Per-block span only when a registry is live (the stream path's
    # inflate unit of work is one ~64 KiB block); disabled runs pay one
    # None-check. Counters track read vs inflate volume either way.
    with obs.span("inflate.block", start=start):
        data = inflate_block_payload(payload[:data_length], uncompressed_size)
    crc = int.from_bytes(payload[data_length:data_length + 4], "little")
    if zlib.crc32(data) & 0xFFFFFFFF != crc:
        raise BlockCorruptionError(
            f"BGZF block at {start}: CRC32 mismatch "
            f"(stored {crc:#010x}, computed {zlib.crc32(data) & 0xFFFFFFFF:#010x})"
        )
    obs.count("bgzf.blocks_read")
    obs.count("bgzf.bytes_read", header.compressed_size)
    obs.count("bgzf.bytes_inflated", uncompressed_size)
    return Block(data, start, header.compressed_size)


class BlockStream:
    """Iterator of decompressed Blocks from a channel (reference ``Stream``).

    ``tolerant=False`` (default, the historical semantics + anomaly
    classification): a genuinely truncated file still ends cleanly, but
    mid-file byte loss raises retryable ``ShortReadError`` and a damaged
    block raises ``BlockCorruptionError`` — no more silent truncation.

    ``tolerant=True`` (``FaultPolicy.mode=tolerant``): a damaged block is
    quarantined instead — the stream re-syncs to the next sound block
    header (``find_block_start``), records the gap in ``self.quarantined``,
    and raises ``BlockGapError`` once so the caller can account for the gap
    (the record layer re-finds a record boundary; a plain block consumer
    may simply continue iterating — the channel is already positioned at
    the resync point).
    """

    def __init__(self, ch: ByteChannel, tolerant: bool = False):
        self.ch = ch
        self.tolerant = tolerant
        self.quarantined: list[BlockGapError] = []
        self._head: Optional[Block] = None
        self._done = False

    def _advance(self) -> Optional[Block]:
        start = self.ch.position()
        try:
            return read_block(self.ch)
        except EOFError as e:
            if self.ch.position() >= self.ch.size:
                # The missing bytes never existed (truncated file): clean
                # stream end, the reference's tolerant-truncation shape.
                return None
            err = ShortReadError(
                f"mid-file EOF in block at {start} "
                f"(channel at {self.ch.position()} of {self.ch.size}): {e}"
            )
            if not self.tolerant:
                raise err from e
            self._resync(start, err)
        except (BlockCorruptionError, MalformedInputError) as e:
            # MalformedInputError covers HeaderParseException plus the
            # structural guards (bad XLEN/BSIZE/ISIZE, core/guard.py).
            if not self.tolerant:
                raise
            self._resync(start, e)

    def _resync(self, damaged_start: int, err: Exception) -> None:
        """Quarantine the damaged block: position the channel at the next
        sound block header and raise ``BlockGapError`` describing the gap."""
        from spark_bam_tpu.bgzf.find_block_start import find_block_start
        from spark_bam_tpu.bgzf.header import HeaderSearchFailedException

        try:
            resync = find_block_start(self.ch, damaged_start + 1)
        except (HeaderSearchFailedException, EOFError):
            resync = None
        self.ch.seek(resync if resync is not None else self.ch.size)
        gap = BlockGapError(
            damaged_start, resync, f"{type(err).__name__}: {err}"
        )
        self.quarantined.append(gap)
        obs.count("faults.quarantined_blocks")
        guard.note_quarantined_block()
        raise gap from err

    def head(self) -> Optional[Block]:
        if self._head is None and not self._done:
            self._head = self._advance()
            if self._head is None:
                self._done = True
        return self._head

    def __iter__(self) -> Iterator[Block]:
        return self

    def __next__(self) -> Block:
        blk = self.head()
        if blk is None:
            raise StopIteration
        self._head = None
        return blk

    def close(self) -> None:
        self.ch.close()


class SeekableBlockStream(BlockStream):
    """BlockStream + ``seek(block_pos)`` + LRU cache of decompressed blocks.

    Cache size 100 matches the reference (Stream.scala:83-92).
    """

    MAX_CACHE_SIZE = 100

    def __init__(self, ch: ByteChannel, tolerant: bool = False):
        super().__init__(ch, tolerant=tolerant)
        self._cache: OrderedDict[int, Block] = OrderedDict()

    def _advance(self) -> Optional[Block]:
        start = self.ch.position()
        blk = self._cache.get(start)
        if blk is not None:
            self._cache.move_to_end(start)
            self.ch.seek(start + blk.compressed_size)
            blk.idx = 0
            return blk
        blk = super()._advance()
        if blk is not None:
            self._cache[start] = blk
            if len(self._cache) > self.MAX_CACHE_SIZE:
                self._cache.popitem(last=False)
        return blk

    def seek(self, block_pos: int) -> None:
        head = self._head
        if head is not None and head.start == block_pos:
            head.idx = 0
            return
        self._head = None
        self._done = False
        self.ch.seek(block_pos)


class MetadataStream:
    """Iterate block Metadata without inflating (reference MetadataStream.scala)."""

    def __init__(self, ch: ByteChannel):
        self.ch = ch

    def __iter__(self) -> Iterator[Metadata]:
        while True:
            start = self.ch.position()
            try:
                header = Header.read(self.ch)
            except EOFError:
                return
            remaining = header.compressed_size - header.size
            self.ch.skip(remaining - 4)
            uncompressed_size = self.ch.read_i32()
            if remaining - FOOTER_SIZE == 2:
                return  # EOF sentinel block
            obs.count("bgzf.blocks_scanned")
            yield Metadata(start, header.compressed_size, uncompressed_size)

    def close(self) -> None:
        self.ch.close()


#: Members one native walk call has room for; a longer file resumes the call.
WALK_CHUNK = 1 << 16
#: The least a member can take: what bounds the members of a stretch of file.
_MIN_MEMBER = EXPECTED_HEADER_SIZE + FOOTER_SIZE


def scan_metadata(ch: ByteChannel) -> list[Metadata]:
    """``list(MetadataStream(ch))``: the walk over every member from the
    channel's position, the head of every whole-file pass.

    Over the plain local mapping (``MMapChannel`` itself: not a remote
    channel, a registered scheme's, a caching or a chaos wrapper) with the
    native library loaded, the walk is ``sbt_walk_members``, one call a
    ``WALK_CHUNK`` members, with the interpreter lock released. It accepts
    a member by the checks of ``Header.parse``; at one it does not accept
    the Python walk takes over, so what is raised (or the silent stop at a
    cut header) is ``MetadataStream``'s own. Anything else is
    ``MetadataStream`` from the start. ``bgzf.blocks_scanned_native`` over
    ``bgzf.blocks_scanned`` is the share of members walked natively; an
    open ``bgzf.read`` span is told ``walk="native"|"python"``.
    """
    metas: list[Metadata] = []
    lib = load_native() if type(ch) is MMapChannel else None
    why = WALK_REJECTED if lib is None else WALK_FULL
    while why == WALK_FULL:
        pos = ch.position()
        room = min(WALK_CHUNK, (ch.size - pos) // _MIN_MEMBER + 1)
        table, pos, why = walk_members_native(
            lib, ch.memoryview(0, ch.size), pos, room)
        metas.extend(map(Metadata, *table.tolist()))
        ch.seek(pos)
    if metas:
        obs.count("bgzf.blocks_scanned", len(metas))
        obs.count("bgzf.blocks_scanned_native", len(metas))
    obs.annotate(
        "bgzf.read", walk="python" if why == WALK_REJECTED else "native")
    if why == WALK_REJECTED:
        metas.extend(MetadataStream(ch))
    return metas


def pos_iterator(meta: Metadata) -> Iterator[Pos]:
    """All candidate virtual positions of a block (reference PosIterator.scala)."""
    for offset in range(meta.uncompressed_size):
        yield Pos(meta.start, offset)


class UncompressedBytes:
    """Linear reader over the concatenated uncompressed bytes of a block stream.

    ``tell()`` is a linear coordinate counted from construction/last seek —
    the checkers only use differences and equality against it (see
    eager.Checker.scala:36-47,116-119).
    """

    def __init__(self, stream: BlockStream):
        self.stream = stream
        self._linear = 0

    # -- position ------------------------------------------------------------
    def tell(self) -> int:
        return self._linear

    def cur_pos(self) -> Optional[Pos]:
        blk = self.stream.head()
        if blk is None:
            return None
        if blk.idx >= len(blk.data):
            next(self.stream, None)
            return self.cur_pos()
        return blk.pos

    def cur_block(self) -> Optional[Block]:
        if self.cur_pos() is None:
            return None
        return self.stream.head()

    # -- reads ---------------------------------------------------------------
    def read(self, n: int) -> bytes:
        out = bytearray()
        while n > 0:
            blk = self.cur_block()
            if blk is None:
                break
            take = min(n, len(blk.data) - blk.idx)
            out += blk.data[blk.idx: blk.idx + take]
            blk.idx += take
            self._linear += take
            n -= take
        return bytes(out)

    def read_fully(self, n: int) -> bytes:
        data = self.read(n)
        if len(data) != n:
            raise EOFError(f"wanted {n} bytes, got {len(data)}")
        return data

    def read_i32(self) -> int:
        return int.from_bytes(self.read_fully(4), "little", signed=True)

    def read_u8(self) -> int:
        return self.read_fully(1)[0]

    def skip(self, n: int) -> int:
        """Advance up to n bytes; returns bytes actually skipped."""
        skipped = 0
        while n > 0:
            blk = self.cur_block()
            if blk is None:
                break
            take = min(n, len(blk.data) - blk.idx)
            blk.idx += take
            self._linear += take
            skipped += take
            n -= take
        return skipped

    def has_next(self) -> bool:
        return self.cur_pos() is not None

    def next_byte(self) -> int:
        blk = self.cur_block()
        if blk is None:
            raise EOFError("at end of stream")
        b = blk.data[blk.idx]
        blk.idx += 1
        self._linear += 1
        return b

    def close(self) -> None:
        self.stream.close()


class SeekableUncompressedBytes(UncompressedBytes):
    """UncompressedBytes addressable by virtual position."""

    def __init__(self, stream: SeekableBlockStream):
        super().__init__(stream)
        self.stream: SeekableBlockStream = stream

    @staticmethod
    def open(ch: ByteChannel, tolerant: bool = False) -> "SeekableUncompressedBytes":
        return SeekableUncompressedBytes(SeekableBlockStream(ch, tolerant=tolerant))

    def seek(self, pos: Pos) -> None:
        self.stream.seek(pos.block_pos)
        self._linear = 0
        blk = self.stream.head()
        if blk is not None:
            blk.idx = pos.offset
