"""Flat uncompressed views of BGZF files.

The vectorized checkers operate on *flat buffers*: the concatenated
uncompressed payloads of a run of blocks, plus the block table needed to map
``Pos(block, offset) ↔ flat index``. This replaces the reference's per-byte
``UncompressedBytes`` iterators for all bulk work (SURVEY.md §7 step 4a:
"inflate on host, ship uncompressed blocks to HBM").

Inflation fans out across threads: the native inflater and zlib release
the GIL, so a thread pool saturates host cores. This is the one way the
device paths inflate (tpu/inflate.py).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from spark_bam_tpu import obs
from spark_bam_tpu.bgzf.block import Metadata, FOOTER_SIZE
from spark_bam_tpu.bgzf.header import Header
from spark_bam_tpu.bgzf.stream import inflate_block_payload, scan_metadata
from spark_bam_tpu.core.channel import ByteChannel, MMapChannel, open_channel


@dataclass
class FlatView:
    """Uncompressed bytes of blocks[first:last] of a file, flat-addressable."""

    data: np.ndarray          # uint8, concatenated uncompressed payloads
    block_starts: np.ndarray  # int64, compressed-file offset per block
    block_flat: np.ndarray    # int64, flat offset of each block's first byte
    file_total: int | None    # total flat size of the *whole* file, if known
    at_eof: bool = False      # view ends exactly at the file's uncompressed end
    # Where the view was inflated into a buffer of the caller's (``into`` of
    # ``inflate_blocks``): that buffer, and the offset of ``data`` in it.
    frame: np.ndarray | None = None
    lead: int = 0

    @property
    def size(self) -> int:
        return int(self.data.shape[0])

    def flat_of_pos(self, block_pos: int, offset: int) -> int:
        i = int(np.searchsorted(self.block_starts, block_pos))
        if i >= len(self.block_starts) or self.block_starts[i] != block_pos:
            raise KeyError(f"block {block_pos} not in view")
        return int(self.block_flat[i]) + offset

    def pos_of_flat(self, flat: int) -> tuple[int, int]:
        return pos_of_flat_tables(self.block_starts, self.block_flat, flat)

    def pos_of_flat_many(self, flat: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        return pos_of_flat_tables(self.block_starts, self.block_flat, flat)


def metas_block_table(metas) -> tuple[np.ndarray, np.ndarray]:
    """(block_starts, block_flat) arrays for a Metadata list — the same
    tables a FlatView carries, without inflating any payloads."""
    block_starts = np.array([m.start for m in metas], dtype=np.int64)
    usizes = np.array([m.uncompressed_size for m in metas], dtype=np.int64)
    block_flat = np.zeros(len(metas), dtype=np.int64)
    if len(metas):
        np.cumsum(usizes[:-1], out=block_flat[1:])
    return block_starts, block_flat


def pos_of_flat_tables(block_starts: np.ndarray, block_flat: np.ndarray, flat):
    """Flat offset → (block_pos, intra-block offset), an array of offsets →
    the two arrays; the single source of truth for the boundary convention
    (shared with FlatView.pos_of_flat and pos_of_flat_many)."""
    i = np.searchsorted(block_flat, flat, side="right") - 1
    blocks, offs = block_starts[i], flat - block_flat[i]
    return (blocks, offs) if np.ndim(flat) else (int(blocks), int(offs))


def read_block_payload(ch: ByteChannel, meta: Metadata):
    """The raw-DEFLATE payload bytes of one block (header/footer stripped);
    zero-copy on mmap-backed channels."""
    if isinstance(ch, MMapChannel):
        comp = ch.memoryview(meta.start, meta.compressed_size)
    else:
        # Positioned read: no shared-cursor mutation, safe for the
        # concurrent block readers above this.
        comp = ch.read_at(meta.start, meta.compressed_size)
        if len(comp) != meta.compressed_size:
            raise EOFError(
                f"wanted {meta.compressed_size} bytes at {meta.start}, "
                f"got {len(comp)}"
            )
    header = Header.parse(comp[:18])
    return comp[header.size: meta.compressed_size - FOOTER_SIZE]


def read_run_payloads(
    ch: ByteChannel, metas: list[Metadata], threads: int = 8
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(comp, offsets, lengths)`` for a run of blocks: a u8 buffer plus
    each block's raw-DEFLATE payload ``(offset, length)`` into it.

    A contiguous run — the BGZF norm, and what the window planner hands
    out — is fetched with ONE positioned read, so a plan-driven remote
    channel (core/remote_plan.py) sees a single large request instead of
    one call per block; per-call locking/assembly overhead is what
    dominates thousand-block windows on a busy host. Non-contiguous runs
    fan per-block reads across ``threads`` so high-latency channels still
    overlap round-trips."""
    offsets = np.empty(len(metas), dtype=np.int64)
    lengths = np.empty(len(metas), dtype=np.int64)
    if not metas:
        return np.empty(0, dtype=np.uint8), offsets, lengths
    lo = metas[0].start
    hi = metas[-1].start + metas[-1].compressed_size
    if hi - lo == sum(m.compressed_size for m in metas):
        blob = ch.read_at(lo, hi - lo)
        if len(blob) != hi - lo:
            raise EOFError(f"wanted {hi - lo} bytes at {lo}, got {len(blob)}")
        for i, m in enumerate(metas):
            at = m.start - lo
            header = Header.parse(blob[at: at + 18])
            offsets[i] = at + header.size
            lengths[i] = m.compressed_size - header.size - FOOTER_SIZE
        return np.frombuffer(blob, dtype=np.uint8), offsets, lengths
    with ThreadPoolExecutor(max_workers=min(8, max(threads, 1))) as pool:
        parts = list(
            pool.map(
                lambda m: np.frombuffer(
                    read_block_payload(ch, m), dtype=np.uint8
                ),
                metas,
            )
        )
    off = 0
    for i, part in enumerate(parts):
        offsets[i] = off
        lengths[i] = len(part)
        off += len(part)
    comp = np.concatenate(parts) if parts else np.empty(0, dtype=np.uint8)
    return comp, offsets, lengths


def _inflate_one(ch: ByteChannel, meta: Metadata, out: np.ndarray, flat_off: int):
    payload = read_block_payload(ch, meta)
    data = inflate_block_payload(payload, meta.uncompressed_size)
    out[flat_off: flat_off + len(data)] = np.frombuffer(data, dtype=np.uint8)


def _inflate_fast_native(
    ch: ByteChannel, metas: list[Metadata], out: np.ndarray, block_flat: np.ndarray,
    usizes: np.ndarray, threads: int = 1,
) -> bool:
    """Batched native fast inflate. On mmap channels the compressed bytes
    are consumed zero-copy straight from the page cache. With ``threads``,
    contiguous block slices inflate in parallel (the C call releases the
    GIL); each slice writes a disjoint, exact-size output region, so
    word-copy slack never races a neighbour. Returns False when the native
    library is unavailable."""
    from spark_bam_tpu.native.build import inflate_blocks_fast_into, load_native

    if load_native() is None or not metas:
        return False
    offsets = np.empty(len(metas), dtype=np.int64)
    lengths = np.empty(len(metas), dtype=np.int64)
    if isinstance(ch, MMapChannel):
        comp = np.frombuffer(ch.memoryview(0, ch.size), dtype=np.uint8)
        for i, m in enumerate(metas):
            header = Header.parse(ch.memoryview(m.start, 18))
            offsets[i] = m.start + header.size
            lengths[i] = m.compressed_size - header.size - FOOTER_SIZE
    else:
        comp, offsets, lengths = read_run_payloads(ch, metas, threads=threads)

    n_chunks = max(1, min(threads, len(metas) // 32))
    if n_chunks == 1:
        return inflate_blocks_fast_into(
            comp, offsets, lengths, out, block_flat, usizes
        )
    bounds = np.linspace(0, len(metas), n_chunks + 1, dtype=np.int64)

    def run_chunk(k: int) -> bool:
        lo, hi = int(bounds[k]), int(bounds[k + 1])
        flat_lo = int(block_flat[lo])
        flat_hi = (
            len(out) if hi == len(metas) else int(block_flat[hi])
        )
        return inflate_blocks_fast_into(
            comp, offsets[lo:hi], lengths[lo:hi],
            out[flat_lo:flat_hi], block_flat[lo:hi] - flat_lo, usizes[lo:hi],
        )

    with ThreadPoolExecutor(max_workers=n_chunks) as pool:
        return all(pool.map(run_chunk, range(n_chunks)))


def inflate_blocks(
    ch: ByteChannel,
    metas: list[Metadata],
    file_total: int | None = None,
    at_eof: bool = False,
    threads: int = 8,
    into: tuple[np.ndarray, int] | None = None,
) -> FlatView:
    """Inflate a run of blocks into one flat buffer.

    Prefers the native table-driven decoder (~1.3-2x zlib, single call for the
    whole run); falls back to parallel host zlib when the native library is
    unavailable. ``into`` = ``(frame, lead)``: inflate into ``frame`` from
    offset ``lead`` on (the caller's buffer, with room behind for the run
    and 8 bytes) and zero what is left of it, instead of allocating; the
    view then names the frame.
    """
    usizes = np.array([m.uncompressed_size for m in metas], dtype=np.int64)
    block_flat = np.zeros(len(metas), dtype=np.int64)
    if len(metas):
        np.cumsum(usizes[:-1], out=block_flat[1:])
    total = int(usizes.sum())
    # 8 bytes of slack: the native decoder's word copies may overrun a
    # block's end (never the allocation); the view handed out is exact.
    frame, lead = into if into is not None else (None, 0)
    out_alloc = (np.empty(total + 8, dtype=np.uint8) if frame is None
                 else frame[lead: lead + total + 8])
    out = out_alloc[:total]
    with obs.span("inflate.window", blocks=len(metas), bytes=total) as sp:
        native = _inflate_fast_native(
            ch, metas, out_alloc, block_flat, usizes, threads=threads
        )
        if not native:
            if len(metas) > 1 and threads > 1:
                with ThreadPoolExecutor(max_workers=threads) as pool:
                    list(
                        pool.map(
                            lambda im: _inflate_one(
                                ch, im[1], out, int(block_flat[im[0]])
                            ),
                            enumerate(metas),
                        )
                    )
            else:
                for i, m in enumerate(metas):
                    _inflate_one(ch, m, out, int(block_flat[i]))
        if frame is not None:
            frame[lead + total:] = 0
        sp.set(engine="native" if native else "zlib")
    obs.count("inflate.windows")
    obs.count("inflate.blocks", len(metas))
    obs.count("inflate.bytes", total)
    return FlatView(
        out,
        np.array([m.start for m in metas], dtype=np.int64),
        block_flat,
        file_total,
        at_eof or (file_total is not None and total == file_total),
        frame,
        lead,
    )


def flatten_file(path, threads: int = 8) -> FlatView:
    """Inflate an entire BAM into one flat buffer (fixtures / small files)."""
    with open_channel(path) as ch, obs.span(
        "bgzf.read", kind="metadata_scan", path=str(path)
    ):
        metas = scan_metadata(ch)
    with open_channel(path) as ch:
        total = sum(m.uncompressed_size for m in metas)
        return inflate_blocks(ch, metas, file_total=total, at_eof=True, threads=threads)
