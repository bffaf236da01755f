"""Single-pass BGZF block indexer → ``.blocks`` sidecar.

Emits ``start,compressedSize,uncompressedSize`` per block (reference
bgzf/.../index/IndexBlocks.scala:11-52; line format :42). The sidecar is the
durable accelerator consumed by the split planner (check/blocks.py) — reading
it skips the parallel block search.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Iterable

from spark_bam_tpu.bgzf.block import Metadata
from spark_bam_tpu.bgzf.stream import MetadataStream, scan_metadata
from spark_bam_tpu.core.channel import (
    is_url,
    open_channel,
    path_exists,
    path_size,
)
from spark_bam_tpu.core.faults import Unrecoverable

log = logging.getLogger(__name__)


class StaleBlocksIndexError(IOError, Unrecoverable):
    """Strict mode: the ``.blocks`` sidecar contradicts the BAM it names.
    Deterministic — retrying the read cannot reconcile them."""


def format_block_line(meta: Metadata) -> str:
    return f"{meta.start},{meta.compressed_size},{meta.uncompressed_size}"


def parse_block_line(line: str) -> Metadata:
    parts = line.strip().split(",")
    if len(parts) != 3:
        raise ValueError(f"Bad blocks-index line: {line!r}")
    return Metadata(int(parts[0]), int(parts[1]), int(parts[2]))


def read_blocks_index(path) -> list[Metadata]:
    from spark_bam_tpu.core.channel import read_text

    return [
        parse_block_line(line)
        for line in read_text(path).splitlines()
        if line.strip()
    ]


def index_blocks(
    bam_path, out_path=None, heartbeat_seconds: float = 10.0
) -> tuple[str, int]:
    """Write the ``.blocks`` sidecar for ``bam_path``; returns (path, #blocks)."""
    out_path = str(out_path) if out_path is not None else str(bam_path) + ".blocks"
    count = 0
    last_beat = time.monotonic()
    # Write-then-rename (pid-suffixed: concurrent indexers must not
    # interleave): a crash mid-index must never leave a truncated sidecar.
    tmp_path = f"{out_path}.tmp{os.getpid()}"
    try:
        with open_channel(bam_path) as ch, open(tmp_path, "w") as out:
            for meta in MetadataStream(ch):
                out.write(format_block_line(meta) + "\n")
                count += 1
                now = time.monotonic()
                if now - last_beat >= heartbeat_seconds:
                    log.info(
                        "indexed %d blocks (at offset %d)", count, meta.start
                    )
                    last_beat = now
        os.replace(tmp_path, out_path)
    finally:
        if os.path.exists(tmp_path):  # failure path only; replace moved it
            os.unlink(tmp_path)
    return out_path, count


def validate_blocks_index(blocks: list[Metadata], file_size: int) -> str | None:
    """Why ``blocks`` cannot describe a BAM of ``file_size`` bytes, or None
    when it checks out: non-empty, starting at 0, a contiguous chain, and
    covering the file up to an optional 28-byte BGZF EOF sentinel (which
    ``MetadataStream`` excludes from the index)."""
    if not blocks:
        return "empty index for a non-empty file" if file_size else None
    if blocks[0].start != 0:
        return f"first block starts at {blocks[0].start}, not 0"
    for prev, cur in zip(blocks, blocks[1:]):
        if prev.start + prev.compressed_size != cur.start:
            return (
                f"gap/overlap at offset {cur.start}: previous block ends at "
                f"{prev.start + prev.compressed_size}"
            )
    last_end = blocks[-1].start + blocks[-1].compressed_size
    if file_size - last_end not in (0, 28):
        return (
            f"index covers {last_end} of {file_size} bytes "
            "(not an EOF-sentinel remainder)"
        )
    return None


def blocks_metadata(
    bam_path, strict: bool = False, config=None
) -> Iterable[Metadata]:
    """All block Metadata of a BAM: from the ``.blocks`` sidecar when
    present *and* consistent with the file (start-chain contiguity + size
    coverage — a stale sidecar from an overwritten BAM must not poison the
    split plan), else from the ``.sbi`` cache tier (fingerprint-validated;
    sbi/store.py), else by scan — with the scan result written through to
    the ``.sbi`` tier so the next load (and every fleet member after the
    first) derives its fetch plan without touching the BAM body.
    ``strict`` raises on a stale sidecar instead of silently rescanning,
    mirroring FaultPolicy's strict mode."""
    from spark_bam_tpu.sbi.store import cached_blocks, store_blocks

    remote = is_url(str(bam_path))
    if remote:
        # Remote paths consult the ``.sbi`` tier FIRST: a warm hit costs
        # two round-trips (the fingerprint's size + head-CRC probe), while
        # the ``.blocks`` existence probe alone is a round-trip against a
        # sidecar that usually does not exist. Local paths keep
        # sidecar-first — the existence check is free and a user-authored
        # sidecar should win. The fingerprint binds the hit to the current
        # file bytes, so precedence cannot serve a stale table.
        blocks = cached_blocks(bam_path, config)
        if blocks is not None:
            return blocks
    sidecar = str(bam_path) + ".blocks"
    if path_exists(sidecar):
        blocks = read_blocks_index(sidecar)
        reason = validate_blocks_index(blocks, path_size(bam_path))
        if reason is None:
            return blocks
        if strict:
            raise StaleBlocksIndexError(f"{sidecar}: {reason}")
        from spark_bam_tpu import obs

        obs.count("cache.invalidations")
        log.warning(
            "ignoring stale .blocks sidecar %s (%s); rescanning", sidecar,
            reason,
        )
    if not remote:
        blocks = cached_blocks(bam_path, config)
        if blocks is not None:
            return blocks
    with open_channel(bam_path) as ch:
        blocks = scan_metadata(ch)
    try:
        store_blocks(bam_path, blocks, config)
    except Exception:  # write-through is an accelerator, never a failure
        log.debug("block-table write-through failed", exc_info=True)
    return blocks
