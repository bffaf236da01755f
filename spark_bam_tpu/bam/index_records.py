"""Single-pass record indexer → ``.records`` sidecar (ground truth).

Emits ``blockPos,offset`` per record (reference
check/.../bam/index/IndexRecords.scala:107-180; line format :149). Tolerant
of truncated files by default: EOF mid-record ends the traversal with what
was seen (reference :160-174), unless ``strict``.
"""

from __future__ import annotations

import logging
import os
import time
import warnings

import numpy as np

from spark_bam_tpu.bam.iterators import PosStream
from spark_bam_tpu.core.channel import open_channel
from spark_bam_tpu.core.pos import Pos

log = logging.getLogger(__name__)


def format_record_line(pos: Pos) -> str:
    return f"{pos.block_pos},{pos.offset}"


def parse_record_line(line: str) -> Pos:
    block, off = line.strip().split(",")
    return Pos(int(block), int(off))


def read_records_index(path) -> list[Pos]:
    from spark_bam_tpu.core.channel import read_text

    return [
        parse_record_line(line)
        for line in read_text(path).splitlines()
        if line.strip()
    ]


def iter_records_arrays(path, chunk_bytes: int):
    """The sidecar in file order, a piece at a time: ``(block_pos, offset)``,
    two int64 arrays a piece, the text parsed by numpy in chunks of
    ``chunk_bytes`` cut at line ends, so no Python object is made a record
    (a 60 GB BAM's sidecar holds 170 million lines). Blank lines and a
    missing or present trailing newline are tolerated, as
    ``read_records_index`` tolerates them; a line that is not
    ``blockPos,offset`` raises ``ValueError`` when its piece is reached."""
    with open_channel(path) as ch:
        pos, carry = 0, b""
        while pos < ch.size or carry:
            data = carry + bytes(ch.read_at(pos, min(chunk_bytes, ch.size - pos)))
            pos = min(pos + chunk_bytes, ch.size)
            cut = len(data) if pos >= ch.size else data.rfind(b"\n") + 1
            data, carry = data[:cut], data[cut:]
            if not data.strip():
                continue  # blank lines alone: numpy reads them as a number
            with warnings.catch_warnings():
                # numpy warns of text it could not read to its end and
                # returns what it had: the count below catches that.
                warnings.simplefilter("ignore", DeprecationWarning)
                values = np.fromstring(
                    data.replace(b",", b" "), dtype=np.int64, sep=" ")
            # One comma in every line that holds anything, two numbers a
            # comma: commas and the starts of the runs between whitespace
            # must alternate.
            raw = np.frombuffer(data, dtype=np.uint8)
            inside = raw > 32
            starts = inside & ~np.concatenate(([False], inside[:-1]))
            line_of_comma = np.cumsum(starts)[raw == 44]
            if len(values) != 2 * len(line_of_comma) or not np.array_equal(
                line_of_comma, np.arange(1, int(starts.sum()) + 1)
            ):
                raise ValueError(
                    f"{path}: not a .records sidecar (blockPos,offset a line)")
            yield values[0::2], values[1::2]


def read_records_arrays(path, chunk_bytes: int = 64 << 20):
    """The sidecar as two int64 arrays ``(block_pos, offset)``, in file
    order: ``iter_records_arrays``' pieces, joined."""
    pieces = list(iter_records_arrays(path, chunk_bytes))
    if not pieces:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    blocks, offsets = zip(*pieces)
    return np.concatenate(blocks), np.concatenate(offsets)


def index_records(
    bam_path, out_path=None, strict: bool = False, heartbeat_seconds: float = 10.0
) -> tuple[str, int]:
    """Write the ``.records`` sidecar for ``bam_path``; returns (path, #records)."""
    out_path = str(out_path) if out_path is not None else str(bam_path) + ".records"
    count = 0
    last_beat = time.monotonic()
    # Write-then-rename (pid-suffixed: concurrent indexers must not
    # interleave): a crash mid-index must never leave a truncated sidecar
    # that downstream consumers would trust as ground truth.
    tmp_path = f"{out_path}.tmp{os.getpid()}"
    try:
        with open_channel(bam_path) as ch, open(tmp_path, "w") as out:
            stream = PosStream.open(ch)
            try:
                for pos in stream:
                    out.write(format_record_line(pos) + "\n")
                    count += 1
                    now = time.monotonic()
                    if now - last_beat >= heartbeat_seconds:
                        log.info("indexed %d records (at %s)", count, pos)
                        last_beat = now
            except (EOFError, IOError):
                if strict:
                    raise
                log.warning("truncated BAM: stopping after %d records", count)
        os.replace(tmp_path, out_path)
    finally:
        if os.path.exists(tmp_path):  # failure path only; replace moved it
            os.unlink(tmp_path)
    return out_path, count
