"""What the generators share: BGZF members, the BAM header, ragged writes and
the index that is every cell's oracle.

Nothing here imports the program. A file is the BAM header plus records,
cut into htslib's 0xFF00-byte payloads, each deflated at zlib level 6 into
one BGZF member. The index records what the generator WROTE (flat offset of
every record, compressed and flat start of every member), so an answer
compared with it is compared with something no checker of the repo made.
"""

from __future__ import annotations

import struct
import zlib
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

BLOCK_PAYLOAD = 0xFF00  # htslib's BGZF_BLOCK_SIZE
LEVEL = 6
EOF_MEMBER = bytes.fromhex(
    "1f8b08040000000000ff0600424302001b0003000000000000000000"
)
_MEMBER_HEAD = bytes.fromhex("1f8b08040000000000ff060042430200")

#: GRCh38 primary assembly (UCSC names), the dictionary of a 1000 Genomes /
#: NA12878 GRCh38 alignment without its alt and decoy contigs.
GRCH38 = (
    ("chr1", 248956422), ("chr2", 242193529), ("chr3", 198295559),
    ("chr4", 190214555), ("chr5", 181538259), ("chr6", 170805979),
    ("chr7", 159345973), ("chr8", 145138636), ("chr9", 138394717),
    ("chr10", 133797422), ("chr11", 135086622), ("chr12", 133275309),
    ("chr13", 114364328), ("chr14", 107043718), ("chr15", 101991189),
    ("chr16", 90338345), ("chr17", 83257441), ("chr18", 80373285),
    ("chr19", 58617616), ("chr20", 64444167), ("chr21", 46709983),
    ("chr22", 50818468), ("chrX", 156040895), ("chrY", 57227415),
    ("chrM", 16569),
)

#: 4-bit BAM codes of A, C, G, T.
BASE_CODES = np.array([1, 2, 4, 8], dtype=np.uint8)


def bgzf_member(payload: bytes) -> bytes:
    """One BGZF member holding ``payload`` (at most ``BLOCK_PAYLOAD`` bytes)."""
    c = zlib.compressobj(LEVEL, zlib.DEFLATED, -15)
    body = c.compress(payload) + c.flush()
    size = len(_MEMBER_HEAD) + 2 + len(body) + 8
    if size > 0x10000:
        raise ValueError(f"member of {size} bytes does not fit BSIZE")
    return b"".join((
        _MEMBER_HEAD, struct.pack("<H", size - 1), body,
        struct.pack("<II", zlib.crc32(payload), len(payload)),
    ))


def bam_header(contigs, read_groups: tuple, program: str) -> bytes:
    text = "@HD\tVN:1.6\tSO:coordinate\n" + "".join(
        f"@SQ\tSN:{n}\tLN:{ln}\n" for n, ln in contigs
    ) + "".join(
        f"@RG\tID:{rg}\tSM:sample\tPL:{program}\n" for rg in read_groups
    )
    out = [b"BAM\x01", struct.pack("<i", len(text)), text.encode(),
           struct.pack("<i", len(contigs))]
    for n, ln in contigs:
        out += [struct.pack("<i", len(n) + 1), n.encode(), b"\x00",
                struct.pack("<i", ln)]
    return b"".join(out)


def reg2bin(beg: np.ndarray, end: np.ndarray) -> np.ndarray:
    """The UCSC bin of ``[beg, end)`` for arrays (SAM specification 5.3)."""
    beg = beg.astype(np.int64)
    end = end.astype(np.int64) - 1
    out = np.zeros(len(beg), dtype=np.int64)
    done = np.zeros(len(beg), dtype=bool)
    for shift, offset in ((14, 4681), (17, 585), (20, 73), (23, 9), (26, 1)):
        hit = ~done & ((beg >> shift) == (end >> shift))
        out[hit] = offset + (beg[hit] >> shift)
        done |= hit
    return out


def padded_matrix(rows: list) -> tuple:
    """``(matrix uint8 (N, W), lengths int64 (N,))`` of a list of bytes."""
    lens = np.fromiter((len(r) for r in rows), dtype=np.int64, count=len(rows))
    width = int(lens.max()) if len(rows) else 0
    flat = b"".join(r.ljust(width, b"\x00") for r in rows)
    return (np.frombuffer(flat, dtype=np.uint8).reshape(len(rows), width),
            lens)


def _kept(parts: list) -> np.ndarray:
    return np.concatenate([
        np.ones(m.shape, dtype=bool) if lens is None
        else np.arange(m.shape[1])[None, :] < lens[:, None]
        for m, lens in parts
    ], axis=1)


def row_lengths(parts: list) -> np.ndarray:
    """Bytes of each record that ``join_rows(parts)`` will write."""
    return sum(
        np.full(m.shape[0], m.shape[1], dtype=np.int64) if lens is None
        else lens.astype(np.int64) for m, lens in parts
    )


def join_rows(parts: list, rows: int) -> np.ndarray:
    """The first ``rows`` records from their fields. ``parts`` is a list of
    ``(matrix (N, W), lens (N,) or None)``: record ``i`` is the concatenation
    of ``matrix[i, :lens[i]]`` over the parts (all ``W`` columns where
    ``lens`` is None)."""
    parts = [(m[:rows], None if lens is None else lens[:rows])
             for m, lens in parts]
    return np.concatenate([m for m, _ in parts], axis=1)[_kept(parts)]


def write_bam(path: Path, header: bytes, records: bytes,
              record_starts: np.ndarray, threads: int = 8) -> dict:
    """Write ``header + records`` as BGZF; returns the index (see module
    docstring). ``record_starts`` are offsets into ``records``."""
    flat = header + records
    chunks = [flat[i: i + BLOCK_PAYLOAD]
              for i in range(0, len(flat), BLOCK_PAYLOAD)]
    with ThreadPoolExecutor(threads) as pool:  # zlib releases the GIL
        members = list(pool.map(bgzf_member, chunks))
    sizes = np.fromiter((len(m) for m in members), dtype=np.int64,
                        count=len(members))
    with open(path, "wb") as f:
        f.write(b"".join(members))
        f.write(EOF_MEMBER)
    compressed = int(sizes.sum()) + len(EOF_MEMBER)
    return {
        "record_starts": record_starts.astype(np.int64) + len(header),
        "block_starts": np.concatenate(([0], np.cumsum(sizes)[:-1])),
        "block_flat": np.arange(len(chunks), dtype=np.int64) * BLOCK_PAYLOAD,
        "header_end": len(header),
        "uncompressed_bytes": len(flat),
        "compressed_bytes": compressed,
        "ratio": len(flat) / compressed,
    }
