"""Coordinate-sorted Oxford Nanopore ultra-long reads, with whales.

Read lengths are log-normal with the stated N50 between a floor and a cap;
a record is about 1.77 bytes a base (36 fixed, a UUID name, a CIGAR of
S/M/I/D with an operation about every ``op_every`` bases, 4-bit bases,
per-base qualities, tags RG NM and the basecaller's scalars). A read whose
CIGAR has more than 65,535 operations carries the placeholder
``<l_seq>S<span>N`` and the real CIGAR in ``CG:B,I`` (SAM specification
4.2.2). Qualities are uniform over the stated Phred range and bases come
from a seeded random reference, so members are literal-heavy.

A **whale** is a read of ``whale_length_min`` to ``whale_length_max``
bases: a record longer than the stream's halo and shorter than
``max_read_size``. ``whales`` of them in a row start at a flat offset the
seed draws in the last ``whale_start_span`` bytes before
``whale_start_end`` (the read before them is cut to end there), and only
where the file reaches that far: a file of fewer bytes holds none.
Parameters come from the configuration's file; every byte follows from
``seed``. Nothing here imports the program.
"""

from __future__ import annotations

import math
import struct

import numpy as np

from bench import bamgen

_HEAD = struct.Struct("<iiiBBHHHiiii")
_MAX_CIGAR_OPS = 65535
_CLIP_MAX = 80  # soft-clipped bases at either end: 1 to this many
#: Bytes of the basecaller's scalar tags (qs du ns ts mx ch rn sm sd) and NM.
_SCALAR_TAG_BYTES = 7 + 7 + 7 + 7 + 4 + 5 + 7 + 7 + 7 + 7


def _events(length: int, op_every: int) -> int:
    """One-base insertions and deletions of a read of ``length`` bases."""
    return max((length - 2 * _CLIP_MAX) // (2 * op_every), 1)


def _cigar(rng, length: int, op_every: int) -> tuple:
    """``(ops uint32, reference span, edit distance)``: a soft clip at each
    end, matches between one-base insertions and deletions, an operation
    about every ``op_every`` bases: ``2 * _events + 3`` of them.
    Query-consuming operations (S, M, I) sum to ``length``."""
    clip = rng.integers(1, _CLIP_MAX + 1, 2)
    body = length - int(clip.sum())
    k = _events(length, op_every)
    cuts = np.sort(rng.choice(body - k - 1, k, replace=False)) + 1
    match = np.diff(np.concatenate(([0], cuts, [body - k])))
    deletion = rng.random(k) < 0.5  # else an insertion, which takes a base
    match[-1] += int(deletion.sum())
    ops = np.empty(2 * k + 3, dtype=np.int64)
    ops[0], ops[-1] = (clip << 4) | 4  # S
    ops[1:-1:2] = match << 4  # M
    ops[2:-1:2] = (1 << 4) | np.where(deletion, 2, 1)
    return ops.astype("<u4"), int(match.sum() + deletion.sum()), k


def record_bytes(length: int, op_every: int, read_group: str) -> int:
    """Bytes of the record of a read of ``length`` bases, its length prefix
    included: what ``generate`` writes for it, exactly."""
    ops = 2 * _events(length, op_every) + 3
    cigar = 4 * ops if ops <= _MAX_CIGAR_OPS else 8 + 8 + 4 * ops
    return (36 + 37 + cigar + (length + 1) // 2 + length
            + 3 + len(read_group) + 1 + _SCALAR_TAG_BYTES)


def _read_that_fills(room: int, op_every: int, read_group: str) -> int:
    """The longest read whose record takes at most ``room`` bytes."""
    lo, hi = 4 * _CLIP_MAX, room
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if record_bytes(mid, op_every, read_group) <= room:
            lo = mid
        else:
            hi = mid - 1
    return lo


def generate(params: dict, seed: int, target_bytes: int, path) -> dict:
    rng = np.random.default_rng([int(seed), 0x0A7])
    lo, hi = int(params["read_length_min"]), int(params["read_length_max"])
    sigma = float(params["read_length_sigma"])
    every = int(params["op_every"])
    origin, contig = int(params["origin"]), int(params["contig"])
    coverage = float(params["coverage"])
    q_lo, q_hi = int(params["quality_min"]), int(params["quality_max"])
    rg = b"RGZ" + params["read_group"].encode() + b"\x00"
    header = bamgen.bam_header(
        bamgen.GRCH38, (params["read_group"],), "ONT")

    # Log-normal lengths whose N50 (the length-weighted median,
    # exp(mu + sigma^2)) is the stated one.
    mu = math.log(float(params["read_length_n50"])) - sigma * sigma
    mean = math.exp(mu + sigma * sigma / 2.0)
    n = int(target_bytes / (mean * 1.5) * 2.0) + 16
    lengths = np.clip(rng.lognormal(mu, sigma, n), lo, hi).astype(np.int64)

    # The whales, where the file reaches their start.
    w_end = int(params["whale_start_end"])
    w_at = w_end - int(rng.integers(64, int(params["whale_start_span"])))
    whales = [int(x) for x in rng.integers(
        int(params["whale_length_min"]), int(params["whale_length_max"]) + 1,
        int(params["whales"]))]
    if len(header) + target_bytes <= w_end:
        whales = []

    ref_len = int((lengths.sum() + sum(whales)) / coverage) + hi + max(
        whales, default=0)
    ref = rng.integers(0, 4, ref_len + 2, dtype=np.uint8)

    out, starts, whale_starts = [], [], []
    at, pos = 0, origin
    group = params["read_group"]

    def write(length: int) -> None:
        nonlocal at, pos
        ops, span, edits = _cigar(rng, length, every)
        tags = [rg, b"NMI", struct.pack("<I", edits)]
        cigar = ops
        if len(ops) > _MAX_CIGAR_OPS:
            cigar = np.array([(length << 4) | 4, (span << 4) | 3], "<u4")
            tags += [b"CGBI", struct.pack("<I", len(ops)), ops.tobytes()]
        samples = int(length * rng.uniform(9.0, 12.0))
        tags += [
            b"qsf", struct.pack("<f", rng.uniform(8.0, 22.0)),
            b"duf", struct.pack("<f", samples / 5000.0),
            b"nsI", struct.pack("<I", samples),
            b"tsI", struct.pack("<I", int(rng.integers(10, 400))),
            b"mxC", bytes((int(rng.integers(1, 5)),)),
            b"chS", struct.pack("<H", int(rng.integers(1, 2676))),
            b"rnI", struct.pack("<I", int(rng.integers(1, 400000))),
            b"smf", struct.pack("<f", rng.uniform(60.0, 110.0)),
            b"sdf", struct.pack("<f", rng.uniform(8.0, 30.0)),
        ]
        tags = b"".join(tags)
        u = rng.bytes(16).hex()
        name = f"{u[:8]}-{u[8:12]}-{u[12:16]}-{u[16:20]}-{u[20:]}".encode(
            ) + b"\x00"
        off = pos - origin
        bases = bamgen.BASE_CODES[ref[off: off + length + length % 2]]
        if length % 2:
            bases[-1] = 0
        seq = (bases[0::2] << 4) | bases[1::2]
        qual = rng.integers(q_lo, q_hi + 1, length, dtype=np.uint8)
        body = len(name) + 4 * len(cigar) + len(seq) + length + len(tags)
        head = _HEAD.pack(
            32 + body, contig, pos, len(name), 60,
            int(bamgen.reg2bin(np.array([pos]), np.array([pos + span]))[0]),
            len(cigar), 0 if rng.random() < 0.5 else 16, length, -1, -1, 0,
        )
        assert 36 + body == record_bytes(length, every, group)
        starts.append(at)
        out.extend((head, name, cigar.tobytes(), seq.tobytes(),
                    qual.tobytes(), tags))
        at += 36 + body
        pos += max(int(length / coverage), 1)

    for length in lengths.tolist():
        spare = record_bytes(length, every, group) + record_bytes(
            lo, every, group)
        if whales and w_at - (len(header) + at) < spare:
            # The next read would reach the whales' start or leave less
            # than a read before it: cut it to end there, then the whales.
            write(_read_that_fills(
                w_at - (len(header) + at), every, group))
            for whale in whales:
                whale_starts.append(len(header) + at)
                write(whale)
            whales = []
        elif target_bytes - at < spare:
            # Likewise at the file's end: the records are ``target_bytes``
            # to a few bytes on every seed, so that a rate over the file
            # does not move with the last read drawn.
            write(_read_that_fills(target_bytes - at, every, group))
        else:
            write(length)
        if target_bytes - at < record_bytes(lo, every, group):
            break
    else:
        raise ValueError("too few records drawn for this size")

    index = bamgen.write_bam(path, header, b"".join(out),
                             np.array(starts, dtype=np.int64))
    index["record_bytes_mean"] = at / len(starts)
    index["whale_starts"] = whale_starts
    return index
