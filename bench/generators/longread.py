"""Coordinate-sorted HiFi-class long reads: records of tens of kilobytes.

Each record spans several BGZF members; per-base qualities use the whole
Phred range and hardly compress; bases come from a seeded random reference
at the stated coverage, but a neighbour's copy of them lies beyond deflate's
32 KiB window, so members are literal-heavy. Parameters come from the
configuration's file; every byte follows from ``seed``.
"""

from __future__ import annotations

import struct

import numpy as np

from bench import bamgen

_HEAD = struct.Struct("<iiiBBHHHiiii")


def _cigar(rng, length: int, indel_every: int) -> tuple:
    """``(ops uint32, reference span)``: matches of about ``indel_every``
    bases between one-base insertions and deletions, as a HiFi alignment
    has them (homopolymer slips). Query-consuming ops sum to ``length``."""
    k = max(int(length // indel_every), 1)
    cuts = np.sort(rng.choice(np.arange(1, length - k), k, replace=False))
    match = np.diff(np.concatenate(([0], cuts, [length - k])))
    deletion = rng.random(k) < 0.5  # else an insertion, which takes a base
    match[-1] += int(deletion.sum())
    ops = np.empty(2 * k + 1, dtype=np.int64)
    ops[0::2] = match << 4  # M
    ops[1::2] = (1 << 4) | np.where(deletion, 2, 1)
    return ops.astype("<u4"), int(match.sum() + deletion.sum())


def generate(params: dict, seed: int, target_bytes: int, path) -> dict:
    rng = np.random.default_rng([int(seed), 0x41F1])
    lo, hi = int(params["read_length_min"]), int(params["read_length_max"])
    origin = int(params["origin"])
    contig = int(params["contig"])
    q_hi = int(params["quality_max"])
    rg = b"RGZ" + params["read_group"].encode() + b"\x00"

    mean_record = 36 + 24 + (lo + hi) * 3 // 4
    n = int(target_bytes / mean_record * 1.25) + 2
    lengths = rng.integers(lo, hi + 1, n)
    ref_len = int(lengths.sum() / float(params["coverage"]))
    ref = rng.integers(0, 4, ref_len + hi + hi // 8, dtype=np.uint8)
    # Homopolymer runs, as a genome has them (poly-A tails, microsatellites).
    # They bound the depth of deflate's copy chains from both sides, and with
    # it the rounds of the device's LZ77 resolve: see ``assumed``.
    every, longest = int(params["homopolymer_every"]), int(
        params["homopolymer_max"])
    at = np.cumsum(rng.geometric(1.0 / every, len(ref) // every))
    for p, run in zip(at[at < len(ref) - longest].tolist(),
                      rng.integers(longest // 2, longest + 1, len(at))):
        ref[p: p + run] = ref[p]
    pos0 = np.sort(rng.integers(0, ref_len, n))
    zmw = rng.integers(1, int(params["zmw_max"]), n)

    out, starts, at = [], [], 0
    for i in range(n):
        length = int(lengths[i])
        ops, span = _cigar(rng, length, int(params["indel_every"]))
        name = b"%s/%d/ccs\x00" % (params["movie"].encode(), zmw[i])
        bases = bamgen.BASE_CODES[ref[pos0[i]: pos0[i] + length + length % 2]]
        if length % 2:
            bases[-1] = 0
        seq = (bases[0::2] << 4) | bases[1::2]
        qual = rng.integers(0, q_hi + 1, length, dtype=np.uint8)
        passes = int(rng.integers(3, 40))
        tags = b"".join((
            rg,
            b"rqf", struct.pack("<f", 1.0 - 10 ** -rng.uniform(2.0, 4.5)),
            b"npC", bytes((passes,)),
            b"ecf", struct.pack("<f", passes * rng.uniform(0.9, 1.1)),
            b"zmI", struct.pack("<I", int(zmw[i])),
        ))
        pos = origin + int(pos0[i])
        body = len(name) + 4 * len(ops) + len(seq) + length + len(tags)
        head = _HEAD.pack(
            32 + body, contig, pos, len(name), 60,
            int(bamgen.reg2bin(np.array([pos]), np.array([pos + span]))[0]),
            len(ops), 0 if rng.random() < 0.5 else 16, length, -1, -1, 0,
        )
        starts.append(at)
        out += [head, name, ops.tobytes(), seq.tobytes(), qual.tobytes(),
                tags]
        at += 36 + body
        if at >= target_bytes:
            break
    else:
        raise ValueError("too few records drawn for this size")

    header = bamgen.bam_header(
        bamgen.GRCH38, (params["read_group"],), "PACBIO")
    index = bamgen.write_bam(path, header, b"".join(out),
                             np.array(starts, dtype=np.int64))
    index["record_bytes_mean"] = at / len(starts)
    return index
