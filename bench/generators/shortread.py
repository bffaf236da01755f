"""Coordinate-sorted paired-end short reads at a stated coverage.

A slice of a 30x whole-genome alignment: fragments fall uniformly on a seeded
random reference of just the length that gives the coverage, so a record
shares sequence with the thirty or so before it, inside deflate's 32 KiB
window, as aligned reads do. Parameters come from the configuration's file;
every byte follows from ``seed``.
"""

from __future__ import annotations

import numpy as np

from bench import bamgen

#: Illumina's 8-level quality binning (HiSeq X / NovaSeq RTA) and how often a
#: run of bases takes each level.
QUAL_LEVELS = np.array([2, 6, 15, 22, 27, 33, 37, 40], dtype=np.uint8)

FIXED = np.dtype([
    ("block_size", "<i4"), ("ref_id", "<i4"), ("pos", "<i4"),
    ("l_read_name", "u1"), ("mapq", "u1"), ("bin", "<u2"),
    ("n_cigar", "<u2"), ("flag", "<u2"), ("l_seq", "<i4"),
    ("next_ref_id", "<i4"), ("next_pos", "<i4"), ("tlen", "<i4"),
])
_LETTERS = "ACGT"


def _qualities(rng, n: int, length: int, weights, run_break: float):
    """Binned qualities in runs: a base starts a new run with probability
    ``run_break`` and the run draws its level by ``weights``. The runs set
    the depth of deflate's copy chains, and with it how many rounds the
    device's LZ77 resolve takes: see the configuration's ``assumed``."""
    lut = QUAL_LEVELS[np.searchsorted(
        np.cumsum(weights) / np.sum(weights), (np.arange(256) + 0.5) / 256
    )]
    out = np.empty((n, length), dtype=np.uint8)
    cols = np.arange(length, dtype=np.int32)
    for lo in range(0, n, 32768):
        m = min(32768, n - lo)
        breaks = rng.random((m, length), dtype=np.float32) < run_break
        breaks[:, 0] = True
        last = np.maximum.accumulate(np.where(breaks, cols, 0), axis=1)
        level = lut[rng.integers(0, 256, (m, length), dtype=np.uint8)]
        out[lo: lo + m] = np.take_along_axis(level, last, axis=1)
    return out


def _md(ref_row: np.ndarray, mismatch_at: np.ndarray) -> bytes:
    """MD:Z of an ungapped alignment: runs of matches and reference bases."""
    out, prev = [], 0
    for at in mismatch_at.tolist():
        out.append(f"{at - prev}{_LETTERS[ref_row[at]]}")
        prev = at + 1
    out.append(str(len(ref_row) - prev))
    return "".join(out).encode()


def generate(params: dict, seed: int, target_bytes: int, path) -> dict:
    rng = np.random.default_rng([int(seed), 0x5407])
    length = int(params["read_length"])
    lanes = int(params["lanes"])
    origin = int(params["origin"])
    special = float(params["clipped_or_unmapped_share"])
    read_groups = tuple(f"{params['flowcell']}.{k + 1}" for k in range(lanes))

    # Somewhat more fragments than fit (no record is under ``min_record``
    # bytes); the file is cut at the first record past ``target_bytes``.
    min_record = 36 + 24 + (length + 1) // 2 + length + 12
    n_frag = target_bytes // (2 * min_record) + 1
    ref_len = int(n_frag * 2 * length / float(params["coverage"]))
    ref = rng.integers(0, 4, ref_len + 2048, dtype=np.uint8)
    insert = np.clip(
        rng.normal(params["insert_mean"], params["insert_sd"], n_frag),
        length, 1000,
    ).astype(np.int64)
    frag = rng.integers(0, ref_len - 1000, n_frag)
    flip = rng.random(n_frag) < 0.5  # which mate lies on the forward strand

    # Two records to a fragment: the left one at its start, forward.
    n = 2 * n_frag
    frag_of = np.repeat(np.arange(n_frag), 2)
    is_right = np.tile(np.array([False, True]), n_frag)
    left, right = frag[frag_of], (frag + insert - length)[frag_of]
    pos0 = np.where(is_right, right, left)
    mate_pos0 = np.where(is_right, left, right)
    first = is_right == flip[frag_of]  # read 1 of the pair
    flag = (1 | 2 | np.where(is_right, 16, 32)
            | np.where(first, 64, 128)).astype(np.int64)
    tlen = np.where(is_right, -insert[frag_of], insert[frag_of])
    mapq = np.where(rng.random(n) < 0.93, 60,
                    rng.integers(0, 60, n)).astype(np.int64)

    # The 1%: soft-clipped starts, and mates that did not map.
    kind = rng.random(n)
    clip = np.where(kind < special / 2, rng.integers(5, 60, n), 0)
    unmapped = (kind >= special / 2) & (kind < special) & is_right
    clip[unmapped] = 0
    partner = np.zeros(n, dtype=bool)
    partner[np.flatnonzero(unmapped) - 1] = True
    flag[unmapped] = (flag[unmapped] & ~(2 | 16)) | 4
    flag[partner] = (flag[partner] & ~(2 | 32)) | 8
    pos0[unmapped] = mate_pos0[unmapped]  # placed with its mate
    mate_pos0[partner] = pos0[partner]
    mapq[unmapped] = 0
    tlen[unmapped | partner] = 0

    order = np.argsort(pos0 + clip, kind="stable")  # by aligned position
    (frag_of, pos0, flag, mate_pos0, tlen, mapq, clip, unmapped) = (
        a[order] for a in
        (frag_of, pos0, flag, mate_pos0, tlen, mapq, clip, unmapped)
    )
    mapped = ~unmapped

    # Bases: the reference under the read, a few substitutions, clipped or
    # unmapped bases at random.
    cols = np.arange(length)
    ref_rows = ref[pos0[:, None] + cols[None, :]]
    bases = ref_rows.copy()
    sub = rng.random((n, length), dtype=np.float32) < float(
        params["mismatch_rate"])
    sub &= mapped[:, None] & (cols[None, :] >= clip[:, None])
    bases[sub] = (bases[sub] + rng.integers(1, 4, int(sub.sum()))) % 4
    noise = unmapped[:, None] | (cols[None, :] < clip[:, None])
    bases[noise] = rng.integers(0, 4, int(noise.sum()))
    codes = bamgen.BASE_CODES[bases]
    seq = (codes[:, 0::2] << 4) | codes[:, 1::2]
    qual = _qualities(rng, n, length, params["quality_weights"],
                      float(params["quality_run_break"]))

    # Names, shared by mates: instrument:run:flowcell:lane:tile:x:y.
    lane = rng.integers(1, lanes + 1, n_frag)
    tile = (rng.integers(1, 3, n_frag) * 1000 + rng.integers(1, 7, n_frag)
            * 100 + rng.integers(1, 29, n_frag))
    xs = rng.integers(1000, 32000, n_frag)
    ys = rng.integers(1000, 250000, n_frag)
    head = f"{params['instrument']}:{params['run']}:{params['flowcell']}"
    frag_names = [
        f"{head}:{a}:{b}:{c}:{d}".encode() + b"\x00"
        for a, b, c, d in zip(lane.tolist(), tile.tolist(), xs.tolist(),
                              ys.tolist())
    ]
    names, name_len = bamgen.padded_matrix(
        [frag_names[i] for i in frag_of.tolist()]
    )

    n_cigar = np.where(unmapped, 0, np.where(clip > 0, 2, 1))
    cigar = np.zeros((n, 2), dtype="<u4")
    cigar[:, 0] = np.where(clip > 0, (clip << 4) | 4, (length << 4) | 0)
    cigar[:, 1] = ((length - clip) << 4) | 0

    # Tags in bwa's order: RG NM MD MC MQ AS XS; an unmapped read has RG alone.
    n_sub = sub.sum(axis=1)
    rg_mat, _ = bamgen.padded_matrix(
        [b"RGZ" + r.encode() + b"\x00" for r in read_groups]
    )
    md = [str(length).encode() + b"\x00"] * n
    for i in np.flatnonzero(((n_sub > 0) | (clip > 0)) & mapped).tolist():
        c = int(clip[i])
        md[i] = _md(ref_rows[i, c:], np.flatnonzero(sub[i, c:])) + b"\x00"
    md_mat, md_len = bamgen.padded_matrix(md)

    def if_mapped(width):
        return np.where(mapped, width, 0)

    def byte_tag(name: bytes, values) -> tuple:
        mat = np.empty((n, 4), dtype=np.uint8)
        mat[:, :3] = np.frombuffer(name + b"C", dtype=np.uint8)
        mat[:, 3] = values
        return mat, if_mapped(4)

    def const(text: bytes) -> tuple:
        return (np.tile(np.frombuffer(text, dtype=np.uint8), (n, 1)),
                if_mapped(len(text)))

    fixed = np.zeros(n, dtype=FIXED)
    parts = [
        (fixed.view(np.uint8).reshape(n, 36), None),
        (names, name_len),
        (cigar.view(np.uint8).reshape(n, 8), 4 * n_cigar),
        (seq, None),
        (qual, None),
        (rg_mat[lane[frag_of] - 1], None),
        byte_tag(b"NM", n_sub),
        const(b"MDZ"), (md_mat, if_mapped(md_len)),
        const(b"MCZ%dM\x00" % length),
        byte_tag(b"MQ", mapq),
        byte_tag(b"AS", length - clip - 5 * n_sub),
        byte_tag(b"XS", rng.integers(0, length - 20, n)),
    ]
    rec_len = bamgen.row_lengths(parts)
    ends = np.cumsum(rec_len)
    keep = int(np.searchsorted(ends, target_bytes, side="left")) + 1
    if keep > n:
        raise ValueError("records are shorter than min_record assumes")

    pos = origin + pos0 + clip
    end = np.where(unmapped, pos + 1, pos + length - clip)
    fixed["block_size"] = rec_len - 4
    fixed["ref_id"] = fixed["next_ref_id"] = int(params["contig"])
    fixed["pos"] = pos
    fixed["l_read_name"] = name_len
    fixed["mapq"] = mapq
    fixed["bin"] = bamgen.reg2bin(pos, end)
    fixed["n_cigar"] = n_cigar
    fixed["flag"] = flag
    fixed["l_seq"] = length
    fixed["next_pos"] = origin + mate_pos0
    fixed["tlen"] = tlen

    header = bamgen.bam_header(bamgen.GRCH38, read_groups, "ILLUMINA")
    index = bamgen.write_bam(path, header,
                             bamgen.join_rows(parts, keep).tobytes(),
                             (ends - rec_len)[:keep])
    index["record_bytes_mean"] = float(rec_len[:keep].mean())
    return index
