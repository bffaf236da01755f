"""TPU-vectorized record-boundary checker.

The JAX twin of check/vectorized.py (same two-pass algorithm — see that
module's docstring for the design; the NumPy engine is the differential
oracle for this one). Everything here is shape-static and jit-compiled:

- window size ``W`` and ``reads_to_check`` are static; the *valid* byte count
  ``n`` and ``at_eof`` flag are traced scalars, so one compiled kernel serves
  every window of a file including the tail.
- all integer work is int32 (TPU-native); the reference's JVM int32 wrap
  semantics come for free, truncating division is ``lax.div``.
- the chain walk's logical cursor is clamped into sentinel ranges when a
  pathological length-prefix would overflow int32; affected lanes are
  reported inexact and re-checked on host (exactness is never silently lost).

Mapping to the hardware: the flag pass is elementwise VPU work + two
prefix-sum scans that XLA fuses over the window; the chain walk is
``reads_to_check`` gather rounds. Candidate independence (SURVEY.md §2.8
item 6) is what makes the whole battery data-parallel.
"""

from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from spark_bam_tpu.check.flags import BIT
from spark_bam_tpu.check.vectorized import DEFINITIVE_MASK, ESCAPE_MASK

# Padding beyond any index the flag pass can touch (36 fixed + 255 name +
# 4*65535 cigar + slack), rounded up to a multiple of 1024 (and so of 4,
# for the stride-4 scan). 257*1024 = 263168 ≥ 262431.
PAD = 257 * 1024

_I32 = jnp.int32


def _i32_at(p: jnp.ndarray, w: int) -> jnp.ndarray:
    """Little-endian u32 at every byte offset of the padded buffer."""
    u = (
        p[:-3].astype(jnp.uint32)
        | (p[1:-2].astype(jnp.uint32) << 8)
        | (p[2:-1].astype(jnp.uint32) << 16)
        | (p[3:].astype(jnp.uint32) << 24)
    )
    return u


#: Words a row of the window's word view as the lane stage fetches it
#: (``_lane_words``): the chip's lane width, 512 B, what a row of a rank
#: table's levels is (``RANK_ROW``) and for the same reason.
WORD_ROW = 128

#: Words behind a position that ONE row of the word view holds whatever the
#: position's place in its row: the view is followed by itself from this
#: word on (``_words_at``), so a position in a row's second half has a row
#: that starts half a row later. A site's offsets stay below it.
WORD_REACH = WORD_ROW // 2


def _words_at(p: jnp.ndarray) -> jnp.ndarray:
    """The window's word view: the little-endian int32 at every byte offset
    of the padded buffer, ``N = w + PAD`` of them (whole rows of
    ``WORD_ROW`` at every width the package runs), and behind it the same
    view again from its word ``WORD_REACH`` on: ``(2 N,)``, one dtype, ONE
    materialized array a window. Stage 0 slices its fields from the first
    half; the funnel's lane stage fetches rows of the whole
    (``_lane_words``): a position in the second half of its row reads the
    row of the second half that starts ``WORD_REACH`` words into its own,
    so ONE row holds a lane's words whatever the residue (129 MiB more at
    32 MiB for one fetch a site where the view alone wants two:
    ``PERF.md`` §6, PR 49). A half's last three entries have no four bytes
    of their own behind them (the first half's run on into the second's
    bytes, the second's into zeros); no lane asks for them."""
    total = p.shape[0]
    whole = -(-total // WORD_ROW) * WORD_ROW
    p = jnp.concatenate(
        [p, jnp.zeros(whole + WORD_REACH - total, dtype=p.dtype)])
    # The halves are laid end to end as BYTES, a quarter of the words'
    # size, and the words made of them in one pass (halves of words laid
    # end to end are written twice over).
    both = jnp.concatenate(
        [p[:whole], p[WORD_REACH:], jnp.zeros(3, dtype=p.dtype)])
    return lax.bitcast_convert_type(_i32_at(both, total - PAD), jnp.int32)


def _lane_words(U, pos, offsets: tuple) -> tuple:
    """The words of the word view ``U`` (``_words_at``) at ``pos + off`` for
    every ``off`` of the static ``offsets`` ((K,) int32 each): each the
    element ``jnp.take(view, pos + off, mode="clip")`` gathers from the
    view itself, the ``N - 3`` words of ``U``'s first half that have four
    bytes of their own.

    A gather costs per index and not per byte behind it, so a lane does not
    gather its words: it fetches ONE row of ``WORD_ROW`` words for all its
    offsets (``jnp.take(rows, at, axis=0)``, which the chip's compiler
    lowers to a fetch of 512 B into fast memory), the row its position lies
    in, or, from the second half of that row, the row of ``U``'s second half
    that starts ``WORD_REACH`` words later, and picks each word out of it by
    comparing the columns with the word's own, as ``_ranked_positions``
    takes its word. On a v5e a row of the 32 MiB window's view costs a lane
    8.5 ns where ONE gathered element of it costs 12.9, and a served row's
    1.7 where 7.3 (``PERF.md`` §6, PR 49: ``tools/lane_sweep.py --sweep
    words``): a site's eight words or three ≈ 11 ns where 103 and 38."""
    assert 0 <= min(offsets) and max(offsets) < WORD_REACH, offsets
    held, start, last = _lane_rows(U, pos)
    column = jnp.arange(WORD_ROW, dtype=_I32)[None, :]

    def word(off):
        mine = column == (jnp.clip(pos + off, 0, last) - start)[:, None]
        return jnp.sum(jnp.where(mine, held, _I32(0)), axis=1, dtype=_I32)

    return tuple(word(off) for off in offsets)


def _lane_rows(U, pos):
    """The fetch of ``_lane_words``: ``(held, start, last)``, each lane's row
    of the word view ((K, WORD_ROW): the words at ``start`` .. ``start +
    WORD_ROW - 1``, which hold those at ``pos`` .. ``pos + WORD_REACH - 1``
    at the least) and the last word with four bytes of its own."""
    rows = U.reshape(-1, WORD_ROW)
    half = rows.shape[0] // 2
    last = half * WORD_ROW - 4      # the last word with four bytes of its own
    at = jnp.clip(pos, 0, last)
    within = at % WORD_ROW
    late = within >= WORD_REACH
    held = jnp.take(
        rows, at // WORD_ROW + jnp.where(late, _I32(half), _I32(0)), axis=0,
        mode="clip")
    start = at - within + jnp.where(late, _I32(WORD_REACH), _I32(0))
    return held, start, last


def _ref_pos_bits(idx, pos, c, len_at, b_neg_idx, b_large_idx, b_neg_pos, b_large_pos):
    neg_idx = idx < -1
    large_idx = (~neg_idx) & (idx >= c)
    neg_pos = pos < -1
    idx_ok = (~neg_idx) & (~large_idx)
    large_pos = idx_ok & (~neg_pos) & (idx >= 0) & (pos > len_at)
    return (
        jnp.where(neg_idx, _I32(b_neg_idx), _I32(0))
        | jnp.where(large_idx, _I32(b_large_idx), _I32(0))
        | jnp.where(neg_pos, _I32(b_neg_pos), _I32(0))
        | jnp.where(large_pos, _I32(b_large_pos), _I32(0))
    )


def _compute_flags(p, lengths, num_contigs, n):
    """Flag pass over a (W+PAD,)-byte padded buffer; returns F (the 19-bit
    mask per position). ``remaining``/``body_end`` live in ``_compute_misc``;
    XLA CSEs the overlapping slices."""
    w = p.shape[0] - PAD
    u = _i32_at(p, w)
    i32 = lax.bitcast_convert_type(u, jnp.int32)

    remaining = i32[0:w]
    ref_idx = i32[4: w + 4]
    ref_pos = i32[8: w + 8]
    name_len = p[12: w + 12].astype(_I32)  # i32 & 0xff ⇒ the low byte
    fnc = u[16: w + 16]
    n_cigar = (fnc & 0xFFFF).astype(_I32)
    mapped = ((fnc >> 18) & 1) == 0
    seq_len = i32[20: w + 20]
    next_ref_idx = i32[24: w + 24]
    next_ref_pos = i32[28: w + 28]

    c = num_contigs
    cmax = lengths.shape[0]
    len_r = jnp.take(lengths, jnp.clip(ref_idx, 0, cmax - 1), mode="clip")
    len_n = jnp.take(lengths, jnp.clip(next_ref_idx, 0, cmax - 1), mode="clip")

    F = _ref_pos_bits(
        ref_idx, ref_pos, c, len_r,
        BIT["negativeReadIdx"], BIT["tooLargeReadIdx"],
        BIT["negativeReadPos"], BIT["tooLargeReadPos"],
    )
    F = F | _ref_pos_bits(
        next_ref_idx, next_ref_pos, c, len_n,
        BIT["negativeNextReadIdx"], BIT["tooLargeNextReadIdx"],
        BIT["negativeNextReadPos"], BIT["tooLargeNextReadPos"],
    )

    # Implied-size consistency: JVM int32 wrap + truncation toward zero.
    t = seq_len + _I32(1)
    half = lax.div(t, _I32(2))
    rhs = _I32(32) + name_len + _I32(4) * n_cigar + half + seq_len
    F = F | jnp.where(remaining < rhs, _I32(BIT["tooFewRemainingBytesImplied"]), _I32(0))

    idx = jnp.arange(w, dtype=_I32)
    name_start = idx + 36
    name_end = name_start + name_len
    has_name = name_len >= 2
    F = F | jnp.where(name_len == 0, _I32(BIT["noReadName"]), _I32(0))
    F = F | jnp.where(name_len == 1, _I32(BIT["emptyReadName"]), _I32(0))

    name_eof = has_name & (name_end > n)
    F = F | jnp.where(name_eof, _I32(BIT["tooFewBytesForReadName"]), _I32(0))

    name_in = has_name & (~name_eof)
    last_idx = name_end - 1
    last_byte = jnp.take(p, last_idx, mode="clip")
    non_null = name_in & (last_byte != 0)
    F = F | jnp.where(non_null, _I32(BIT["nonNullTerminatedReadName"]), _I32(0))

    allowed = ((p >= 0x21) & (p <= 0x7E) & (p != 0x40)).astype(_I32)
    acc = jnp.concatenate([jnp.zeros(1, _I32), jnp.cumsum(allowed, dtype=_I32)])
    good = jnp.take(acc, last_idx, mode="clip") - jnp.take(acc, name_start, mode="clip")
    bad_chars = name_in & (~non_null) & (good != name_len - 1)
    F = F | jnp.where(bad_chars, _I32(BIT["nonASCIIReadName"]), _I32(0))

    # Cigar: stride-4 suffix sums of bad-op indicators (op = low nibble of the
    # int's first byte). Ints are readable only when fully inside the valid n.
    j = jnp.arange(p.shape[0], dtype=_I32)
    bad_op = (((p & 0xF) > 8) & (j + 4 <= n)).astype(_I32)
    b4 = bad_op.reshape(-1, 4)
    B = jnp.flip(jnp.cumsum(jnp.flip(b4, 0), axis=0, dtype=_I32), 0).reshape(-1)

    cig_start = name_start + jnp.where(name_in, name_len, _I32(0))
    cig_end = cig_start + _I32(4) * n_cigar
    cig_considered = ~name_eof
    bad_count = jnp.take(B, cig_start, mode="clip") - jnp.take(B, cig_end, mode="clip")
    has_bad = cig_considered & (bad_count > 0)
    F = F | jnp.where(has_bad, _I32(BIT["invalidCigarOp"]), _I32(0))
    cig_eof = cig_considered & (~has_bad) & (cig_end > n)
    F = F | jnp.where(cig_eof, _I32(BIT["tooFewBytesForCigarOps"]), _I32(0))
    empty_ok = cig_considered & (~has_bad) & (~cig_eof) & mapped
    empty_seq = empty_ok & (seq_len == 0)
    empty_cig = empty_ok & (n_cigar == 0)
    some_empty = empty_seq | empty_cig
    # Swapped on purpose: reference quirk (see check/vectorized.py).
    F = F | jnp.where(some_empty & empty_seq, _I32(BIT["emptyMappedCigar"]), _I32(0))
    F = F | jnp.where(some_empty & empty_cig, _I32(BIT["emptyMappedSeq"]), _I32(0))

    few_fixed = idx > n - 36
    F = jnp.where(few_fixed, _I32(BIT["tooFewFixedBlockBytes"]), F)
    return F


def _compute_misc(p, n):
    """remaining + body_end only (the non-flag outputs of the flag pass) —
    what the chain walk needs beside F."""
    w = p.shape[0] - PAD
    u = _i32_at(p, w)
    i32 = lax.bitcast_convert_type(u, jnp.int32)
    remaining = i32[0:w]
    name_len = p[12: w + 12].astype(_I32)
    n_cigar = (u[16: w + 16] & 0xFFFF).astype(_I32)
    idx = jnp.arange(w, dtype=_I32)
    has_name = name_len >= 2
    name_eof = has_name & (idx + 36 + name_len > n)
    name_in = has_name & (~name_eof)
    cig_start = idx + 36 + jnp.where(name_in, name_len, _I32(0))
    few_fixed = idx > n - 36
    body_end = jnp.where(
        few_fixed,
        idx + 36,
        cig_start + jnp.where(~name_eof, _I32(4) * n_cigar, _I32(0)),
    )
    return remaining, body_end


def _misc_at(U, n, pos):
    """``_compute_misc`` evaluated at arbitrary positions (K,) int32.

    The funnel walk needs remaining/body_end only at lane positions, so it
    reads them there instead of materializing two full-width arrays: THREE
    words of the window's word view ``U`` (``_words_at``: the int32 at every
    byte offset), ``remaining = U[pos]``, ``name_len = U[pos + 12] & 0xFF``,
    ``n_cigar = U[pos + 16] & 0xFFFF``, out of the ONE row of the view that
    ``_lane_words`` fetches the lane (three gathered words until PR 49,
    seven gathered bytes until PR 36: a gather costs per index).
    Value-identical to indexing ``_compute_misc``'s outputs at ``pos``
    (``pos`` pre-clipped to [0, w), PAD covers the +16)."""
    remaining, name_len, n_cigar = _lane_words(U, pos, (0, 12, 16))
    name_len = name_len & 0xFF
    n_cigar = n_cigar & 0xFFFF
    has_name = name_len >= 2
    name_eof = has_name & (pos + 36 + name_len > n)
    name_in = has_name & (~name_eof)
    cig_start = pos + 36 + jnp.where(name_in, name_len, _I32(0))
    few_fixed = pos > n - 36
    body_end = jnp.where(
        few_fixed,
        pos + 36,
        cig_start + jnp.where(~name_eof, _I32(4) * n_cigar, _I32(0)),
    )
    return remaining, body_end


# ---------------------------------------------------------------------------
# Candidate funnel: stage 0 = cheap prefilter over every position, stage 1 =
# compact survivors and deep-check only those. The prefilter evaluates ONLY
# fixed-block-derivable bits (remaining bounds, refID ranges, positions
# against the longest contig, name_len sanity, implied-size consistency) — no
# name-byte scans, no cigar scans, no lookup in the contig table — so it is
# provably a superset filter: every bit it can set is also set by the full
# pass at the same position, hence full-pass survivors (F == 0) always pass
# the prefilter. Deep-only tests (a position against its OWN contig's length,
# name charset/termination, cigar ops, empty-mapped) are evaluated once at
# candidate positions via K-sized gathers
# against word-level hierarchical tables (full-width cumsums cost ~60 ms per
# 8 MB window on CPU XLA; packed-u32 popcount prefixes cost ~3 ms).

_U32 = jnp.uint32


def _prefilter_flags(p, lengths, num_contigs, n, U=None):
    """Stage-0 funnel pass: the fixed-block-derivable subset of the 19 bits.

    A subset of ``_compute_flags``'s mask at every position, bit for bit:
    the same tests on the same fields, but a position is held against ONE
    bound, the longest contig, and not its own contig's length (a gather
    costs per index: a lookup at every position was most of a window).
    ``pos > max(len)`` implies ``pos > len[idx]``, so every bit set here
    the full pass sets too; where only the exact test rejects, the position
    survives and ``_deep_flags_at`` looks its length up. The
    ``tooFewFixedBlockBytes`` *overwrite* (not OR) is kept — at few-fixed
    positions the prefilter mask equals the full mask.

    ``U`` is the window's word view (``_words_at``) where the caller holds
    it materialized for the lane stage: the fields are then slices of that
    one array and the words are not assembled a second time."""
    w = p.shape[0] - PAD
    i32 = _words_at(p) if U is None else U
    remaining = i32[0:w]
    ref_idx = i32[4: w + 4]
    ref_pos = i32[8: w + 8]
    name_len = p[12: w + 12].astype(_I32)
    n_cigar = i32[16: w + 16] & 0xFFFF
    seq_len = i32[20: w + 20]
    next_ref_idx = i32[24: w + 24]
    next_ref_pos = i32[28: w + 28]

    c = num_contigs
    # No valid index without a contig: the bound is then never compared.
    contig = jnp.arange(lengths.shape[0], dtype=_I32) < c
    len_max = jnp.max(jnp.where(contig, lengths, _I32(0)))
    F = _ref_pos_bits(
        ref_idx, ref_pos, c, len_max,
        BIT["negativeReadIdx"], BIT["tooLargeReadIdx"],
        BIT["negativeReadPos"], BIT["tooLargeReadPos"],
    )
    F = F | _ref_pos_bits(
        next_ref_idx, next_ref_pos, c, len_max,
        BIT["negativeNextReadIdx"], BIT["tooLargeNextReadIdx"],
        BIT["negativeNextReadPos"], BIT["tooLargeNextReadPos"],
    )
    t = seq_len + _I32(1)
    half = lax.div(t, _I32(2))
    rhs = _I32(32) + name_len + _I32(4) * n_cigar + half + seq_len
    F = F | jnp.where(remaining < rhs, _I32(BIT["tooFewRemainingBytesImplied"]), _I32(0))
    F = F | jnp.where(name_len == 0, _I32(BIT["noReadName"]), _I32(0))
    F = F | jnp.where(name_len == 1, _I32(BIT["emptyReadName"]), _I32(0))
    idx = jnp.arange(w, dtype=_I32)
    few_fixed = idx > n - 36
    F = jnp.where(few_fixed, _I32(BIT["tooFewFixedBlockBytes"]), F)
    return F


def _pack_bits(bits):
    """Pack a bool vector into uint32 words (lane = bit index), zero-padding
    the tail to a word boundary."""
    length = bits.shape[0]
    full = -(-length // 32) * 32
    if full != length:
        bits = jnp.concatenate([bits, jnp.zeros(full - length, dtype=bits.dtype)])
    lanes = jnp.arange(32, dtype=_U32)
    return jnp.sum(bits.reshape(-1, 32).astype(_U32) << lanes[None, :], axis=1)


def _funnel_tables(p, n):
    """Word-level hierarchical prefix tables for the deep checks: packed
    indicator bitmasks + exclusive per-word popcount prefixes. Exact
    per-position prefix counts are recovered at query time with one masked
    popcount, so no full-width cumsum is ever materialized."""
    allowed = (p >= 0x21) & (p <= 0x7E) & (p != 0x40)
    nwords = _pack_bits(allowed)
    nwpc = lax.population_count(nwords).astype(_I32)
    nwpre = jnp.cumsum(nwpc) - nwpc

    j = jnp.arange(p.shape[0], dtype=_I32)
    bad_op = ((p & 0xF) > 8) & (j + 4 <= n)
    cwords = _pack_bits(bad_op)
    cm = _U32(0x11111111)
    wpc4 = jnp.stack(
        [lax.population_count(cwords & (cm << c)).astype(_I32) for c in range(4)],
        axis=1,
    )
    cwpre4 = (jnp.cumsum(wpc4, axis=0) - wpc4).reshape(-1)  # flat: wi*4 + class
    return nwords, nwpre, cwords, cwpre4


def _allowed_before(nwords, nwpre, q):
    """# allowed read-name chars at byte positions < q."""
    wi = q >> 5
    r = (q & 31).astype(_U32)
    word = jnp.take(nwords, wi, mode="clip")
    part = lax.population_count(word & ((_U32(1) << r) - _U32(1)))
    return jnp.take(nwpre, wi, mode="clip") + part.astype(_I32)


def _badops_before(cwords, cwpre4, q, c):
    """# bad cigar-op bytes j < q with j ≡ c (mod 4)."""
    wi = q >> 5
    r = (q & 31).astype(_U32)
    word = jnp.take(cwords, wi, mode="clip")
    cmask = _U32(0x11111111) << c.astype(_U32)
    part = lax.population_count(word & cmask & ((_U32(1) << r) - _U32(1)))
    return jnp.take(cwpre4, wi * 4 + c, mode="clip") + part.astype(_I32)


def _deep_flags_at(p, U, lengths, num_contigs, n, tables, pos):
    """The full 19-bit mask of ``_compute_flags`` at arbitrary positions
    (K,), via K-sized reads + the hierarchical tables. The fixed block's
    eight fields are EIGHT words of the window's word view ``U``
    (``_words_at``), all out of the ONE row of it that ``_lane_words``
    fetches the lane (eight gathered words until PR 49; a 36-byte slab of
    byte indices until PR 36; bytes 32-35 were never read); the name's last
    byte is the one gather left that reads the bytes ``p``.
    Field-for-field identical to the full pass (same overwrite, same
    reference quirks).

    Returns ``(F, remaining, body_end)``: the last two are ``_misc_at``'s
    at ``pos``, from fields read here already, so the walk's first step
    (which stands on the lane's own position) fetches nothing."""
    nwords, nwpre, cwords, cwpre4 = tables
    total = p.shape[0]
    pc = jnp.clip(pos, 0, total - 36)

    (remaining, ref_idx, ref_pos, name_len, fnc, seq_len, next_ref_idx,
     next_ref_pos) = _lane_words(U, pc, (0, 4, 8, 12, 16, 20, 24, 28))
    name_len = name_len & 0xFF
    n_cigar = fnc & 0xFFFF
    mapped = ((fnc >> 18) & 1) == 0

    c = num_contigs
    cmax = lengths.shape[0]
    len_r = jnp.take(lengths, jnp.clip(ref_idx, 0, cmax - 1), mode="clip")
    len_n = jnp.take(lengths, jnp.clip(next_ref_idx, 0, cmax - 1), mode="clip")
    F = _ref_pos_bits(
        ref_idx, ref_pos, c, len_r,
        BIT["negativeReadIdx"], BIT["tooLargeReadIdx"],
        BIT["negativeReadPos"], BIT["tooLargeReadPos"],
    )
    F = F | _ref_pos_bits(
        next_ref_idx, next_ref_pos, c, len_n,
        BIT["negativeNextReadIdx"], BIT["tooLargeNextReadIdx"],
        BIT["negativeNextReadPos"], BIT["tooLargeNextReadPos"],
    )
    t = seq_len + _I32(1)
    half = lax.div(t, _I32(2))
    rhs = _I32(32) + name_len + _I32(4) * n_cigar + half + seq_len
    F = F | jnp.where(remaining < rhs, _I32(BIT["tooFewRemainingBytesImplied"]), _I32(0))
    F = F | jnp.where(name_len == 0, _I32(BIT["noReadName"]), _I32(0))
    F = F | jnp.where(name_len == 1, _I32(BIT["emptyReadName"]), _I32(0))

    name_start = pos + 36
    name_end = name_start + name_len
    has_name = name_len >= 2
    name_eof = has_name & (name_end > n)
    F = F | jnp.where(name_eof, _I32(BIT["tooFewBytesForReadName"]), _I32(0))
    name_in = has_name & (~name_eof)
    last_idx = name_end - 1
    last_byte = jnp.take(p, jnp.clip(last_idx, 0, total - 1), mode="clip")
    non_null = name_in & (last_byte != 0)
    F = F | jnp.where(non_null, _I32(BIT["nonNullTerminatedReadName"]), _I32(0))
    good = (
        _allowed_before(nwords, nwpre, jnp.clip(last_idx, 0, total - 1))
        - _allowed_before(nwords, nwpre, jnp.clip(name_start, 0, total - 1))
    )
    bad_chars = name_in & (~non_null) & (good != name_len - 1)
    F = F | jnp.where(bad_chars, _I32(BIT["nonASCIIReadName"]), _I32(0))

    cig_start = name_start + jnp.where(name_in, name_len, _I32(0))
    cig_end = cig_start + _I32(4) * n_cigar
    cig_considered = ~name_eof
    ccls = cig_start & 3
    bad_count = (
        _badops_before(cwords, cwpre4, jnp.clip(cig_end, 0, total - 1), ccls)
        - _badops_before(cwords, cwpre4, jnp.clip(cig_start, 0, total - 1), ccls)
    )
    has_bad = cig_considered & (bad_count != 0)
    F = F | jnp.where(has_bad, _I32(BIT["invalidCigarOp"]), _I32(0))
    cig_eof = cig_considered & (~has_bad) & (cig_end > n)
    F = F | jnp.where(cig_eof, _I32(BIT["tooFewBytesForCigarOps"]), _I32(0))
    empty_ok = cig_considered & (~has_bad) & (~cig_eof) & mapped
    empty_seq = empty_ok & (seq_len == 0)
    empty_cig = empty_ok & (n_cigar == 0)
    some_empty = empty_seq | empty_cig
    # Swapped on purpose: reference quirk (see check/vectorized.py).
    F = F | jnp.where(some_empty & empty_seq, _I32(BIT["emptyMappedCigar"]), _I32(0))
    F = F | jnp.where(some_empty & empty_cig, _I32(BIT["emptyMappedSeq"]), _I32(0))

    few_fixed = pos > n - 36
    F = jnp.where(few_fixed, _I32(BIT["tooFewFixedBlockBytes"]), F)
    body_end = jnp.where(
        few_fixed, pos + 36, jnp.where(name_eof, cig_start, cig_end))
    return F, remaining, body_end


#: Keys a row of a rank table's levels (``_rank_table``): the chip's lane
#: width, so a lane's step down a level is ONE fetch of 512 B. On a v5e such
#: a fetch costs 1.6 ns a lane where a gathered element of the same 4 MiB
#: costs 7.2; a row of 32 keys costs the same to fetch, one of 1,024 keys
#: 4.6, and no fan-out swept beat this one (``PERF.md`` §6, PR 48:
#: ``tools/lane_sweep.py``).
RANK_ROW = 128

#: Most keys the level at the top, which every lane compares itself with
#: whole and fetches nothing for: a served row's 2**15 words are one level
#: of 256 rows under a top of 256 keys.
RANK_TOP = 1024

_NO_RANK = jnp.iinfo(jnp.int32).max     # a key no rank reaches: the padding


class _RankTable(NamedTuple):
    """The word-level rank table of a mask: its bits packed to u32 words,
    the population, and the words' inclusive popcount prefix (a tiny cumsum)
    as the LEVELS of a search tree, top first, each ``(rows, keys a row)``.
    The last level is the prefix itself in rows of ``RANK_ROW`` keys (padded
    with a key no rank reaches); a level above holds the last key of every
    row below, in such rows too; the top is one row of at most ``RANK_TOP``
    keys. A table of no more words than that is its top alone. The words lie
    in the last level's rows, a word where its key is.
    ``_ranked_positions`` finds the k-th set bit from it without any
    full-width cumsum/sort/scatter."""
    words: jnp.ndarray
    levels: tuple
    n_set: jnp.ndarray


def _rank_table(mask) -> _RankTable:
    words = _pack_bits(mask)
    keys = jnp.cumsum(lax.population_count(words).astype(_I32))
    n_set = keys[-1]
    levels = []
    while keys.shape[0] > RANK_TOP:
        rows = -(-keys.shape[0] // RANK_ROW)
        keys = jnp.pad(
            keys, (0, rows * RANK_ROW - keys.shape[0]),
            constant_values=_NO_RANK).reshape(rows, RANK_ROW)
        levels.append(keys)
        keys = keys[:, -1]
    levels.append(keys[None, :])
    last = levels[0]
    words = jnp.pad(words, (0, last.size - words.shape[0])).reshape(last.shape)
    return _RankTable(words, tuple(reversed(levels)), n_set)


def _bit_of_rank(word, r):
    """The bit (0..31) that is the ``r``-th set one of its ``word``, ``r``
    from 1 ((K,) each; 0 where the word has no such bit): masked popcounts."""
    lanes = jnp.arange(32, dtype=_U32)
    incl = (_U32(2) << lanes) - _U32(1)           # inclusive masks (lane 31 wraps to ~0)
    pcnt = lax.population_count(word[:, None] & incl[None, :])
    hit = (pcnt == r[:, None]) & (((word[:, None] >> lanes[None, :]) & 1) == 1)
    return jnp.argmax(hit, axis=1).astype(_I32)


def _rows_at(level, at):
    """Each lane's row of a level (``at``: (K,) rows): one fetch a lane. A
    level of one row, the top, is every lane's and is fetched by none."""
    if level.shape[0] == 1:
        return level
    return jnp.take(level, at, axis=0, mode="clip")


def _ranked_positions(table, k):
    """Positions of the set bits of ranks ``k`` ((K,) int32, 0-based), -1
    outside the population (a rank below zero too): find the word holding
    the k-th survivor, then locate the in-word bit with masked popcounts.

    The word is the first whose inclusive prefix reaches ``k + 1``
    (``searchsorted``'s ``side="left"``), found down the table's levels: a
    lane counts the keys below its target in ONE row a level, and the count
    is its row in the next. The largest key below the target, kept on the
    way down, is the prefix before the word, and the word comes out of its
    row as its key did. So a 32 MiB window's 2**20 words cost a lane three
    row fetches (two of keys, one of words) and a served row's 2**15 two,
    where a binary search gathered an element for every halving and two
    more (23 and 18): on a v5e 8 ns a lane where it was 165 (``PERF.md``
    §6, PR 48)."""
    words, levels, n_set = table
    target = (k + 1)[:, None]
    at = wi = excl = _I32(0)
    for level in levels:
        # Beyond the population the count runs off the level's end.
        at = jnp.minimum(wi, level.shape[0] - 1)
        row = _rows_at(level, at)
        below = row < target
        wi = at * level.shape[1] + jnp.sum(below, axis=1, dtype=_I32)
        excl = jnp.maximum(
            excl, jnp.max(jnp.where(below, row, _I32(0)), axis=1))
    r = k + 1 - excl                              # target rank within word: 1..32
    column = jnp.arange(words.shape[1], dtype=_I32)[None, :]
    mine = column == (wi - at * words.shape[1])[:, None]
    word = jnp.sum(
        jnp.where(mine, _rows_at(words, at), _U32(0)), axis=1, dtype=_U32)
    return jnp.where(
        (k >= 0) & (k < n_set), wi * 32 + _bit_of_rank(word, r), _I32(-1))


def lane_capacity(w: int) -> int:
    """Lanes the check of a ``w``-byte window can hold: a window with more
    stage-0 survivors escapes whole to the host engine."""
    return max(w // 32, 4096)


#: Most lanes a block of the funnel's lane stage (``lane_block``): the
#: block of every window of 2 MiB and more, the count's and check-bam's
#: 32 MiB among them. The stage runs ``ceil(n_survivors / block)`` blocks, a
#: number read on the device from the window itself, so a window pays for
#: its survivors rounded up to this and not for the worst window the format
#: allows. Swept on the chip at 32 MiB (``PERF.md`` §6, PR 40; programs
#: alone, ms at 16,384 / 8,192 / 4,096 / 2,048 / 1,024): a long-read window
#: of 965 survivors 29.4 / 21.2 / 17.0 / 14.8 / 13.7, a short-read window of
#: 87,178 98.9 / 90.8 / 91.1 / 89.9 / 93.0, check-bam's step of three rows
#: 363.3 / 342.7 / 340.2 / 335.1 / 343.6. A lane run cost ≈ 0.9 µs live and
#: 1.05 dead then, and a block ≈ 0.07 ms besides (its hundred-odd operations'
#: fixed cost), which is what 1,024 loses to 2,048 on a window of 87,000
#: survivors (86 blocks against 43) and wins on one of 965 (a millisecond of
#: dead lanes). Since PR 48 a lane, live or dead, costs 0.16 µs less (the
#: compaction's search, ``_ranked_positions``: the short-read window above
#: 89.8 → 75.6 ms for the same 88,064 lanes, so ≈ 0.74 µs live), and since
#: PR 49 0.30 µs less again (its words by the row, ``_lane_words``: 75.9 →
#: 49.3 ms, ≈ 0.44 µs live); the widths were not swept again.
LANE_BLOCK = 2048

#: Fewest lanes a block: a served row's (1 MiB). Swept at that width alone
#: (PR 34): the narrowest block run was 512, no faster than this.
LANE_BLOCK_MIN = 1024


def lane_block(w: int) -> int:
    """Lanes a block of the lane stage of a ``w``-byte window: a 32nd of
    the window's capacity within ``[LANE_BLOCK_MIN, LANE_BLOCK]``, so 1,024
    at a served row's 1 MiB and 2,048 from 2 MiB up, at the count's and
    check-bam's 32 MiB. A block costs its lanes and little besides, so a
    row wants the block that leaves the fewest dead lanes until the blocks'
    own fixed cost shows. At 1 MiB (a served step of eight rows of ≈ 2,970
    survivors: 33.4 ms at 1,024, 40.1 at 2,048 and at 4,096, 73.6 at 8,192,
    140.8 at 16,384; ``PERF.md`` §6, PR 34, before PR 36 halved a lane's
    cost) that is 1,024, where a row runs three blocks; at 32 MiB, where a
    short-read window runs its survivors in 43 blocks or in 86, it is 2,048
    (``LANE_BLOCK`` has the sweep)."""
    return max(LANE_BLOCK_MIN, min(LANE_BLOCK, lane_capacity(w) // 32))


#: Slots of the count's escape list: the window positions of the owned lanes
#: whose chains ran past the buffer, which the stream resolves on the host
#: from the bytes the following windows bring. A real window holds tens (the
#: ``reads_to_check`` records before one longer than the halo, and that
#: one); a window with more reports an overflow and the pass starts over.
ESCAPE_LIST = 64


def _flag_stage(
    padded, lengths, num_contigs, n, at_eof, funnel: bool,
):
    """Stage 0, position-wide and run once a window: the flag pass (the
    prefilter under the funnel, over the word view), the survivors it
    leaves and every non-survivor's verdict straight from F. Also
    ``misc_at``, which reads remaining/body_end at lane positions for the
    walk, and under the funnel ``U``, the window's word view whose rows it
    and the deep flags fetch (``_lane_words``)."""
    w = padded.shape[0] - PAD
    U = None
    with jax.named_scope("flags"):
        if funnel:
            # The lane stage pays per gather index, so it reads 32-bit
            # words, by the row: the int32 at every byte offset, ONE
            # materialized array a window (the barrier: left to fuse, each
            # lane's fetch would assemble its words from byte gathers
            # again), which the prefilter reads its fields from too (its
            # first half: ``_words_at``). The lanes see it in rows of
            # ``WORD_ROW``: the same array, no copy.
            U = lax.optimization_barrier(_words_at(padded))
            F = _prefilter_flags(padded, lengths, num_contigs, n, U)
        else:
            F = _compute_flags(padded, lengths, num_contigs, n)
    if funnel:
        # Lane-width misc: the walk only ever reads remaining/body_end at
        # lane positions — full-width materialization is the single
        # biggest non-prefilter cost on the funnel path.
        misc_at = functools.partial(_misc_at, U, n)
    else:
        with jax.named_scope("flags"):
            remaining, body_end = _compute_misc(padded, n)

        def misc_at(pi):
            return (
                jnp.take(remaining, pi, mode="clip"),
                jnp.take(body_end, pi, mode="clip"),
            )

    in_range = jnp.arange(w, dtype=_I32) < n
    definitive0 = F & DEFINITIVE_MASK
    boundary0 = F & ESCAPE_MASK
    survivor = (F == 0) & in_range

    # --- non-survivor resolution straight from F -------------------------
    # (Under the funnel, F here is the prefilter mask: positions it rejects
    # resolve identically — every prefilter bit is definitive except the
    # tooFewFixedBlockBytes overwrite, where prefilter == full mask.)
    fail0 = (F != 0) & ((definitive0 != 0) | (at_eof & (boundary0 != 0)))
    esc0 = (F != 0) & (~at_eof) & (definitive0 == 0) & (boundary0 != 0)
    inexact0 = (F != 0) & (~at_eof) & (definitive0 != 0) & (boundary0 != 0)

    res0 = jnp.where(fail0, jnp.int8(-1), jnp.int8(0))
    res0 = jnp.where(esc0, jnp.int8(2), res0)
    fail_mask0 = jnp.where(fail0, F, _I32(0))
    return {
        "F": F, "U": U, "misc_at": misc_at, "survivor": survivor, "res0": res0,
        "fail_mask0": fail_mask0, "inexact0": inexact0,
    }


def _deep_lanes(padded, U, lengths, num_contigs, n, tables, cand, live):
    """Stage 1 at the lanes ``cand``: the full 19-bit flags once at
    candidate positions. Returns the targets of their scatter ONTO the
    position-wide prefilter mask (``_lane_flags``), so the chain walk looks
    a position's flags up once (dead lanes target the pad slot ``w``), and
    ``(masks, remaining, body_end)``: what the walk's first step would look
    up at the lane's own position. A walked position either passes the
    prefilter (then its deep mask is there — deep-failing candidates
    resolve inside the walk's step logic exactly like fail0/esc0/inexact0)
    or fails it (then the prefilter bits alone are verdict-equivalent)."""
    w = padded.shape[0] - PAD
    with jax.named_scope("flags"):
        F_cand, remaining, body_end = _deep_flags_at(
            padded, U, lengths, num_contigs, n, tables,
            jnp.where(live, cand, _I32(0)),
        )
    with jax.named_scope("funnel"):
        F_cand = jnp.where(live, F_cand, _I32(0))
        tgt0 = jnp.where(live, cand, _I32(w))
    return tgt0, (F_cand, remaining, body_end)


def _lane_flags(F):
    """The array the deep masks are scattered onto: the prefilter's ``F``
    and the pad slot ``w`` for dead lanes. A survivor has ``F == 0`` by
    definition and the deep masks land at survivors only, so once every
    block is in, the array is the prefilter's mask where it rejects and the
    deep mask where it passed, at every position."""
    return jnp.concatenate([F, jnp.zeros(1, dtype=F.dtype)])


# Sentinel bounds for the logical cursor: anything outside [0, n] behaves
# identically (it can never equal the physical cursor at EOF), so clamping is
# exact unless the cursor needs to *re-enter* range — tracked per lane.
def _walk_lanes(
    cand, live, flags_lookup, misc_at, n, at_eof, w: int,
    reads_to_check: int, unroll, first=None,
):
    """The chain walk over the lanes ``cand`` (any number of them: lanes are
    independent), ``reads_to_check`` gather rounds; per-lane verdicts.
    With ``first`` (``_deep_lanes``: the flags, remaining and body_end at
    the lanes' own positions) the first round takes them and gathers
    nothing: ``reads_to_check - 1`` gather rounds."""
    capacity = cand.shape[0]
    logical = jnp.where(live, cand, _I32(0))
    physical = logical
    l_overflowed = jnp.zeros(capacity, dtype=bool)
    res = jnp.where(live, jnp.int8(0), jnp.int8(-1))
    fail_mask = jnp.zeros(capacity, dtype=_I32)
    reads_before = jnp.zeros(capacity, dtype=_I32)
    reads_parsed = jnp.zeros(capacity, dtype=_I32)
    exact = jnp.ones(capacity, dtype=bool)

    def step(state, step_idx, looked=None):
        logical, physical, l_overflowed, res, fail_mask, reads_before, reads_parsed, exact = state
        run = res == 0

        # --- EOF at record edge (zero bytes): eager/Checker.scala:36-39 ---
        at_end = run & (physical >= n)
        edge = (physical == logical) & (~l_overflowed) & (step_idx > 0)
        maybe_edge = l_overflowed & (step_idx > 0)  # can't trust comparison
        eof_ok = at_end & edge & at_eof
        eof_bad = at_end & (~edge) & (~maybe_edge) & at_eof
        eof_esc = at_end & ((~at_eof) | maybe_edge)
        res = jnp.where(eof_ok, jnp.int8(1), res)
        reads_parsed = jnp.where(eof_ok, step_idx, reads_parsed)
        res = jnp.where(eof_bad, jnp.int8(-1), res)
        fail_mask = jnp.where(eof_bad, _I32(BIT["tooFewFixedBlockBytes"]), fail_mask)
        reads_before = jnp.where(eof_bad, step_idx, reads_before)
        res = jnp.where(eof_esc, jnp.int8(2), res)
        run = res == 0

        if looked is None:
            f = flags_lookup(jnp.clip(physical, 0, w - 1))
        else:
            f, rem, b_end = looked
        f = jnp.where(run, f, _I32(0))
        definitive = f & DEFINITIVE_MASK
        boundary = f & ESCAPE_MASK

        fail = run & ((definitive != 0) | (at_eof & (boundary != 0)))
        esc = run & (~at_eof) & (definitive == 0) & (boundary != 0)
        inexact = run & (~at_eof) & (definitive != 0) & (boundary != 0)
        res = jnp.where(fail, jnp.int8(-1), res)
        fail_mask = jnp.where(fail, f, fail_mask)
        reads_before = jnp.where(fail, step_idx, reads_before)
        res = jnp.where(esc, jnp.int8(2), res)
        exact = exact & (~inexact)
        run = res == 0

        ok = run & (f == 0)
        if looked is None:
            # Here and not beside the flags' lookup: the order the program
            # without the funnel has always lowered in.
            rem, b_end = misc_at(jnp.clip(physical, 0, w - 1))
        # int32-safe logical advance: out-of-range values collapse to
        # sentinels (n+64 / -64) that preserve all future comparisons unless
        # the cursor would legitimately re-enter [0, n] — flagged for host
        # re-check via l_overflowed.
        big = rem > n + 64
        small = rem < -(n + 64)
        rem_c = jnp.clip(rem, -(n + 64), n + 64)
        next_logical = logical + 4 + rem_c
        next_logical = jnp.clip(next_logical, -(n + 64), n + 64)
        overflow_now = big | small | (logical + 4 + rem_c != next_logical)
        next_physical = jnp.maximum(b_end, next_logical)
        next_physical = jnp.minimum(next_physical, n)
        # (A chain stepping to/past the buffer end resolves at the next
        #  iteration's EOF check: success/fail when at_eof, escape otherwise.)
        logical = jnp.where(ok, next_logical, logical)
        physical = jnp.where(ok, next_physical, physical)
        l_overflowed = l_overflowed | (ok & overflow_now)
        return (
            logical, physical, l_overflowed, res, fail_mask,
            reads_before, reads_parsed, exact,
        ), None

    state = (logical, physical, l_overflowed, res, fail_mask, reads_before, reads_parsed, exact)
    with jax.named_scope("chain_walk"):
        rounds = jnp.arange(reads_to_check, dtype=_I32)
        if first is not None:
            state, _ = step(state, rounds[0], first)
            rounds = rounds[1:]
        state, _ = lax.scan(step, state, rounds, unroll=unroll)
    logical, physical, l_overflowed, res, fail_mask, reads_before, reads_parsed, exact = state

    full_chain = live & (res == 0)
    res = jnp.where(full_chain, jnp.int8(1), res)
    reads_parsed = jnp.where(full_chain, _I32(reads_to_check), reads_parsed)
    return {
        "res": res, "fail_mask": fail_mask, "reads_before": reads_before,
        "reads_parsed": reads_parsed, "exact": exact,
    }


class _LaneBlocks(NamedTuple):
    """Stage 0 and pass 1 of the funnel's lane stage (``_deep_blocks``), as
    pass 2 (``_walk_blocks``) and its consumer take them."""
    S: dict                  # stage 0 (``_flag_stage``)
    F_lane: jnp.ndarray      # (w + 1,) F, the deep masks at the survivors
    cands: jnp.ndarray       # (max_blocks * block,) lane positions by rank
    first: tuple             # as wide: flags, remaining, body_end at them
    blocks: jnp.ndarray      # () blocks holding the survivors: the trip count
    block: int
    overflow: jnp.ndarray    # () more survivors than ``lane_capacity``
    n_survivors: jnp.ndarray

    @property
    def lanes(self):
        """Lanes the stage runs: whole blocks."""
        return self.blocks * _I32(self.block)


def _deep_blocks(
    padded, lengths, num_contigs, n, at_eof, block: int | None,
) -> _LaneBlocks:
    """Stage 0 and pass 1 of THE lane stage of the funnel, sized by the
    window's own survivors; ``_walk_blocks`` is pass 2.

    Stage 0 (``_flag_stage``) runs once. The lane stage (compaction → deep
    flags → their scatter → the walk → what the consumer keeps) runs in
    blocks of ``lane_block(w)`` lanes, ``ceil(n_survivors / block)`` of
    them: a trip count the device reads from the window. Pass 1 compacts
    block k (ranks ``k·block …``), deep-checks it (eight words a lane out
    of one fetched row of stage 0's word view ``U``) and scatters its masks
    onto the carried position-wide ``F_lane``, which starts as the
    prefilter's ``F`` (``_lane_flags``), keeping what the walk's first step
    needs lane-wide beside the positions; only then can pass 2 walk, since
    a lane's chain visits survivors of later blocks. Lanes are
    independent, so every verdict is what ONE stage of ``lane_capacity``
    lanes gives; a window over that capacity runs every block and reports
    ``overflow`` as that stage does. Under ``vmap`` the trip count is the
    rows' maximum (a row's blocks beyond its own hold dead lanes only).

    The block is the one static width here, and what the window pays for:
    its survivors rounded up to whole blocks (``LANE_BLOCK`` has the sweep
    and what a dead lane and a block cost). One width a window, so one copy
    of each loop's body: wide blocks followed by a remainder in narrow ones
    was swept against (PR 40) and not needed."""
    w = padded.shape[0] - PAD
    S = _flag_stage(padded, lengths, num_contigs, n, at_eof, True)
    capacity = lane_capacity(w)
    block = min(block or lane_block(w), capacity)
    max_blocks = -(-capacity // block)
    with jax.named_scope("funnel"):
        # The barrier makes the survivors one materialized (W,) mask. Left to
        # fuse, the word packing re-derives F in its own (W/32, 32) shape
        # from nine position-wide operands, each laid out again at four
        # times its bytes (32 of a tile's 128 lanes): 4.6 GiB of a v5e's
        # temporaries, and the relayouts' time.
        table = _rank_table(lax.optimization_barrier(S["survivor"]))
        tables = _funnel_tables(padded, n)
    n_survivors = table.n_set
    overflow = n_survivors > capacity
    blocks = jnp.minimum(
        lax.div(n_survivors + _I32(block - 1), _I32(block)), _I32(max_blocks))
    ranks = jnp.arange(block, dtype=_I32)

    def deep_block(k, carry):
        F_lane, cands, first = carry
        with jax.named_scope("funnel"):
            cand = _ranked_positions(table, k * block + ranks)
        tgt0, at_cand = _deep_lanes(
            padded, S["U"], lengths, num_contigs, n, tables, cand, cand >= 0)
        with jax.named_scope("funnel"):
            return (
                F_lane.at[tgt0].set(at_cand[0], mode="drop"),
                lax.dynamic_update_slice(cands, cand, (k * block,)),
                tuple(lax.dynamic_update_slice(buf, x, (k * block,))
                      for buf, x in zip(first, at_cand)),
            )

    with jax.named_scope("funnel"):
        F0 = _lane_flags(S["F"])
        lane_wide = jnp.zeros(max_blocks * block, dtype=_I32)
    F_lane, cands, first = lax.fori_loop(
        0, blocks, deep_block,
        (F0, jnp.full(max_blocks * block, -1, dtype=_I32), (lane_wide,) * 3),
    )
    return _LaneBlocks(
        S, F_lane, cands, first, blocks, block, overflow, n_survivors)


def _walk_blocks(
    B: _LaneBlocks, n, at_eof, reads_to_check: int, fold, init,
    walk_scope: str | None = None,
):
    """Pass 2 of the lane stage: walk block k and hand its lanes to the
    stage's consumer, ``fold(carry, k, cand, live, lanes) -> carry`` with
    ``lanes`` the per-lane verdicts of ``_walk_lanes``. The count folds them
    into its scalars and its escape list (``_count_lanes``); ``check_window``
    keeps them, lane for lane (``_check_lanes``); the load parses and
    filters the records among them (``_load_lanes``).

    The count and ``check_window`` stand under ``check`` whole, fold and
    all. A consumer whose fold is no part of the check (the load's) calls
    from outside it and names the scope the walk goes under
    (``walk_scope``): its fold then runs under its own names alone."""
    w = B.F_lane.shape[0] - 1
    walk = (contextlib.nullcontext if walk_scope is None
            else functools.partial(jax.named_scope, walk_scope))

    def flags_lookup(pi):
        # ONE gather a step: the merged array (``_lane_flags``).
        return jnp.take(B.F_lane, pi, mode="clip")

    def walk_block(k, carry):
        with walk():
            with jax.named_scope("chain_walk"):
                cand, *first = (
                    lax.dynamic_slice(buf, (k * B.block,), (B.block,))
                    for buf in (B.cands, *B.first))
                live = cand >= 0
            lanes = _walk_lanes(
                cand, live, flags_lookup, B.S["misc_at"], n, at_eof, w,
                reads_to_check, unroll=True, first=tuple(first),
            )
        if walk_scope is not None:
            return fold(carry, k, cand, live, lanes)
        with jax.named_scope("chain_walk"):
            return fold(carry, k, cand, live, lanes)

    return lax.fori_loop(0, B.blocks, walk_block, init)


#: What ``_walk_lanes`` says of a lane, in the order ``_check_lanes`` keeps it.
_LANE_KEYS = ("res", "fail_mask", "reads_before", "reads_parsed", "exact")


@jax.named_scope("check")
def _check_lanes(
    padded, lengths, num_contigs, n, at_eof,
    reads_to_check: int = 10, funnel: bool = False,
    block: int | None = None,
):
    """Flag pass + survivor compaction + lane walk, WITHOUT the full-width
    scatters: the core of ``check_window``, which scatters the lanes back
    to (W,) arrays (``_scatter_lanes``).

    Under the funnel the lanes come from the one lane stage there is
    (``_deep_blocks`` / ``_walk_blocks``), as many blocks as hold the row's
    survivors: each block's verdicts go into lane-wide buffers beside its
    positions, dead lanes (``cand < 0``: the block's tail, the blocks never
    run) into the scatter's pad slot. The verdict code rides the loop as
    int32: int8 is the scatter's own in this program, which is how a trace
    tells the scatter's nameless expansion from the walk
    (``bench/readers/trace_orphans``).

    Without the funnel (``make_shard_map_full_step`` and the two check
    steps: exact masks for forensics, and the rolled scan that is the
    funnel's A/B baseline) the stage is ONE of the window's whole capacity,
    as it always was: no cell runs it and its masks are another contract."""
    w = padded.shape[0] - PAD
    if funnel:
        B = _deep_blocks(padded, lengths, num_contigs, n, at_eof, block)
        S, cand = B.S, B.cands

        def keep(carry, k, _cand, _live, lanes):
            return tuple(
                lax.dynamic_update_slice(
                    buf, lanes[key].astype(buf.dtype), (k * B.block,))
                for buf, key in zip(carry, _LANE_KEYS))

        kept = _walk_blocks(B, n, at_eof, reads_to_check, keep, tuple(
            jnp.zeros(cand.shape, dtype=bool if key == "exact" else _I32)
            for key in _LANE_KEYS))
        lanes = dict(zip(_LANE_KEYS, kept))
        overflow, n_survivors, ran = B.overflow, B.n_survivors, B.lanes
    else:
        S = _flag_stage(padded, lengths, num_contigs, n, at_eof, False)
        F, survivor = S["F"], S["survivor"]
        capacity = lane_capacity(w)
        # No funnel: the survivors' compaction is the walk's own prologue.
        with jax.named_scope("chain_walk"):
            n_survivors = jnp.sum(survivor.astype(_I32))
            (cand,) = jnp.nonzero(survivor, size=capacity, fill_value=-1)
            cand = cand.astype(_I32)
        overflow = n_survivors > capacity

        def flags_lookup(pi):
            return jnp.take(F, pi, mode="clip")

        # Rolled (under the funnel the walk is unrolled: the loop-carried
        # scan blocks XLA from fusing the lane gathers with their
        # producers), so the funnel A/B baseline measures the original
        # kernel.
        lanes = _walk_lanes(
            cand, cand >= 0, flags_lookup, S["misc_at"], n, at_eof, w,
            reads_to_check, unroll=1,
        )
        ran = _I32(capacity)
    return {
        "survivor": S["survivor"], "res0": S["res0"],
        "fail_mask0": S["fail_mask0"], "inexact0": S["inexact0"],
        "cand": cand, **lanes,
        "overflow": overflow, "n_survivors": n_survivors, "lanes": ran,
    }


@jax.named_scope("check")
def _count_lanes(
    padded, lengths, num_contigs, n, at_eof, lo, own,
    reads_to_check: int, block: int | None = None, escapes: int = 0,
):
    """The funnelled check reduced to the count's scalars: the lane stage
    (``_deep_blocks`` / ``_walk_blocks``) with each block's lanes summed as
    they are walked, nothing lane-wide kept.

    With ``escapes`` slots the walk also lists WHICH owned lanes escaped
    (``esc_pos``: their window positions, ascending, -1 beyond them): each
    block holds its lanes' positions and results already, so nothing
    position-wide is added. Escapes beyond the slots are counted in ``esc``
    and not listed."""
    B = _deep_blocks(padded, lengths, num_contigs, n, at_eof, block)

    def tally(carry, _k, cand, live, lanes):
        count, esc, *listed = carry
        res = lanes["res"]
        own_lane = live & (cand >= lo) & (cand < own)
        counted = count + jnp.sum(own_lane & (res == 1))
        escaped = own_lane & (res == 2)
        if listed:
            listed = [_list_escapes(listed[0], esc, escaped, cand)]
        return counted, esc + jnp.sum(escaped), *listed

    listed = (jnp.full(escapes, -1, dtype=_I32),) if escapes else ()
    count, esc, *listed = _walk_blocks(
        B, n, at_eof, reads_to_check, tally, (_I32(0), _I32(0), *listed))
    out = {
        "count": count, "esc": esc, "res0": B.S["res0"],
        "overflow": B.overflow, "n_survivors": B.n_survivors,
        "lanes": B.lanes,
    }
    if listed:
        out["esc_pos"] = listed[0]
    return out


def _list_escapes(esc_pos, before, escaped, cand):
    """``esc_pos`` with one block's escaped lanes appended: slot ``j`` takes
    the position of the block's escape of rank ``j - before`` (``before``
    escapes are listed already; blocks come in rank order, so the list is
    ascending). Word-level ranks over the block's lanes and a gather of
    ``len(esc_pos)``: no scatter, nothing as wide as the block but the bit
    packing."""
    rank = jnp.arange(esc_pos.shape[0], dtype=_I32) - before
    lane = _ranked_positions(_rank_table(escaped), rank)
    mine = (rank >= 0) & (lane >= 0)
    return jnp.where(
        mine, jnp.take(cand, jnp.maximum(lane, 0), mode="clip"), esc_pos)


@functools.partial(
    jax.jit,
    static_argnames=("reads_to_check", "window", "funnel"),
)
def check_window(
    padded: jnp.ndarray,       # (W+PAD,) uint8; zeros beyond n
    lengths: jnp.ndarray,      # (Cmax,) int32 contig lengths, padded
    num_contigs: jnp.ndarray,  # () int32
    n: jnp.ndarray,            # () int32: valid byte count
    at_eof: jnp.ndarray,       # () bool: buffer end == file end
    reads_to_check: int = 10,
    window: int | None = None,
    funnel: bool = False,      # two-stage candidate funnel (Config.funnel)
):
    """Flag pass + chain walk over one window; verdicts for every offset.

    The walk runs only over *survivor* lanes (positions whose own record
    passes every check, F==0 — ~0.2% of positions on real data): candidates
    compact into a fixed-capacity lane buffer, walk ``reads_to_check`` gather
    rounds, and scatter back. Non-survivors resolve directly from F. If an
    adversarial input overflows the lane capacity, the whole window escapes
    to the host engine — exactness over speed, never a guess.

    ``funnel=True`` swaps the full-width 19-bit pass for the two-stage
    candidate funnel: the cheap prefilter screens every position, survivors
    compact, and the deep bits are evaluated once at candidate positions
    only. Verdicts (and hence record-start positions) are identical to
    ``funnel=False``; the documented differences are that ``fail_mask`` at
    prefilter-rejected positions carries only the prefilter bits (a subset
    of the full mask: no deep bit, and the two ``tooLarge*ReadPos`` bits
    only past the longest contig; at a position the prefilter passes it is
    the full mask), and ``exact`` may be True where the full pass reports a
    (definitively failing) lane as inexact — both only affect forensic
    projections, which run with the funnel off (Config.funnel="auto").

    Returns dict of (W,) arrays: verdict, fail_mask, reads_parsed,
    reads_before, exact, escaped — plus the () int32 ``survivors`` count
    (stage-0 survivors under the funnel; full-pass survivors otherwise) and
    ``lanes``, the lanes the lane stage ran for them: under the funnel whole
    blocks of ``lane_block(w)``, as many as hold the survivors (a number
    the device reads from the row: ``_deep_blocks``), otherwise the window's
    whole ``lane_capacity``.
    """
    w = padded.shape[0] - PAD
    L = _check_lanes(
        padded, lengths, num_contigs, n, at_eof,
        reads_to_check=reads_to_check, funnel=funnel,
    )
    return _scatter_lanes(L, w)


@jax.named_scope("check")
@jax.named_scope("scatter")
def _scatter_lanes(L: dict, w: int) -> dict:
    """``_check_lanes``' verdicts scattered back over the F-derived base:
    the (W,) arrays ``check_window`` returns. Under ``check/scatter``, so a
    trace tells the scatter from the lane stage."""
    survivor, res0 = L["survivor"], L["res0"]
    fail_mask0, inexact0 = L["fail_mask0"], L["inexact0"]
    cand, res = L["cand"], L["res"]
    live = cand >= 0
    fail_mask, reads_before = L["fail_mask"], L["reads_before"]
    reads_parsed, exact = L["reads_parsed"], L["exact"]
    overflow, n_survivors = L["overflow"], L["n_survivors"]
    tgt = jnp.where(live, cand, _I32(w))  # dead lanes scatter into the pad row
    # int8 from here on (the funnel's lane buffers hold the code as int32).
    res_full = jnp.zeros(w + 1, dtype=jnp.int8).at[tgt].set(
        jnp.where(live, res.astype(jnp.int8), jnp.int8(0)), mode="drop"
    )[:w]
    res_full = jnp.where(survivor, res_full, res0)
    fm_full = jnp.zeros(w + 1, dtype=_I32).at[tgt].set(fail_mask, mode="drop")[:w]
    fm_full = jnp.where(survivor, fm_full, fail_mask0)
    rb_full = jnp.zeros(w + 1, dtype=_I32).at[tgt].set(reads_before, mode="drop")[:w]
    rb_full = jnp.where(survivor, rb_full, _I32(0))
    rp_full = jnp.zeros(w + 1, dtype=_I32).at[tgt].set(reads_parsed, mode="drop")[:w]
    rp_full = jnp.where(survivor, rp_full, _I32(0))
    ex_full = jnp.ones(w + 1, dtype=bool).at[tgt].set(exact, mode="drop")[:w]
    ex_full = jnp.where(survivor, ex_full, ~inexact0)

    # Capacity overflow: the whole window is unresolved (host fallback).
    res_full = jnp.where(overflow, jnp.int8(2), res_full)
    escaped = res_full == 2
    exact_out = ex_full & (~escaped) & (~overflow)
    return {
        "verdict": res_full == 1,
        "fail_mask": jnp.where(overflow, _I32(0), fm_full),
        "reads_parsed": rp_full,
        "reads_before": rb_full,
        "exact": exact_out,
        "escaped": escaped,
        "survivors": n_survivors,
        "lanes": L["lanes"],
    }


def _count_funnel(
    padded, lengths, num_contigs, n, at_eof, lo, own,
    reads_to_check: int, block: int | None = None, escapes: int = 0,
):
    """``count_window`` under the funnel. Scatter-free reduction: verdicts
    live only on survivor lanes (non-survivors never reach res==1) and
    escapes split cleanly into prefilter-rejected positions (res0==2) plus
    lane escapes, so both scalars reduce over lanes without materializing
    the (W,) arrays.

    The escape list (``escapes`` slots) holds the lane escapes alone. A
    position stage 0 rejects escapes only with nothing but boundary flags
    set, which under the prefilter means the buffer ends within its fixed
    block: the last 35 bytes, never owned under a halo of 36 bytes or more
    (the default is 4 MiB). Should one be owned all the same, the window
    reports ``esc_overflow`` like one whose escapes outnumber the slots or
    whose survivors outnumber the lanes, rather than lose it."""
    w = padded.shape[0] - PAD
    i = jnp.arange(w, dtype=_I32)
    m = (i >= lo) & (i < own)
    L = _count_lanes(
        padded, lengths, num_contigs, n, at_eof, lo, own,
        reads_to_check, block, escapes,
    )
    with jax.named_scope("reduce"):
        esc0 = jnp.sum(m & (L["res0"] == 2))
        esc = esc0 + L["esc"]
        count = jnp.where(L["overflow"], 0, L["count"])
        esc = jnp.where(L["overflow"], jnp.sum(m), esc)
    out = {
        "count": count, "esc_count": esc, "survivors": L["n_survivors"],
        "lanes": L["lanes"],
    }
    if escapes:
        out["esc_pos"] = L["esc_pos"]
        out["esc_overflow"] = (
            L["overflow"] | (esc0 > 0) | (L["esc"] > escapes))
    return out


@functools.partial(
    jax.jit,
    static_argnames=("reads_to_check", "window", "funnel", "escapes"),
)
def count_window(
    padded, lengths, num_contigs, n, at_eof, lo, own,
    reads_to_check: int = 10, window: int | None = None,
    funnel: bool = False, escapes: int = 0,
):
    """check_window fused with its owned-span count reduction.

    One dispatch per streaming window instead of kernel + separate reduce,
    and XLA
    dead-code-eliminates everything the two scalars don't need — the
    fail_mask/reads_* scatters and the per-position arrays themselves.
    Beside the two scalars: ``survivors`` (stage 0's) and ``lanes``, the
    lanes the lane stage ran — under the funnel as many blocks as hold the
    survivors (``_deep_blocks``), without it the window's whole capacity.

    Escapes are rare, and a caller that cannot resolve them starts over on
    the exact spans path when ``esc_count`` is ever nonzero (the mesh step:
    ``escapes`` 0, the program it always was). The one-chip stream asks for
    ``escapes`` slots and gets ``esc_pos``, the window positions of the
    owned escaped candidates in ascending order (-1 beyond them), and
    ``esc_overflow``: more of them than slots, or a window that escaped
    whole, which is the case that still starts over.
    """
    if funnel:
        return _count_funnel(
            padded, lengths, num_contigs, n, at_eof, lo, own,
            reads_to_check, escapes=escapes,
        )
    w = padded.shape[0] - PAD
    i = jnp.arange(w, dtype=_I32)
    m = (i >= lo) & (i < own)
    res = check_window(
        padded, lengths, num_contigs, n, at_eof,
        reads_to_check=reads_to_check, window=window, funnel=funnel,
    )
    with jax.named_scope("reduce"):
        out = {
            "count": jnp.sum(m & res["verdict"]),
            "esc_count": jnp.sum(m & res["escaped"]),
            "survivors": res["survivors"],
            "lanes": res["lanes"],
        }
        if escapes:
            (at,) = jnp.nonzero(
                m & res["escaped"], size=escapes, fill_value=-1)
            out["esc_pos"] = at.astype(_I32)
            out["esc_overflow"] = out["esc_count"] > escapes
        return out


def make_count_window(
    window: int, reads_to_check: int = 10, funnel: bool = False,
    escapes: int = 0,
):
    """The fused count kernel (``jit_count_window``) for a fixed ``window``."""
    return functools.partial(
        count_window, reads_to_check=reads_to_check, window=window,
        funnel=funnel, escapes=escapes,
    )


def make_check_window(
    window: int, reads_to_check: int = 10, funnel: bool = False,
):
    """The window kernel (``jit_check_window``) for a fixed ``window``;
    ``funnel=True`` is the two-stage candidate funnel (same verdicts, see
    ``check_window``)."""
    return functools.partial(
        check_window, reads_to_check=reads_to_check, window=window,
        funnel=funnel,
    )


def _compact_lanes(keep, columns: tuple):
    """The lanes of a block where ``keep``, moved to the front in lane
    order: ``(picked, n)`` with ``picked`` (len(columns), block) int32, whose
    first ``n`` columns are those lanes' entries of ``columns`` and the rest
    whatever. Ranks over the block, as ``_list_escapes`` lists escapes, and
    ONE fetch of a row a lane from the block's columns laid side by side:
    no scatter, nothing wider than the block."""
    table = _rank_table(keep)
    lane = _ranked_positions(
        table, jnp.arange(keep.shape[0], dtype=_I32))
    picked = jnp.take(
        jnp.stack(columns, axis=1), jnp.maximum(lane, 0), axis=0,
        mode="clip")
    return picked.T, table.n_set


#: What ``load_window`` says of a window beside its rows, one int32 each,
#: in the order of its ``stats``: the owned record starts, the owned
#: escapes, the rows that passed the filter, those of them whose CIGAR the
#: scan did not finish, stage 0's survivors, the lanes run, and whether the
#: window's escapes could not be listed (``count_window``'s ``esc_overflow``).
LOAD_STATS = ("count", "esc_count", "rows", "cigar_over", "survivors",
              "lanes", "esc_overflow")


def _load_lanes(
    padded, lengths, num_contigs, n, at_eof, lo, own, rows,
    reads_to_check: int, escapes: int,
):
    """The funnelled check with the load's fold: ``_count_lanes``' lane
    stage, and where that sums ``own_lane & (res == 1)`` this keeps those
    lanes: parses each record out of the word view the check made
    (``parser.parse_lanes``), tests it (``parser.rows_pass``) and appends
    the block's rows that passed to the window's table (``_compact_lanes``),
    which therefore holds them in file order. A window has at most
    ``lane_capacity(w)`` lanes and a lane at most one record, so the table
    cannot overflow; a block is appended whole at the rows' count, the next
    over its tail, so the table is a block longer than the lanes."""
    from spark_bam_tpu.tpu import parser

    with jax.named_scope("check"):
        B = _deep_blocks(padded, lengths, num_contigs, n, at_eof, None)
    U = B.S["U"]

    def keep(carry, _k, cand, live, lanes):
        count, esc, listed, table, n_rows, over = carry
        with jax.named_scope("check"):
            res = lanes["res"]
            own_lane = live & (cand >= lo) & (cand < own)
            record = own_lane & (res == 1)
            escaped = own_lane & (res == 2)
            listed = _list_escapes(listed, esc, escaped, cand)
        words, cols, span, exact = parser.parse_lanes(
            U, jnp.where(record, cand, _I32(0)), record)
        passed = parser.rows_pass(record, cols, span, exact, rows)
        with jax.named_scope("filter"):
            picked, got = _compact_lanes(passed, (*words, cand, span))
            table = lax.dynamic_update_slice(table, picked, (0, n_rows))
            over = over + jnp.sum(passed & ~exact)
        return (count + jnp.sum(record), esc + jnp.sum(escaped), listed,
                table, n_rows + got, over)

    lanes_wide = B.cands.shape[0]
    count, esc, listed, table, n_rows, over = _walk_blocks(
        B, n, at_eof, reads_to_check, keep,
        (_I32(0), _I32(0), jnp.full(escapes, -1, dtype=_I32),
         jnp.zeros((parser.ROW_WORDS, lanes_wide + B.block), dtype=_I32),
         _I32(0), _I32(0)),
        walk_scope="check")
    return B, count, esc, listed, table, n_rows, over


@functools.partial(
    jax.jit,
    static_argnames=("reads_to_check", "window", "escapes"),
)
def load_window(
    padded, lengths, num_contigs, n, at_eof, lo, own, rows,
    reads_to_check: int = 10, window: int | None = None,
    escapes: int = ESCAPE_LIST,
):
    """``count_window`` with the records handed back: the check of every
    position, and at the owned lanes it accepts the record parsed, tested
    against ``rows`` (``parser.RowFilter``: loci and flag masks) and, where
    it passes, appended to ``table``: (``parser.ROW_WORDS``, lanes + a
    block) int32, a column a row in file order, the nine words of the
    fixed block, the record's position in the window and the reference span
    of its CIGAR. The first ``stats[rows]`` columns are rows; the caller
    reads ``stats`` (``LOAD_STATS``: seven integers) and then as many
    columns as that says, never the table.

    Always the funnel: the verdicts are the same with and without it, and
    the parse reads the funnel's word view. Escapes as ``count_window``'s
    with ``escapes`` slots (``esc_pos``): a record that runs past the
    buffer is listed and not parsed. A window that escaped whole or could
    not list its escapes says so in ``stats`` and has no rows."""
    w = padded.shape[0] - PAD
    B, count, esc, listed, table, n_rows, over = _load_lanes(
        padded, lengths, num_contigs, n, at_eof, lo, own, rows,
        reads_to_check, escapes)
    with jax.named_scope("reduce"):
        i = jnp.arange(w, dtype=_I32)
        m = (i >= lo) & (i < own)
        esc0 = jnp.sum(m & (B.S["res0"] == 2))
        lost = B.overflow | (esc0 > 0) | (esc > escapes)
        stats = jnp.stack([
            jnp.where(lost, 0, count),
            jnp.where(B.overflow, jnp.sum(m), esc0 + esc),
            jnp.where(lost, 0, n_rows), jnp.where(lost, 0, over),
            B.n_survivors, B.lanes, lost.astype(_I32),
        ]).astype(_I32)
    return {"stats": stats, "esc_pos": listed, "table": table}


def make_load_window(
    window: int, reads_to_check: int = 10, escapes: int = ESCAPE_LIST,
):
    """The fused load kernel (``jit_load_window``) for a fixed ``window``."""
    return functools.partial(
        load_window, reads_to_check=reads_to_check, window=window,
        escapes=escapes,
    )


@functools.partial(jax.jit, static_argnames=("rows",))
def table_head(table, rows: int):
    """The first ``rows`` columns of ``load_window``'s table: what of it
    crosses to the host, in power-of-two buckets."""
    return table[:, :rows]


@dataclass
class WindowResult:
    verdict: np.ndarray
    fail_mask: np.ndarray
    reads_parsed: np.ndarray
    reads_before: np.ndarray
    exact: np.ndarray
    escaped: np.ndarray


class TpuChecker:
    """Host wrapper: windows a flat uncompressed stream through the device
    kernel; escaped/inexact candidates fall back to the NumPy engine (and
    ultimately the sequential oracle), so results are always exact.

    The ``Checker`` plugin face of the TPU backend (``spark.bam.backend=tpu``).
    """

    def __init__(
        self,
        contig_lengths: np.ndarray,
        window: int = 16 << 20,
        halo: int = 4 << 20,
        reads_to_check: int = 10,
        cmax: int = 1024,
    ):
        self.window = window
        self.halo = halo
        self.reads_to_check = reads_to_check
        self.num_contigs = np.int32(len(contig_lengths))
        cmax = max(cmax, len(contig_lengths))
        self.lengths = np.zeros(cmax, dtype=np.int32)
        self.lengths[: len(contig_lengths)] = contig_lengths
        self._kernel = make_check_window(window, reads_to_check)

    def check_buffer(self, buf: np.ndarray, at_eof: bool = True) -> WindowResult:
        """Check every position of ``buf``; exact everywhere except possibly
        within the final chain-reach when ``at_eof=False`` (those escape)."""
        n_total = len(buf)
        out = {
            k: np.empty(n_total, dtype=d)
            for k, d in [
                ("verdict", bool), ("fail_mask", np.int32),
                ("reads_parsed", np.int32), ("reads_before", np.int32),
                ("exact", bool), ("escaped", bool),
            ]
        }
        w = self.window
        step = max(w - self.halo, 1)
        s = 0
        while True:
            e = min(s + w, n_total)
            chunk_eof = at_eof and e == n_total
            padded = np.zeros(w + PAD, dtype=np.uint8)
            padded[: e - s] = buf[s:e]
            res = self._kernel(
                jnp.asarray(padded),
                jnp.asarray(self.lengths),
                jnp.int32(self.num_contigs),
                jnp.int32(e - s),
                jnp.bool_(chunk_eof),
            )
            res = {k: np.asarray(v) for k, v in res.items()}
            # Own [s, s+step) — the halo tail belongs to the next window —
            # except the last window, which owns through the end.
            own_end = e if e == n_total else min(s + step, n_total)
            for k in out:
                out[k][s:own_end] = res[k][: own_end - s]
            if e == n_total:
                break
            s += step
        result = WindowResult(**out)
        self._host_recheck(buf, result, at_eof)
        return result

    def _host_recheck(self, buf, result: WindowResult, at_eof: bool):
        """Resolve escaped/inexact lanes with the NumPy engine on a widened
        span (covers sentinel-overflow lanes and halo-exceeding chains)."""
        bad = result.escaped | ~result.exact
        if at_eof:
            idxs = np.flatnonzero(bad)
        else:
            # In pure windowed mode the tail escapes are legitimate output.
            idxs = np.flatnonzero(bad[: max(len(buf) - self.halo, 0)])
        if len(idxs) == 0:
            return
        from spark_bam_tpu.check.vectorized import check_flat

        # Escapes are rare (chains outrunning the halo, sentinel overflows);
        # re-run only the suffix that can influence them.
        base = int(idxs.min())
        res = check_flat(
            buf[base:], self.lengths[: int(self.num_contigs)],
            candidates=(idxs - base).astype(np.int64),
            at_eof=at_eof, reads_to_check=self.reads_to_check,
        )
        result.verdict[idxs] = res.verdict
        result.fail_mask[idxs] = res.fail_mask
        result.reads_parsed[idxs] = res.reads_parsed
        result.reads_before[idxs] = res.reads_before
        result.exact[idxs] = res.exact | res.verdict | (res.fail_mask != 0)
        result.escaped[idxs] = res.escaped
