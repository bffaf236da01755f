"""Pallas TPU kernels for the checker hot path.

Two kernels, verified bit-exact against the engines they mirror:

``prefilter_flags_kernel`` — stage 0 of the candidate funnel: only the
flag bits derivable from the fixed 36-byte block (``remaining`` bounds,
refID range, positions against the longest contig, name-length sanity),
no name-byte scans, no cigar scans and no per-lane contig lookup, so the
slab halo shrinks from ``PAD`` to one DMA tile and the 254-way unroll
disappears entirely.  Positions it cannot reject go on to the deep pass
in tpu/checker.py, which looks the contig's own length up.

``full_flags_kernel`` — ALL 19 flag bits of the checker error model
(check/flags.py; reference full/Checker.scala:17-198) computed in-kernel,
**gather-free** — Mosaic does not lower 1-D dynamic gathers, so every
data-dependent lookup is restructured:

- contig-length lookup (tooLarge*Pos): a scalar ``fori_loop`` over the
  SMEM contig table, selecting each length into the lanes that reference
  it — O(C) vector selects instead of a gather;
- read-name byte/charset checks: name lengths are one *byte* (≤255), so
  the per-lane variable-length reads unroll into 254 statically-shifted
  slices with masked selects, and the charset count is a running sum that
  grows by one shifted slice per iteration;
- cigar-op validity: a stride-4 suffix-min scan over the slab yields, for
  every offset, the first bad-op position at int-stride in its class —
  membership in ``[cig_start, cig_end)`` becomes one compare, and the
  ``cig_start`` lookup rides the same 254-way unrolled select (cig_end,
  which can lie 256 KiB ahead, never needs a lookup at all).

The slab halo equals the checker's ``PAD`` (≥ 36 + 255 + 4·65535), so even
a worst-case cigar array resolves in-slab. Wired into the product behind
``spark.bam.backend=pallas`` (tpu/checker.py swaps its flag pass for this
kernel; the chain walk is unchanged). On non-TPU backends it runs in
interpret mode — the parity artifact (tests/test_pallas.py) pins it
against both the XLA flag pass and the NumPy engine.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from spark_bam_tpu.check.flags import BIT


def interpret_for_platform(platform: str | None = None) -> bool:
    """Whether Pallas kernels placed on ``platform`` (the process-default
    backend when None) run in interpret mode: Mosaic compiles on a TPU, the
    CPU (the tests' virtual mesh) interprets, and any other platform is an
    error rather than a silent slow path."""
    if platform is None:
        platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run on tpu (Mosaic) or cpu (interpret mode), "
        f"not on platform {platform!r}"
    )

TILE = 32 * 1024

_I32 = jnp.int32

def _i32_at(tile: jnp.ndarray, off: int, n: int) -> jnp.ndarray:
    u = (
        tile[off: off + n].astype(jnp.uint32)
        | (tile[off + 1: off + n + 1].astype(jnp.uint32) << 8)
        | (tile[off + 2: off + n + 2].astype(jnp.uint32) << 16)
        | (tile[off + 3: off + n + 3].astype(jnp.uint32) << 24)
    )
    return lax.bitcast_convert_type(u, jnp.int32)


# ----------------------------------------------------- full 19-bit kernel

# Slab halo for the full kernel: the checker's PAD (a multiple of 1024 for
# Mosaic's DMA tiling, ≥ 36 + 255 + 4*65535 so cigar scans resolve in-slab;
# import-cycle-safe — checker.py only imports this module lazily).
from spark_bam_tpu.tpu.checker import PAD as FULL_HALO  # noqa: E402
_INF = 1 << 28  # beyond any slab-relative cig_end; selected lanes stay int32


def _iota(n: int) -> jnp.ndarray:
    # TPU requires ≥2-D iota; squeeze back to the lane vector.
    return lax.broadcasted_iota(jnp.int32, (n, 1), 0).squeeze(-1)


def _full_flags_kernel(p_hbm, lengths_ref, nc_ref, n_ref, out_ref, slab, sem):
    i = pl.program_id(0)
    copy = pltpu.make_async_copy(
        p_hbm.at[pl.ds(i * TILE, TILE + FULL_HALO)], slab, sem
    )
    copy.start()
    copy.wait()
    tile = slab[...]
    slab_len = TILE + FULL_HALO
    t = TILE
    base = i * TILE
    nval = n_ref[0]
    c = nc_ref[0]

    # --- fixed-field extraction (lane l ↔ candidate offset base+l) -------
    remaining = _i32_at(tile, 0, t)
    ref_idx = _i32_at(tile, 4, t)
    ref_pos = _i32_at(tile, 8, t)
    name_len = tile[12: t + 12].astype(_I32)
    fnc = _i32_at(tile, 16, t)
    n_cigar = fnc & 0xFFFF
    mapped = ((fnc >> 18) & 1) == 0
    seq_len = _i32_at(tile, 20, t)
    next_ref_idx = _i32_at(tile, 24, t)
    next_ref_pos = _i32_at(tile, 28, t)

    rel = _iota(t)
    abs_i = base + rel

    # --- contig-length lookup without gather: scalar loop over SMEM ------
    def contig_body(j, carry):
        len_r, len_n = carry
        lj = lengths_ref[j]
        len_r = jnp.where(ref_idx == j, lj, len_r)
        len_n = jnp.where(next_ref_idx == j, lj, len_n)
        return len_r, len_n

    len_r, len_n = lax.fori_loop(
        0, c, contig_body,
        (jnp.zeros(t, dtype=_I32), jnp.zeros(t, dtype=_I32)),
    )

    def ref_bits(idx, pos, len_at, b_neg_idx, b_large_idx, b_neg_pos, b_large_pos):
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

    F = ref_bits(
        ref_idx, ref_pos, len_r,
        BIT["negativeReadIdx"], BIT["tooLargeReadIdx"],
        BIT["negativeReadPos"], BIT["tooLargeReadPos"],
    )
    F = F | ref_bits(
        next_ref_idx, next_ref_pos, len_n,
        BIT["negativeNextReadIdx"], BIT["tooLargeNextReadIdx"],
        BIT["negativeNextReadPos"], BIT["tooLargeNextReadPos"],
    )

    # --- implied size (JVM int32 wrap + truncating division) -------------
    tt = seq_len + _I32(1)
    half = lax.div(tt, _I32(2))
    rhs = _I32(32) + name_len + _I32(4) * n_cigar + half + seq_len
    F = F | jnp.where(
        remaining < rhs, _I32(BIT["tooFewRemainingBytesImplied"]), _I32(0)
    )
    F = F | jnp.where(name_len == 0, _I32(BIT["noReadName"]), _I32(0))
    F = F | jnp.where(name_len == 1, _I32(BIT["emptyReadName"]), _I32(0))

    # --- cigar suffix-min scan: first bad-op position per stride class ---
    j_slab = _iota(slab_len)
    bad_op = ((tile & 0xF) > 8) & (base + j_slab + 4 <= nval)
    V = jnp.where(bad_op, j_slab, _I32(_INF)).reshape(slab_len // 4, 4)
    D = jnp.flip(lax.cummin(jnp.flip(V, 0), axis=0), 0).reshape(slab_len)

    # --- per-lane variable-length lookups: 254-way static unroll ---------
    allowed = ((tile >= 0x21) & (tile <= 0x7E) & (tile != 0x40)).astype(_I32)
    run_sum = jnp.zeros(t, dtype=_I32)
    last_byte = jnp.zeros(t, dtype=jnp.uint8)
    good = jnp.zeros(t, dtype=_I32)
    d_cig = D[36: 36 + t]  # cig_start = l+36 for nameless lanes
    for L in range(2, 256):
        m = name_len == L
        # window [l+36, l+36+L-1) grows by the byte at offset 36+L-2
        run_sum = run_sum + allowed[36 + L - 2: 36 + L - 2 + t]
        last_byte = jnp.where(m, tile[36 + L - 1: 36 + L - 1 + t], last_byte)
        good = jnp.where(m, run_sum, good)
        d_cig = jnp.where(m, D[36 + L: 36 + L + t], d_cig)

    has_name = name_len >= 2
    name_eof = has_name & (abs_i + 36 + name_len > nval)
    F = F | jnp.where(name_eof, _I32(BIT["tooFewBytesForReadName"]), _I32(0))
    name_in = has_name & (~name_eof)
    non_null = name_in & (last_byte != 0)
    F = F | jnp.where(non_null, _I32(BIT["nonNullTerminatedReadName"]), _I32(0))
    bad_chars = name_in & (~non_null) & (good != name_len - 1)
    F = F | jnp.where(bad_chars, _I32(BIT["nonASCIIReadName"]), _I32(0))

    # --- cigar bits: membership via the suffix-min, no cig_end lookup ----
    cig_start = rel + 36 + jnp.where(name_in, name_len, _I32(0))
    cig_end = cig_start + _I32(4) * n_cigar
    cig_considered = ~name_eof
    has_bad = cig_considered & (d_cig < cig_end)
    F = F | jnp.where(has_bad, _I32(BIT["invalidCigarOp"]), _I32(0))
    cig_eof = cig_considered & (~has_bad) & (base + cig_end > nval)
    F = F | jnp.where(cig_eof, _I32(BIT["tooFewBytesForCigarOps"]), _I32(0))
    empty_ok = cig_considered & (~has_bad) & (~cig_eof) & mapped
    empty_seq = empty_ok & (seq_len == 0)
    empty_cig = empty_ok & (n_cigar == 0)
    some_empty = empty_seq | empty_cig
    # Swapped on purpose: reference quirk (check/vectorized.py).
    F = F | jnp.where(some_empty & empty_seq, _I32(BIT["emptyMappedCigar"]), _I32(0))
    F = F | jnp.where(some_empty & empty_cig, _I32(BIT["emptyMappedSeq"]), _I32(0))

    # --- the only flag when the fixed 36-byte read itself fails ----------
    few_fixed = abs_i > nval - 36
    F = jnp.where(few_fixed, _I32(BIT["tooFewFixedBlockBytes"]), F)

    out_ref[...] = F


# --------------------------------------------------- funnel stage-0 kernel

# The prefilter only reads the fixed block (bytes [l, l+36)); one 1 KiB
# halo tile keeps the DMA length a multiple of Mosaic's tiling like PAD.
PRE_HALO = 1024


def _prefilter_flags_kernel(p_hbm, lengths_ref, nc_ref, n_ref, out_ref, slab, sem):
    i = pl.program_id(0)
    copy = pltpu.make_async_copy(
        p_hbm.at[pl.ds(i * TILE, TILE + PRE_HALO)], slab, sem
    )
    copy.start()
    copy.wait()
    tile = slab[...]
    t = TILE
    base = i * TILE
    nval = n_ref[0]
    c = nc_ref[0]

    # --- fixed-field extraction (lane l ↔ candidate offset base+l) -------
    remaining = _i32_at(tile, 0, t)
    ref_idx = _i32_at(tile, 4, t)
    ref_pos = _i32_at(tile, 8, t)
    name_len = tile[12: t + 12].astype(_I32)
    fnc = _i32_at(tile, 16, t)
    n_cigar = fnc & 0xFFFF
    seq_len = _i32_at(tile, 20, t)
    next_ref_idx = _i32_at(tile, 24, t)
    next_ref_pos = _i32_at(tile, 28, t)

    abs_i = base + _iota(t)

    # --- the longest contig bounds every position (checker's stage 0; the
    # exact length is looked up at the survivors): a scalar max over SMEM --
    len_max = lax.fori_loop(
        0, c, lambda j, m: jnp.maximum(m, lengths_ref[j]), _I32(0))

    def ref_bits(idx, pos, b_neg_idx, b_large_idx, b_neg_pos, b_large_pos):
        neg_idx = idx < -1
        large_idx = (~neg_idx) & (idx >= c)
        neg_pos = pos < -1
        idx_ok = (~neg_idx) & (~large_idx)
        large_pos = idx_ok & (~neg_pos) & (idx >= 0) & (pos > len_max)
        return (
            jnp.where(neg_idx, _I32(b_neg_idx), _I32(0))
            | jnp.where(large_idx, _I32(b_large_idx), _I32(0))
            | jnp.where(neg_pos, _I32(b_neg_pos), _I32(0))
            | jnp.where(large_pos, _I32(b_large_pos), _I32(0))
        )

    F = ref_bits(
        ref_idx, ref_pos,
        BIT["negativeReadIdx"], BIT["tooLargeReadIdx"],
        BIT["negativeReadPos"], BIT["tooLargeReadPos"],
    )
    F = F | ref_bits(
        next_ref_idx, next_ref_pos,
        BIT["negativeNextReadIdx"], BIT["tooLargeNextReadIdx"],
        BIT["negativeNextReadPos"], BIT["tooLargeNextReadPos"],
    )

    # --- implied size (JVM int32 wrap + truncating division) -------------
    tt = seq_len + _I32(1)
    half = lax.div(tt, _I32(2))
    rhs = _I32(32) + name_len + _I32(4) * n_cigar + half + seq_len
    F = F | jnp.where(
        remaining < rhs, _I32(BIT["tooFewRemainingBytesImplied"]), _I32(0)
    )
    F = F | jnp.where(name_len == 0, _I32(BIT["noReadName"]), _I32(0))
    F = F | jnp.where(name_len == 1, _I32(BIT["emptyReadName"]), _I32(0))

    # --- the only flag when the fixed 36-byte read itself fails ----------
    few_fixed = abs_i > nval - 36
    F = jnp.where(few_fixed, _I32(BIT["tooFewFixedBlockBytes"]), F)

    out_ref[...] = F


@functools.partial(jax.jit, static_argnames=("interpret",))
def prefilter_check_flags(
    padded: jnp.ndarray,       # (W + FULL_HALO,) uint8, W a multiple of TILE
    lengths: jnp.ndarray,      # (Cmax,) int32
    num_contigs: jnp.ndarray,  # (1,) int32
    n: jnp.ndarray,            # (1,) int32: valid byte count
    interpret: bool = False,
):
    """Stage-0 funnel bits at every offset of the window: the fixed-block
    subset of the 19-flag model, a guaranteed superset of full-pass
    rejections among those bits (positions it clears still face the deep
    pass)."""
    w = padded.shape[0] - FULL_HALO
    assert w % TILE == 0, "window must be a multiple of the tile size"
    grid = (w // TILE,)
    return pl.pallas_call(
        _prefilter_flags_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),     # bytes stay in HBM; DMA'd
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((TILE,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((w,), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((TILE + PRE_HALO,), jnp.uint8),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(padded, lengths, num_contigs, n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def full_check_flags(
    padded: jnp.ndarray,       # (W + FULL_HALO,) uint8, W a multiple of TILE
    lengths: jnp.ndarray,      # (Cmax,) int32
    num_contigs: jnp.ndarray,  # (1,) int32
    n: jnp.ndarray,            # (1,) int32: valid byte count
    interpret: bool = False,
):
    """All 19 flag bits at every offset of the window (the Pallas flag
    pass behind ``spark.bam.backend=pallas``)."""
    w = padded.shape[0] - FULL_HALO
    assert w % TILE == 0, "window must be a multiple of the tile size"
    grid = (w // TILE,)
    return pl.pallas_call(
        _full_flags_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),     # bytes stay in HBM; DMA'd
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((TILE,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((w,), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((TILE + FULL_HALO,), jnp.uint8),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(padded, lengths, num_contigs, n)
