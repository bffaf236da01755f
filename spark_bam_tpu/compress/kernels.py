"""Device kernels for the write path: batched CRC32 + fixed-Huffman pack.

Many ≤64 KiB payload lanes per dispatch, ``(B, STRIDE)`` u8 with the batch
dim padded to a power of two so jit shape churn stays bounded. Both kernels
are XLA programs (jnp + lax).

**CRC32** is the sequential half: slice-by-4 table lookups
(four 256-entry u32 tables as baked constants), one ``fori_loop``
iteration per 4-byte group across all lanes at once. Variable lane
lengths are handled by *masking, not padding*: zero padding would
corrupt the digest, so groups fully inside a lane's length take the
slice-by-4 update while groups straddling the boundary re-compute
byte-wise with per-byte ``where`` masks (identical result where both
apply). The loop bound is the batch's max length, traced.

**Fixed-Huffman pack** is the parallel half: per-byte (nbits, reversed
code) table lookups, an exclusive cumulative sum for every code's
absolute bit offset (3 header bits lead; a 7-bit all-zero end-of-block
trails), then one scatter-add of every *set* bit into a zeroed output
byte plane — bit ``i`` lands in ``out[i >> 3]`` as ``1 << (i & 7)``.
Bit positions are unique so the adds never collide; zero bits and the
zero padding need no writes at all. Lanes whose fixed stream would
exceed the stored alternative scatter with ``mode='drop'`` past the
buffer edge — the codec picks stored for them anyway.

compress/huffman.py holds the byte-identical host reference; parity is
pinned by tests/test_deflate.py.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from spark_bam_tpu.bgzf.block import MAX_BLOCK_SIZE
from spark_bam_tpu.compress.huffman import NBITS, RCODE

#: Fixed lane width — one BGZF payload never exceeds this (bgzf/block.py).
STRIDE = MAX_BLOCK_SIZE
#: Output byte plane per lane: a useful fixed stream is < payload + 5
#: bytes (else stored wins), so STRIDE + 8 covers every kept result.
OUT_BYTES = STRIDE + 8


def _crc_tables() -> np.ndarray:
    """Slice-by-4 CRC32 tables, ``(4, 256) u32``; row 0 is the standard
    reflected CRC-32 (poly 0xEDB88320) byte table."""
    t = np.zeros((4, 256), dtype=np.uint64)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ 0xEDB88320 if c & 1 else c >> 1
        t[0, i] = c
    for k in range(1, 4):
        prev = t[k - 1]
        t[k] = (prev >> 8) ^ t[0][prev & 0xFF]
    return t.astype(np.uint32)


_T = _crc_tables()


def _crc_body(data: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """Traced CRC32 over ``(B, STRIDE)`` u8 lanes of ``lengths`` bytes."""
    t0, t1, t2, t3 = (jnp.asarray(_T[k]) for k in range(4))
    lens = lengths.astype(jnp.int32)

    def lookup(table, idx):
        return jnp.take(table, (idx & 0xFF).astype(jnp.int32))

    def body(g, crc):
        grp = lax.dynamic_slice_in_dim(data, 4 * g, 4, axis=1)
        b = [grp[:, j].astype(jnp.uint32) for j in range(4)]
        word = b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)
        c = crc ^ word
        full = (
            lookup(t3, c) ^ lookup(t2, c >> 8)
            ^ lookup(t1, c >> 16) ^ lookup(t0, c >> 24)
        )
        # Boundary groups: byte-at-a-time with per-byte validity masks
        # (zero padding would change the digest; masking cannot).
        bw = crc
        for j in range(4):
            step = (bw >> 8) ^ lookup(t0, bw ^ b[j])
            bw = jnp.where(4 * g + j < lens, step, bw)
        return jnp.where(4 * g + 4 <= lens, full, bw)

    n_groups = (jnp.max(lens) + 3) // 4
    crc0 = jnp.full(data.shape[0], 0xFFFFFFFF, dtype=jnp.uint32)
    return lax.fori_loop(0, n_groups, body, crc0) ^ jnp.uint32(0xFFFFFFFF)


@jax.jit
def crc32_lanes(data: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """``(B,) u32`` CRC32 of each lane — the whole device side of stored
    mode (stored bodies are framing around the raw bytes)."""
    return _crc_body(data, lengths)


@jax.jit
def deflate_fixed_lanes(data: jnp.ndarray, lengths: jnp.ndarray):
    """Fixed-Huffman pack + CRC32 for every lane in one program.

    Returns ``(packed (B, OUT_BYTES) u8, total_bits (B,) i32,
    crc (B,) u32)``. ``packed``'s first ``ceil(total_bits / 8)`` bytes
    are the complete DEFLATE body (header bits, codes, end-of-block,
    zero pad) — byte-identical to ``huffman.fixed_pack``. A lane whose
    stream outgrows ``OUT_BYTES`` has its tail bits dropped; its
    ``total_bits`` still reports the true size so the codec's
    pick-smaller step selects stored and never reads the clipped bytes.
    """
    b_dim, stride = data.shape
    byte_idx = data.astype(jnp.int32)
    nb = jnp.take(jnp.asarray(NBITS.astype(np.int32)), byte_idx)
    rc = jnp.take(jnp.asarray(RCODE.astype(np.int32)), byte_idx)
    valid = jnp.arange(stride, dtype=jnp.int32)[None, :] < (
        lengths.astype(jnp.int32)[:, None]
    )
    nbv = jnp.where(valid, nb, 0)
    pos = 3 + jnp.cumsum(nbv, axis=1) - nbv          # exclusive, header-led
    total_bits = 3 + jnp.sum(nbv, axis=1) + 7        # + all-zero EOB

    span = jnp.arange(9, dtype=jnp.int32)[None, None, :]
    bit_idx = pos[:, :, None] + span                 # (B, S, 9)
    live = (
        valid[:, :, None]
        & (span < nb[:, :, None])
        & (((rc[:, :, None] >> span) & 1) == 1)
        & (bit_idx < OUT_BYTES * 8)                  # clip: stored wins there
    )
    lane = jnp.arange(b_dim, dtype=jnp.int32)[:, None, None]
    flat = jnp.where(
        live, lane * OUT_BYTES + (bit_idx >> 3), b_dim * OUT_BYTES
    )
    val = (jnp.int32(1) << (bit_idx & 7)).astype(jnp.uint8)
    out = jnp.zeros(b_dim * OUT_BYTES, dtype=jnp.uint8)
    out = out.at[flat.reshape(-1)].add(val.reshape(-1), mode="drop")
    out = out.reshape(b_dim, OUT_BYTES)
    out = out.at[:, 0].add(3)                        # BFINAL=1, BTYPE=01
    return out, total_bits, _crc_body(data, lengths)


def pack_lanes(payloads: "list[bytes]"):
    """Host staging: payload list → ``(data (B', STRIDE) u8,
    lengths (B',) i32, b)`` with ``B'`` the power-of-two pad of ``b``
    (bounded jit shape churn)."""
    b = len(payloads)
    b_pad = max(1 << max(b - 1, 0).bit_length(), 1)
    data = np.zeros((b_pad, STRIDE), dtype=np.uint8)
    lengths = np.zeros(b_pad, dtype=np.int32)
    for i, p in enumerate(payloads):
        data[i, : len(p)] = np.frombuffer(p, dtype=np.uint8)
        lengths[i] = len(p)
    return data, lengths, b
