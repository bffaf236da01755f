"""Build + ctypes-load the native runtime (g++ → shared object, cached).

No pybind11 in this environment, so the binding is plain ctypes over an
``extern "C"`` surface. The build is lazy and cached next to the source;
everything degrades gracefully to the NumPy/Python paths when a compiler is
unavailable (``load_native()`` returns None).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path

import numpy as np

log = logging.getLogger(__name__)

_SRC = Path(__file__).parent / "spark_bam_native.cpp"
_LIB_CACHE: list = []  # [lib or None], filled once
_LOAD_INFO: dict = {}  # path / built (this process ran g++) / error
_LOAD_LOCK = threading.Lock()  # concurrent first-use (pipeline threads)


def _build(src: Path, out: Path) -> bool:
    base = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17"]
    # Build to a temp name then atomically rename: a killed/concurrent build
    # can never leave a half-written .so that later loads would trip over.
    tmp = out.with_suffix(f".tmp{os.getpid()}")
    tail = [str(src), "-o", str(tmp), "-lz"]
    # -march=native helps the bit-twiddling hot loops measurably; the cache
    # key includes a host-CPU token, so a shared checkout never serves one
    # machine's tuned binary to a different machine. Retry generic in case
    # the toolchain rejects -march=native.
    last_err = None
    try:
        for flags in ([*base, "-march=native", *tail], [*base, *tail]):
            try:
                subprocess.run(flags, check=True, capture_output=True)
                os.replace(tmp, out)
                return True
            except FileNotFoundError as e:
                _LOAD_INFO["error"] = f"g++ not found: {e}"
                log.warning("native build failed (%s); using Python fallbacks", e)
                return False
            except subprocess.CalledProcessError as e:
                last_err = e
        tail_err = (last_err.stderr or b"").decode(errors="replace")[-500:]
        _LOAD_INFO["error"] = f"g++ rc={last_err.returncode}: {tail_err}"
        log.warning(
            "native build failed (rc=%s): %s; using Python fallbacks",
            last_err.returncode, tail_err,
        )
        return False
    finally:
        tmp.unlink(missing_ok=True)


def _host_token() -> str:
    """A short token identifying this host's CPU (for the .so cache key)."""
    import platform

    desc = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features", "model name")):
                    desc += line
                    break
    except OSError:
        pass
    return hashlib.sha256(desc.encode()).hexdigest()[:8]


def load_native():
    """The loaded shared library with argtypes set, or None."""
    if _LIB_CACHE:
        return _LIB_CACHE[0]
    with _LOAD_LOCK:
        if _LIB_CACHE:  # another thread finished while we waited
            return _LIB_CACHE[0]
        return _load_native_locked()


def native_info() -> dict:
    """How this process got the library: ``path``, ``built`` (g++ ran here,
    as opposed to a cached .so) and, when it is unavailable, ``error``."""
    load_native()
    return dict(_LOAD_INFO)


def require_native(why: str):
    """``load_native()`` for callers that cannot do without it: raises,
    naming the build error, where the optional paths return None."""
    lib = load_native()
    if lib is None:
        raise RuntimeError(
            f"{why} needs the native library "
            f"({_SRC.name}) and it is unavailable: "
            f"{_LOAD_INFO.get('error', 'unknown build error')}"
        )
    return lib


def _load_native_locked():
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    out = _SRC.parent / f"_spark_bam_native_{digest}_{_host_token()}.so"
    _LOAD_INFO.update(path=str(out), built=not out.exists())
    if not out.exists() and not _build(_SRC, out):
        _LIB_CACHE.append(None)
        return None
    try:
        lib = ctypes.CDLL(str(out))
    except OSError as e:
        _LOAD_INFO["error"] = f"dlopen failed: {e}"
        log.warning("native load failed (%s); using Python fallbacks", e)
        _LIB_CACHE.append(None)
        return None

    c_u8p = ctypes.POINTER(ctypes.c_uint8)
    c_i64p = ctypes.POINTER(ctypes.c_int64)
    c_i32p = ctypes.POINTER(ctypes.c_int32)

    lib.sbt_inflate_blocks.restype = ctypes.c_long
    lib.sbt_inflate_blocks.argtypes = [
        c_u8p, c_i64p, c_i64p, ctypes.c_int64, c_u8p, c_i64p, c_i64p,
    ]
    lib.sbt_walk_members.restype = ctypes.c_int64
    lib.sbt_walk_members.argtypes = [
        c_u8p, ctypes.c_int64, ctypes.c_int64, c_i64p, c_i64p, c_i64p,
        ctypes.c_int64, c_i64p, c_i32p,
    ]
    lib.sbt_eager_check.restype = None
    lib.sbt_eager_check.argtypes = [
        c_u8p, ctypes.c_int64, c_i64p, ctypes.c_int64,
        c_i32p, ctypes.c_int32, ctypes.c_int32, c_u8p,
    ]
    lib.sbt_find_record_start.restype = ctypes.c_int64
    lib.sbt_find_record_start.argtypes = [
        c_u8p, ctypes.c_int64, ctypes.c_int64,
        c_i32p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
    ]
    lib.sbt_find_record_start_window.restype = ctypes.c_int64
    lib.sbt_find_record_start_window.argtypes = [
        c_u8p, ctypes.c_int64, ctypes.c_int64,
        c_i32p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
        ctypes.c_int32, c_i64p,
    ]
    lib.sbt_eager_check_window.restype = None
    lib.sbt_eager_check_window.argtypes = [
        c_u8p, ctypes.c_int64, c_i64p, ctypes.c_int64,
        c_i32p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, c_u8p,
    ]
    lib.sbt_rans_decompress.restype = ctypes.c_int64
    lib.sbt_rans_decompress.argtypes = [
        c_u8p, ctypes.c_int64, c_u8p, ctypes.c_int64,
    ]
    lib.sbt_inflate_blocks_fast.restype = ctypes.c_long
    lib.sbt_inflate_blocks_fast.argtypes = [
        c_u8p, c_i64p, c_i64p, ctypes.c_int64, c_u8p, c_i64p, c_i64p,
        ctypes.c_int64,
    ]
    _LIB_CACHE.append(lib)
    return lib


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


#: ``sbt_walk_members``' reasons for stopping.
WALK_END, WALK_SENTINEL, WALK_FULL, WALK_REJECTED = range(4)


def walk_members_native(
    lib, data, start: int, capacity: int
) -> tuple[np.ndarray, int, int]:
    """One ``sbt_walk_members`` call over ``data`` (anything with the
    buffer protocol: the mapped file) from byte ``start``: ``(table,
    stop_pos, stop_why)``, ``table`` an int64 array of three rows (start,
    compressed size, uncompressed size) and at most ``capacity`` columns.
    ctypes releases the interpreter lock for the call. The view of ``data``
    is dropped before returning, so the caller may close its mapping."""
    view = np.frombuffer(data, dtype=np.uint8)
    table = np.empty((3, capacity), dtype=np.int64)
    stop_pos = ctypes.c_int64(start)
    stop_why = ctypes.c_int32(WALK_END)
    n = lib.sbt_walk_members(
        _ptr(view, ctypes.c_uint8), len(view), start,
        _ptr(table[0], ctypes.c_int64), _ptr(table[1], ctypes.c_int64),
        _ptr(table[2], ctypes.c_int64), capacity,
        ctypes.byref(stop_pos), ctypes.byref(stop_why),
    )
    return table[:, :n], int(stop_pos.value), int(stop_why.value)


def eager_check_native(
    buf: np.ndarray,
    candidates: np.ndarray,
    contig_lengths: np.ndarray,
    reads_to_check: int = 10,
) -> np.ndarray | None:
    """Native eager verdicts for candidate offsets; None if unavailable."""
    lib = load_native()
    if lib is None:
        return None
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    cand = np.ascontiguousarray(candidates, dtype=np.int64)
    lens = np.ascontiguousarray(contig_lengths, dtype=np.int32)
    out = np.zeros(len(cand), dtype=np.uint8)
    lib.sbt_eager_check(
        _ptr(buf, ctypes.c_uint8), len(buf),
        _ptr(cand, ctypes.c_int64), len(cand),
        _ptr(lens, ctypes.c_int32), len(lens),
        reads_to_check, _ptr(out, ctypes.c_uint8),
    )
    return out.astype(bool)


def find_record_start_native(
    buf: np.ndarray,
    start: int,
    contig_lengths: np.ndarray,
    reads_to_check: int = 10,
    max_read_size: int = 10_000_000,
) -> int | None:
    """First boundary at/after start (flat offset), -1 if none; None if the
    native library is unavailable."""
    lib = load_native()
    if lib is None:
        return None
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    lens = np.ascontiguousarray(contig_lengths, dtype=np.int32)
    return int(
        lib.sbt_find_record_start(
            _ptr(buf, ctypes.c_uint8), len(buf), start,
            _ptr(lens, ctypes.c_int32), len(lens),
            reads_to_check, max_read_size,
        )
    )


def eager_check_window_native(
    buf: np.ndarray,
    candidates: np.ndarray,
    contig_lengths: np.ndarray,
    reads_to_check: int = 10,
    exact_eof: bool = False,
) -> np.ndarray | None:
    """Tri-state verdicts per candidate over a bounded window: 0/1 =
    certain fail/pass (chain resolved on in-window bytes), 2 = the verdict
    depended on the window edge (retry with more lookahead). ``None`` if
    the native library is unavailable."""
    lib = load_native()
    if lib is None:
        return None
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    cand = np.ascontiguousarray(candidates, dtype=np.int64)
    lens = np.ascontiguousarray(contig_lengths, dtype=np.int32)
    out = np.zeros(len(cand), dtype=np.uint8)
    lib.sbt_eager_check_window(
        _ptr(buf, ctypes.c_uint8), len(buf),
        _ptr(cand, ctypes.c_int64), len(cand),
        _ptr(lens, ctypes.c_int32), len(lens),
        reads_to_check, 1 if exact_eof else 0,
        _ptr(out, ctypes.c_uint8),
    )
    return out


def find_record_start_window_native(
    buf: np.ndarray,
    start: int,
    contig_lengths: np.ndarray,
    reads_to_check: int = 10,
    max_read_size: int = 10_000_000,
    exact_eof: bool = False,
) -> tuple[int, int] | None:
    """Tri-state bounded-window scan: ``(found, uncertain_at)``.

    ``found`` ≥ 0 is the first position whose chain passed on in-window
    bytes alone (certain). ``found`` = -1 with ``uncertain_at`` ≥ 0 means
    scanning stopped where a verdict depended on the window edge — every
    earlier position is a certain fail; grow the window and resume there.
    ``(-1, -1)`` = certain fails throughout the scanned span. ``None`` if
    the native library is unavailable."""
    lib = load_native()
    if lib is None:
        return None
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    lens = np.ascontiguousarray(contig_lengths, dtype=np.int32)
    uncertain = ctypes.c_int64(-1)
    found = int(
        lib.sbt_find_record_start_window(
            _ptr(buf, ctypes.c_uint8), len(buf), start,
            _ptr(lens, ctypes.c_int32), len(lens),
            reads_to_check, max_read_size,
            1 if exact_eof else 0, ctypes.byref(uncertain),
        )
    )
    return found, int(uncertain.value)


def rans_decompress_native(blob: bytes, out_size: int) -> bytes | None:
    """Native rANS 4x8 decode (cram/rans.py is the fallback + encoder).
    Returns None when the library is unavailable; raises on bad input."""
    lib = load_native()
    if lib is None:
        return None
    data = np.frombuffer(blob, dtype=np.uint8)
    out = np.empty(out_size, dtype=np.uint8)
    produced = lib.sbt_rans_decompress(
        _ptr(np.ascontiguousarray(data), ctypes.c_uint8), len(data),
        _ptr(out, ctypes.c_uint8), out_size,
    )
    if produced != out_size:
        raise IOError(f"rANS decode produced {produced}, wanted {out_size}")
    return out.tobytes()


def inflate_blocks_fast_into(
    comp: np.ndarray,
    offsets: np.ndarray,
    lengths: np.ndarray,
    out: np.ndarray,
    out_offsets: np.ndarray,
    out_lengths: np.ndarray,
) -> bool:
    """Fast table-driven inflate of raw-DEFLATE payloads into ``out``.

    Word copies only engage where >=8 bytes of room remain before the end
    of ``out`` (they degrade to byte copies near it), so callers may pass
    exact-size buffers; +8 slack past the last block's end recovers full
    speed on the tail. Blocks the fast decoder rejects are re-run through
    zlib, so a True return always means exact output (a block zlib rejects
    too raises ``BlockCorruptionError``); returns False only when the
    native library is unavailable (caller falls back entirely).
    """
    lib = load_native()
    if lib is None:
        return False
    count = len(offsets)
    if count == 0:
        return True
    start = 0
    while start < count:
        rc = lib.sbt_inflate_blocks_fast(
            _ptr(comp, ctypes.c_uint8),
            _ptr(offsets[start:], ctypes.c_int64),
            _ptr(lengths[start:], ctypes.c_int64),
            count - start,
            _ptr(out, ctypes.c_uint8),
            _ptr(out_offsets[start:], ctypes.c_int64),
            _ptr(out_lengths[start:], ctypes.c_int64),
            len(out),
        )
        if rc == 0:
            return True
        # Block (start + rc - 1) was rejected: decode it with zlib (the
        # permanent correctness fallback, which raises BlockCorruptionError
        # on a corrupt stream or a footer that lies) and resume after it.
        from spark_bam_tpu.bgzf.stream import inflate_block_payload

        i = start + int(rc) - 1
        o, l = int(offsets[i]), int(lengths[i])
        data = inflate_block_payload(
            comp[o: o + l].tobytes(), int(out_lengths[i]))
        oo = int(out_offsets[i])
        out[oo: oo + len(data)] = np.frombuffer(data, dtype=np.uint8)
        start = i + 1
    return True


def inflate_blocks_native(
    comp: np.ndarray,
    offsets: np.ndarray,
    lengths: np.ndarray,
    out_lengths: np.ndarray,
) -> np.ndarray | None:
    """Batched raw-DEFLATE inflate; returns the flat output buffer or None."""
    lib = load_native()
    if lib is None:
        return None
    comp = np.ascontiguousarray(comp, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    out_lengths = np.ascontiguousarray(out_lengths, dtype=np.int64)
    out_offsets = np.zeros(len(out_lengths), dtype=np.int64)
    np.cumsum(out_lengths[:-1], out=out_offsets[1:])
    out = np.empty(int(out_lengths.sum()), dtype=np.uint8)
    rc = lib.sbt_inflate_blocks(
        _ptr(comp, ctypes.c_uint8),
        _ptr(offsets, ctypes.c_int64),
        _ptr(lengths, ctypes.c_int64),
        len(offsets),
        _ptr(out, ctypes.c_uint8),
        _ptr(out_offsets, ctypes.c_int64),
        _ptr(out_lengths, ctypes.c_int64),
    )
    if rc != 0:
        raise IOError(f"native inflate failed at block {rc - 1}")
    return out
