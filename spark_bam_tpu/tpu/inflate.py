"""BGZF inflate feeding the device: the host path, and the two-phase device path.

**The default on every backend is host inflate** (path A): the native
table-driven inflater (``bgzf/flat.inflate_blocks``; zlib where the native
library is missing) decodes AND copies on a thread pool, and flat windows of
inflated bytes go to HBM, where the device does nothing but check them
(``checker.count_window``; on the mesh ``count_step``). A host that has just
decoded a token copies its bytes for nothing: one 24 MiB window inflates in
14-50 ms on eight threads (sandbox CPU), against 320-600 ms to tokenize it
for the device and 5.2-5.9 s for the device to resolve the tokens on a v5e
(``PERF.md`` §6, PR 28). ``Config.device_inflate=None`` resolves to this
path everywhere (``resolve_device_inflate``).

Path B, reached only by an explicit ``Config.device_inflate=True``, is the
**batched two-phase device inflate** (SURVEY §7 hard-part #1). Bit-serial
Huffman decoding resists lane-parallelism, so the split is:

1. *Host entropy phase* (`sbt_tokenize_deflate`, native/): decode the
   DEFLATE bitstream into per-output-byte tokens — ``lit[i]`` (the byte, if
   position ``i`` was emitted by a literal) and ``dist[i]`` (0 for
   literals; the back-reference distance otherwise, u16 — DEFLATE's max is
   32768). The LZ77 "copy" half of inflate — the memory-bandwidth half —
   is deferred entirely. Token rows for a whole window's worth of blocks
   are **packed into one contiguous u8 buffer** (lit plane then dist
   plane) so the H2D hop is a single 3-bytes-per-output-byte transfer,
   unpacked on device by a bitcast inside the same XLA program as the
   resolve kernel.
2. *Device copy phase* (`resolve_lz77`): every output byte's value is the
   byte at its pointer chain's root literal; parents materialize as
   ``i - dist`` from an iota. Chains collapse with lock-step
   pointer-doubling — ``parent = parent[parent]`` per round — which
   **early-exits as soon as every chain has reached its root**
   (``lax.while_loop`` convergence test; the same loop shape as the fused
   Pallas kernel in tpu/pallas_kernels.py, ``lz77_resolve_pallas``).
   ``log2(64 KiB) = 16`` rounds bound the worst case (a block-spanning
   distance-1 RLE run); typical BAM blocks converge in a handful, and the
   per-call round count feeds the ``inflate.rounds`` histogram.

Batching: ALL blocks of a window group go through one tokenize call, one
packed H2D transfer, and one resolve dispatch — (blocks, 64 Ki) lanes per
launch, batch dim padded to a power of two so jit shape churn is bounded.

``InflatePipeline`` overlaps the stages on either path: worker threads
inflate (path A), or run read + tokenize + pack + **async device dispatch**
(path B), for up to ``depth`` window groups while the consumer feeds the
previous window to the device.

Path B's fully device-resident consumer (``checker.count_window_tokens``)
takes the packed tokens directly, resolves + windows + counts inside ONE
program, and only scalars (and the halo carry) ever leave HBM — see
stream_check.StreamChecker._count_reads_fused. It ships until the
``simplicity`` PR that deletes it (ROADMAP D1/D2).

The checker consumes identical flat windows from either producer, and a
member the native inflater rejects still goes to zlib.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator

import numpy as np

from spark_bam_tpu import obs

log = logging.getLogger(__name__)

import jax
import jax.numpy as jnp
from jax import lax

from spark_bam_tpu.bgzf.block import MAX_BLOCK_SIZE, Metadata
from spark_bam_tpu.bgzf.flat import (
    FlatView, inflate_blocks, read_run_payloads, stage_run_payloads,
)
from spark_bam_tpu.core.channel import open_channel
from spark_bam_tpu.core.guard import INPUT_ERRORS

# Fixed token-row width: one BGZF block inflates to ≤ MAX_BLOCK_SIZE
# (reference Block.scala:49-51).
STRIDE = MAX_BLOCK_SIZE
_DOUBLING_ROUNDS = (STRIDE - 1).bit_length()  # collapses any chain in-range


def pack_tokens(lit: np.ndarray, dist: np.ndarray) -> np.ndarray:
    """Pack (B, STRIDE) u8/u16 token rows into ONE contiguous u8 buffer
    (lit plane, then the dist plane's little-endian bytes) — a single H2D
    transfer instead of two, and the layout `_unpack_tokens` bitcasts back
    for free on device."""
    return np.concatenate([
        np.ascontiguousarray(lit, dtype=np.uint8).reshape(-1),
        np.ascontiguousarray(dist, dtype="<u2").view(np.uint8).reshape(-1),
    ])


def _unpack_tokens(packed: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Device-side inverse of ``pack_tokens`` (shape-derived batch dim)."""
    plane = packed.shape[0] // 3
    b = plane // STRIDE
    with jax.named_scope("unpack"):
        lit = packed[:plane].reshape(b, STRIDE)
        dist = lax.bitcast_convert_type(
            packed[plane:].reshape(b, STRIDE, 2), jnp.uint16
        )
    return lit, dist


def _resolve_body(lit: jnp.ndarray, dist: jnp.ndarray):
    """The traced LZ77 resolve: early-exit pointer doubling.

    Returns ``(resolved (B, STRIDE) u8, rounds () i32)``. Convergence test:
    ``parent[parent] == parent`` everywhere ⇔ every pointer reached a root
    (roots are the only fixed points — dist=0 ⇒ parent=i), after which
    further doubling is the identity. Worst case ``_DOUBLING_ROUNDS``; a
    literal-only batch costs exactly one gather (the test itself)."""
    def cond(state):
        _, r, done = state
        return jnp.logical_and(~done, r < _DOUBLING_ROUNDS)

    def body(state):
        p, r, _ = state
        nxt = jnp.take_along_axis(p, p, axis=1)
        return nxt, r + jnp.int32(1), jnp.all(nxt == p)

    with jax.named_scope("lz77_resolve"):
        iota = jnp.arange(lit.shape[1], dtype=jnp.int32)[None, :]
        parent = iota - dist.astype(jnp.int32)
        roots, rounds, _ = lax.while_loop(
            cond, body, (parent, jnp.int32(0), jnp.bool_(False))
        )
        return jnp.take_along_axis(lit, roots, axis=1), rounds


@jax.jit
def resolve_lz77(lit: jnp.ndarray, dist: jnp.ndarray):
    """Device phase 2: resolve all LZ77 back-references in parallel.

    ``lit``/``dist`` are (B, STRIDE) u8/u16 token rows from the host
    entropy phase (dist=0 ⇒ literal). Returns ``(resolved, rounds)`` —
    the output bytes plus the number of pointer-doubling rounds the batch
    actually needed (early exit on convergence; see ``_resolve_body``).
    Padded tails are dist=0 identities, so they resolve to themselves
    harmlessly.
    """
    return _resolve_body(lit, dist)


@jax.jit
def _resolve_packed(packed: jnp.ndarray):
    """Unpack + resolve in ONE XLA program: the packed token buffer is the
    only H2D operand, the bitcast unpack fuses with the first gather."""
    lit, dist = _unpack_tokens(packed)
    return _resolve_body(lit, dist)


# Resolve straight from unpacked token planes (the device-tokenizer path:
# the planes were BORN on device, there is nothing to unpack). The donated
# variant aliases the lit plane into the resolved output — same (B, STRIDE)
# u8 shape — so the window ring's steady state reuses HBM instead of
# allocating a fresh output plane per window (``Config.inflate`` donate=off
# is the debugging escape hatch; tests/test_tokenize_device.py pins the
# flat-allocation regression).
_resolve_planes = jax.jit(_resolve_body)
_resolve_planes_donated = jax.jit(_resolve_body, donate_argnums=(0,))


# LZ77 resolve engine. ``auto`` is the XLA while_loop on every backend:
# Mosaic refuses ``lz77_resolve_pallas`` for the v5e (a (1, 64 Ki) row block
# is not divisible by 8 sublanes, and past that the body gathers over a
# whole 64 Ki row), so the kernel is reachable only by its explicit
# setting, SPARK_BAM_LZ77=pallas, and then raises what the compiler raised.
def _lz77_impl() -> str:
    env = os.environ.get("SPARK_BAM_LZ77", "").lower()
    return env if env in ("xla", "pallas") else "xla"


def _dispatch_resolve(packed: np.ndarray):
    """H2D + resolve dispatch (async; nothing is synced here). Returns
    ``(resolved_dev (B, STRIDE) u8, rounds_dev () i32)``."""
    if _lz77_impl() == "pallas":
        from spark_bam_tpu.tpu.pallas_kernels import (
            interpret_for_platform, lz77_resolve_pallas,
        )

        lit, dist = _unpack_tokens(jnp.asarray(packed))
        return lz77_resolve_pallas(
            lit, dist, interpret=interpret_for_platform()
        )
    return _resolve_packed(jnp.asarray(packed))


def _tok_impl(kernel: str = "auto") -> str:
    """Device-tokenizer engine for ``Config.inflate``'s kernel= knob.
    ``auto`` is the XLA vmap bit-reader on every backend: Mosaic refuses
    ``tokenize_pallas`` for the v5e the same way it refuses the LZ77
    kernel, so only ``kernel=pallas`` reaches it, and a refusal raises."""
    return "pallas" if kernel == "pallas" else "xla"


def _dispatch_tokenize(staged_dev, clens_dev, kernel: str = "auto"):
    """Device entropy phase dispatch (async; nothing synced). Takes the
    staged raw-payload matrix + per-row compressed lengths already on
    device; returns ``(lit, dist, out_lens_dev, ok_dev)`` token planes plus
    the per-row produced length and well-formedness flag the materialize
    sync validates against the block footers."""
    if _tok_impl(kernel) == "pallas":
        from spark_bam_tpu.tpu.pallas_kernels import (
            interpret_for_platform, tokenize_pallas,
        )

        return tokenize_pallas(
            staged_dev, clens_dev, interpret=interpret_for_platform()
        )
    from spark_bam_tpu.tpu.tokenize_device import tokenize_planes

    return tokenize_planes(staged_dev, clens_dev)


def _inflate_cfg(spec: str | None = None):
    """The effective ``InflateConfig``: an explicit spec (``Config.inflate``
    threaded down by callers that hold a Config) or the ``SPARK_BAM_INFLATE``
    env var (bench children, ad-hoc scripts)."""
    from spark_bam_tpu.core.inflate_config import InflateConfig

    if spec is None:
        spec = os.environ.get("SPARK_BAM_INFLATE", "")
    return InflateConfig.parse(spec)


def tokenize_pack(
    comp: np.ndarray,
    offsets: np.ndarray,
    lengths: np.ndarray,
    out_lengths: np.ndarray,
):
    """Host entropy phase for a batch of raw-DEFLATE payloads: tokenize,
    verify sizes against the block footers, pow2-pad the batch dim, pack.

    Returns ``(packed u8, out_lens i64 (B,), b)`` — ``b`` the real (un-
    padded) block count — or None when the native tokenizer is missing.
    Raises IOError when the tokenizer disagrees with the footers.
    """
    from spark_bam_tpu.native.build import tokenize_deflate_native

    t_host = time.perf_counter()
    with obs.span("inflate.tokenize", blocks=len(offsets)):
        toks = tokenize_deflate_native(comp, offsets, lengths, stride=STRIDE)
    if toks is None:
        return None
    lit, dist, out_lens = toks
    out_lengths = np.asarray(out_lengths, dtype=np.int64)
    if not np.array_equal(out_lens, out_lengths):
        raise IOError("tokenized output sizes disagree with block footers")
    # Pad the batch dim to a power of two so jit shape churn is bounded to
    # log2(max blocks) compiles, not one per distinct window block count.
    b = len(out_lens)
    b_pad = max(1 << max(b - 1, 0).bit_length(), 1)
    if b_pad != b:
        lit = np.concatenate([lit, np.zeros((b_pad - b, STRIDE), dtype=np.uint8)])
        # dist=0 rows are identity chains — the pad resolves to itself.
        dist = np.concatenate(
            [dist, np.zeros((b_pad - b, STRIDE), dtype=np.uint16)]
        )
    with obs.span("inflate.pack", blocks=b, bytes=lit.nbytes + dist.nbytes):
        packed = pack_tokens(lit, dist)
    # The host entropy phase IS tokenize+pack — both device-inflate
    # consumers (two-phase resolve and the fused count kernel) route
    # through here. Attributed under its own name so the device-tokenizer
    # A/B compares like with like; ``inflate.host_ms`` is only the residual
    # read/boundary-scan work either mode must do on host.
    attribute_ms(tokenize_host_ms=(time.perf_counter() - t_host) * 1e3)
    return packed, out_lens, b


def _record_rounds(rounds_dev) -> None:
    """Feed the rounds-to-convergence histogram (costs one scalar sync —
    only under a live registry)."""
    if obs.enabled():
        try:
            obs.observe("inflate.rounds", int(rounds_dev), unit="rounds")
        except Exception:
            pass


def attribute_ms(host_ms=None, h2d_ms=None, device_ms=None,
                 tokenize_host_ms=None, tokenize_device_ms=None) -> None:
    """Per-window host-vs-device attribution: each phase lands in an
    ms-unit histogram, and the three ``top`` shows (host / h2d / device)
    also as a gauge (last window + peak). No-op without a live registry.

    ``host_ms`` is ONLY the residual host work every mode shares (bulk
    read + boundary scan + staging); the entropy phase reports under the
    tokenize_* names so the host-vs-device tokenizer A/B reads directly
    off the attribution split.
    """
    r = obs.registry()
    if r is None:
        return
    for name, v, shown in (
            ("inflate.host_ms", host_ms, True),
            ("inflate.h2d_ms", h2d_ms, True),
            ("inflate.device_ms", device_ms, True),
            ("inflate.tokenize_host_ms", tokenize_host_ms, False),
            ("inflate.tokenize_device_ms", tokenize_device_ms, False)):
        if v is not None:
            if shown:
                r.gauge(name).set(round(v, 3))
            r.histogram(name, unit="ms").observe(v)


class DeviceObserver:
    """Waits on a window's device arrays OFF the thread that feeds the
    chip, so a live registry changes neither that thread's dispatches nor
    its waits. Built only under a live registry (``maybe``).

    The feeding thread hands over, per window, the H2D operand and one
    output of the dispatch with the host times it issued them at. Two
    daemon threads block on them in order: one (started by the first
    operand: the mesh steps hand over none) observes
    ``inflate.h2d_ms`` (issue to arrival of the operand, as a rule hidden
    behind the previous window's program), the other
    ``inflate.device_ms = t_ready(k) - max(t_dispatch(k), t_ready(k-1))``
    and, where the program resolves tokens and hands its round count over,
    ``inflate.rounds``. ``device_ms`` is therefore the program's time
    plus whatever of its operand's H2D the previous program did not hide
    (all of it on the first window of a pass)."""

    def __init__(self):
        self._threads: list = []
        self._h2d = None  # started by the first operand handed over
        self._dev = self._start("obs-device", self._on_device)
        self._t_ready = 0.0

    @classmethod
    def maybe(cls) -> "DeviceObserver | None":
        return cls() if obs.enabled() else None

    def _start(self, name: str, handle) -> queue.SimpleQueue:
        q: queue.SimpleQueue = queue.SimpleQueue()

        def run():
            while (item := q.get()) is not None:
                try:
                    handle(*item)
                except Exception:
                    # A failed program surfaces on the feeding thread, at
                    # its own next wait; there is nothing to time here.
                    log.debug("%s: wait failed", name, exc_info=True)

        self._threads.append(
            threading.Thread(target=run, name=name, daemon=True))
        self._threads[-1].start()
        return q

    def window(self, operand, t_put: float, out, t_dispatch: float,
               rounds=None) -> None:
        """``operand``: the H2D array (None when the transfer happened on
        a producer thread); ``out``: an output of the dispatch to wait on
        (the count scalar, a step's totals); ``rounds``: the LZ77 round
        count of a program that resolves tokens, None of one that only
        checks."""
        if operand is not None:
            if self._h2d is None:
                self._h2d = self._start("obs-h2d", self._on_h2d)
            self._h2d.put((operand, t_put))
        self._dev.put((out, t_dispatch, rounds))

    def close(self) -> None:
        """Drains the threads: every window handed over is observed."""
        for q in (self._h2d, self._dev):
            if q is not None:
                q.put(None)
        for thread in self._threads:
            thread.join()

    @staticmethod
    def _on_h2d(operand, t_put: float) -> None:
        operand.block_until_ready()
        attribute_ms(h2d_ms=(time.perf_counter() - t_put) * 1e3)

    def _on_device(self, out, t_dispatch: float, rounds) -> None:
        out.block_until_ready()
        t_ready = time.perf_counter()
        device_ms = (t_ready - max(t_dispatch, self._t_ready)) * 1e3
        self._t_ready = t_ready
        # A mesh step hands over one round count a chip: the most of them.
        self._observe(
            device_ms,
            None if rounds is None else int(np.asarray(rounds).max()),
        )

    @staticmethod
    def _observe(device_ms: float, rounds: int | None) -> None:
        attribute_ms(device_ms=device_ms)
        if rounds is not None:
            obs.observe("inflate.rounds", rounds, unit="rounds")


PROFILE_ENV = "SPARK_BAM_PROFILE"
_profiled = False
_profile_seen: set = set()


@contextlib.contextmanager
def maybe_profile_window(label: str = "inflate_window", shape=None):
    """One-shot ``jax.profiler.trace`` around ONE steady window of the
    process when ``SPARK_BAM_PROFILE`` names a dump directory (the CLI's
    ``--profile`` flag sets it): the first window whose ``shape`` (what
    its program's jit key depends on) has executed before, so the capture
    holds a window that does not compile, with the programs' scopes and
    the obs spans in it. A pass whose windows all differ in shape (a file
    of one window) captures nothing. Exactly one window is captured — the
    profiler's own overhead would poison every later window's host/device
    attribution. The dump path lands in the flight ring (and the log) so
    ``top``/postmortems can point an operator at the TensorBoard trace.
    Never raises: a missing/failed profiler degrades to a plain window."""
    global _profiled
    out = os.environ.get(PROFILE_ENV)
    if not out or _profiled:
        yield None
        return
    key = (label, shape)
    if key not in _profile_seen:
        _profile_seen.add(key)
        yield None
        return
    _profiled = True
    path = os.path.join(out, f"profile-{os.getpid()}-{label}")
    try:
        os.makedirs(path, exist_ok=True)
        prof = jax.profiler.trace(path)
        prof.__enter__()
    except Exception:
        log.warning("jax.profiler.trace unavailable; --profile window "
                    "skipped", exc_info=True)
        yield None
        return
    try:
        yield path
    finally:
        try:
            prof.__exit__(None, None, None)
        except Exception:
            log.warning("profiler dump failed", exc_info=True)
        else:
            from spark_bam_tpu.obs import flight

            flight.record("profile_dump", path=path, label=label)
            log.info("profiler trace for one %s written to %s", label, path)


def inflate_blocks_device(
    comp: np.ndarray,
    offsets: np.ndarray,
    lengths: np.ndarray,
    out_lengths: np.ndarray,
) -> np.ndarray | None:
    """Two-phase inflate of raw-DEFLATE payloads: host tokenize + packed
    H2D + device LZ77 resolution, all blocks in ONE kernel launch. Returns
    the concatenated output bytes, or None when the native tokenizer is
    unavailable (callers fall back to zlib)."""
    tp = tokenize_pack(comp, offsets, lengths, out_lengths)
    if tp is None:
        return None
    packed, out_lens, b = tp
    if obs.enabled():
        # Phase-split timing: H2D transfer (one packed buffer) vs the LZ77
        # kernel + D2H. The explicit sync between phases exists only under
        # a live registry — the production path keeps the async dispatch.
        t0 = time.perf_counter()
        with obs.span("inflate.h2d", blocks=b, bytes=packed.nbytes):
            packed_dev = jnp.asarray(packed)
            packed_dev.block_until_ready()
        t1 = time.perf_counter()
        obs.count("inflate.h2d_bytes", int(packed.nbytes))
        with obs.span("inflate.device_kernel", blocks=b):
            resolved_dev, rounds_dev = _resolve_packed(packed_dev)
            resolved = np.asarray(resolved_dev)[:b]
        attribute_ms(h2d_ms=(t1 - t0) * 1e3,
                     device_ms=(time.perf_counter() - t1) * 1e3)
        _record_rounds(rounds_dev)
    else:
        resolved_dev, rounds_dev = _dispatch_resolve(packed)
        resolved = np.asarray(resolved_dev)[:b]
    return np.concatenate(
        [resolved[i, :n] for i, n in enumerate(out_lens.tolist())]
    ) if len(out_lens) else np.empty(0, dtype=np.uint8)


def _read_group_payloads(ch, metas: list[Metadata]):
    """A group's payload buffer + per-block (offset, length) — one bulk
    positioned read for contiguous runs (host read phase)."""
    return read_run_payloads(ch, metas)


def tokenize_group(ch, metas: list[Metadata]):
    """Read + tokenize + pack one window group of blocks. Returns
    ``(packed, out_lens, b)`` or None (tokenizer unavailable); raises
    IOError on footer disagreement. This is the host half the fully
    device-resident count path feeds to ``checker.count_window_tokens``."""
    t0 = time.perf_counter()
    comp, offs, lens = _read_group_payloads(ch, metas)
    # Residual host work (read + boundary slices) — the part that stays on
    # host no matter where the entropy phase runs.
    attribute_ms(host_ms=(time.perf_counter() - t0) * 1e3)
    usizes = np.array([m.uncompressed_size for m in metas], dtype=np.int64)
    return tokenize_pack(comp, offs, lens, usizes)


def stage_group_device(ch, metas: list[Metadata]):
    """Read + stage + H2D one window group's RAW payloads — the worker-
    thread half of the device-tokenize path. Because this runs on the
    pipeline's producer threads (and the fused count's prefetch pool),
    window k+1's H2D overlaps window k's kernel: ``inflate.h2d_ms`` comes
    off the critical path entirely. Returns
    ``(staged_dev (B_pad, C_pad) u8, clens_dev (B_pad,) i32, usizes)``."""
    t0 = time.perf_counter()
    staged, clens = stage_run_payloads(ch, metas)
    attribute_ms(host_ms=(time.perf_counter() - t0) * 1e3)
    usizes = np.array([m.uncompressed_size for m in metas], dtype=np.int64)
    if obs.enabled():
        t0 = time.perf_counter()
        with obs.span("inflate.h2d", blocks=len(metas), bytes=staged.nbytes):
            staged_dev = jnp.asarray(staged)
            clens_dev = jnp.asarray(clens)
            staged_dev.block_until_ready()
        attribute_ms(h2d_ms=(time.perf_counter() - t0) * 1e3)
        obs.count("inflate.h2d_bytes", int(staged.nbytes))
    else:
        staged_dev = jnp.asarray(staged)
        clens_dev = jnp.asarray(clens)
    return staged_dev, clens_dev, usizes


class _PendingDeviceView:
    """A window group whose resolve dispatch is in flight: the device
    arrays plus everything needed to materialize a FlatView later (the
    double-buffering seam — workers dispatch, the consumer materializes).

    In device-tokenize mode ``tok_ok``/``tok_lens`` carry the bit-reader's
    per-row well-formedness flags and produced lengths; ``materialize``
    validates them against the block footers and raises IOError on any
    disagreement, so a malformed member demotes that window to host zlib —
    the device tokenizer can refuse bytes but never deliver wrong ones."""

    __slots__ = ("resolved_dev", "rounds_dev", "out_lens", "b", "metas",
                 "file_total", "at_eof", "tok_ok", "tok_lens")

    def __init__(self, resolved_dev, rounds_dev, out_lens, b, metas,
                 file_total, at_eof, tok_ok=None, tok_lens=None):
        self.resolved_dev = resolved_dev
        self.rounds_dev = rounds_dev
        self.out_lens = out_lens
        self.b = b
        self.metas = metas
        self.file_total = file_total
        self.at_eof = at_eof
        self.tok_ok = tok_ok
        self.tok_lens = tok_lens

    def materialize(self) -> FlatView:
        t0 = time.perf_counter()
        with obs.span("inflate.device_kernel", blocks=self.b):
            resolved = np.asarray(self.resolved_dev)[: self.b]
        # Async dispatch means the kernel+D2H wait is only observable at
        # the materialize sync — that wait is the window's device_ms.
        if obs.enabled():
            attribute_ms(device_ms=(time.perf_counter() - t0) * 1e3)
        if self.tok_ok is not None:
            ok = np.asarray(self.tok_ok)[: self.b]
            lens = np.asarray(self.tok_lens)[: self.b]
            expected = np.asarray(self.out_lens, dtype=np.int64)
            if not (ok.all() and np.array_equal(lens.astype(np.int64),
                                                expected)):
                obs.count("inflate.tokenize_demotions")
                bad = int(np.argmax(~ok | (lens.astype(np.int64) != expected)))
                raise IOError(
                    f"device tokenizer disagreed with block footers "
                    f"(first bad row {bad}: ok={bool(ok[bad])}, "
                    f"produced={int(lens[bad])}, footer={int(expected[bad])})"
                )
        _record_rounds(self.rounds_dev)
        data = np.concatenate(
            [resolved[i, :n] for i, n in enumerate(self.out_lens.tolist())]
        ) if len(self.out_lens) else np.empty(0, dtype=np.uint8)
        return _group_view(data, self.metas, self.file_total, self.at_eof)


def _group_view(
    data: np.ndarray, metas: list[Metadata], file_total, at_eof
) -> FlatView:
    usizes = np.array([m.uncompressed_size for m in metas], dtype=np.int64)
    block_flat = np.zeros(len(metas), dtype=np.int64)
    if len(metas):
        np.cumsum(usizes[:-1], out=block_flat[1:])
    total = int(usizes.sum())
    return FlatView(
        data,
        np.array([m.start for m in metas], dtype=np.int64),
        block_flat,
        file_total,
        at_eof or (file_total is not None and total == file_total),
    )


def dispatch_group_device(
    ch,
    metas: list[Metadata],
    file_total: int | None = None,
    at_eof: bool = False,
    inflate_spec: str | None = None,
) -> _PendingDeviceView | None:
    """Host phases + async device dispatch for one group; no sync. Returns
    None when the entropy phase is unavailable (host mode without the
    native tokenizer). ``inflate_spec`` is ``Config.inflate`` — its
    tokenize= knob routes the entropy phase (host tokenize+pack vs the
    device bit-reader over raw payload bytes)."""
    icfg = _inflate_cfg(inflate_spec)
    if icfg.resolve_tokenize() == "device":
        return _dispatch_group_raw(ch, metas, file_total, at_eof, icfg)
    t0 = time.perf_counter()
    comp, offs, lens = _read_group_payloads(ch, metas)
    attribute_ms(host_ms=(time.perf_counter() - t0) * 1e3)
    usizes = np.array([m.uncompressed_size for m in metas], dtype=np.int64)
    tp = tokenize_pack(comp, offs, lens, usizes)
    if tp is None:
        return None
    packed, out_lens, b = tp
    if obs.enabled():
        t0 = time.perf_counter()
        with obs.span("inflate.h2d", blocks=b, bytes=packed.nbytes):
            packed_dev = jnp.asarray(packed)
            packed_dev.block_until_ready()
        attribute_ms(h2d_ms=(time.perf_counter() - t0) * 1e3)
        obs.count("inflate.h2d_bytes", int(packed.nbytes))
        resolved_dev, rounds_dev = _resolve_packed(packed_dev)
    else:
        resolved_dev, rounds_dev = _dispatch_resolve(packed)
    return _PendingDeviceView(
        resolved_dev, rounds_dev, out_lens, b, metas, file_total, at_eof
    )


def _dispatch_group_raw(
    ch, metas, file_total, at_eof, icfg
) -> _PendingDeviceView:
    """Device-tokenize dispatch: raw payload bytes ship (≈1/3 the H2D
    traffic of packed token planes), the bit-reader kernel runs the entropy
    phase, and the LZ77 resolve consumes its planes in place — with
    donation on, the lit plane's HBM is reused as the resolved output, so
    steady state holds one staged matrix + two planes per in-flight window
    instead of growing per window. All dispatches are async; the footer
    validation happens at the materialize sync (never wrong bytes)."""
    staged_dev, clens_dev, usizes = stage_group_device(ch, metas)
    b = len(metas)
    if obs.enabled():
        t0 = time.perf_counter()
        with obs.span("inflate.tokenize_device", blocks=b):
            lit, dist, lens_dev, ok_dev = _dispatch_tokenize(
                staged_dev, clens_dev, icfg.kernel
            )
            ok_dev.block_until_ready()
        attribute_ms(tokenize_device_ms=(time.perf_counter() - t0) * 1e3)
    else:
        lit, dist, lens_dev, ok_dev = _dispatch_tokenize(
            staged_dev, clens_dev, icfg.kernel
        )
    obs.count("inflate.tokenize_blocks", b)
    resolve = _resolve_planes_donated if icfg.donate_enabled else _resolve_planes
    resolved_dev, rounds_dev = resolve(lit, dist)
    return _PendingDeviceView(
        resolved_dev, rounds_dev, usizes, b, metas, file_total, at_eof,
        tok_ok=ok_dev, tok_lens=lens_dev,
    )


def inflate_group_device(
    ch,
    metas: list[Metadata],
    file_total: int | None = None,
    at_eof: bool = False,
    inflate_spec: str | None = None,
) -> FlatView | None:
    """Two-phase device inflate of a run of blocks → FlatView (the device
    producer counterpart of bgzf/flat.py inflate_blocks; synchronous)."""
    pending = dispatch_group_device(
        ch, metas, file_total, at_eof, inflate_spec
    )
    if pending is None:
        return None
    return pending.materialize()


def inflate_file_device(path) -> FlatView | None:
    """Whole-file two-phase device inflate → FlatView (mirrors
    bgzf/flat.py flatten_file, with the device doing the copy phase)."""
    from spark_bam_tpu.bgzf.index_blocks import blocks_metadata

    metas = list(blocks_metadata(path))
    with open_channel(path) as ch:
        view = inflate_group_device(
            ch,
            metas,
            file_total=sum(m.uncompressed_size for m in metas),
            at_eof=True,
        )
    return view


def resolve_device_inflate(config) -> bool:
    """Resolve ``Config.device_inflate``'s auto (``None``) state: host
    inflate, on every backend and for every consumer. The host that decodes
    a member's tokens copies its bytes for a fraction of what handing the
    tokens to the device costs (the module text has the numbers), and no
    observable property of a BAM makes the device copy the better half, so
    there is nothing to select on: host inflate is the designed path and
    counts as no demotion. Only an explicit ``device_inflate=True`` reaches
    the two-phase device inflate and the fused count, where a host entropy
    phase without the native tokenizer raises on a TPU
    (``StreamChecker._count_reads_fused``). Never touches a JAX backend."""
    return bool(config.device_inflate)


def window_plan(metas: list[Metadata], window_uncompressed: int) -> list[list[Metadata]]:
    """Group consecutive blocks into ≈window-sized uncompressed runs."""
    groups: list[list[Metadata]] = []
    cur: list[Metadata] = []
    size = 0
    for m in metas:
        if cur and size + m.uncompressed_size > window_uncompressed:
            groups.append(cur)
            cur, size = [], 0
        cur.append(m)
        size += m.uncompressed_size
    if cur:
        groups.append(cur)
    return groups


class InflatePipeline:
    """Double-buffered host-inflate → device-window stream.

    With ``device_copy``, worker threads run the host phases (read +
    tokenize + pack) and the *async* device dispatch for up to ``depth``
    groups ahead; the consumer thread materializes resolved windows one at
    a time. Tokenize of window k+1 therefore overlaps the device resolve
    and D2H of window k — the device never idles on the host entropy
    phase."""

    def __init__(
        self,
        path,
        window_uncompressed: int = 64 << 20,
        threads: int = 8,
        device_copy: bool = False,
        depth: int = 2,
        metas: list | None = None,
        inflate_spec: str | None = None,
    ):
        from spark_bam_tpu.bgzf.index_blocks import blocks_metadata

        self.path = path
        # ``Config.inflate`` spec (tokenize=/kernel=/donate=); None reads
        # SPARK_BAM_INFLATE at dispatch time.
        self.inflate_spec = inflate_spec
        # ``metas``: reuse a prior metadata scan (whole-file header walk)
        # when the caller already has one.
        if metas is None:
            with obs.span("bgzf.read", kind="metadata_scan", path=str(path)):
                metas = list(blocks_metadata(path))
        self.metas = metas
        self.total = sum(m.uncompressed_size for m in self.metas)
        self.groups = window_plan(self.metas, window_uncompressed)
        self.threads = threads
        self.device_copy = device_copy
        # Window groups in flight at once: >1 fans the produce stage out
        # across groups (on top of each group's internal block-slice
        # parallelism), keeping every host core busy while the device runs.
        self.depth = max(1, depth)
        self._warned_device_demote = False

    def _demote_warn(self):
        obs.count("inflate.host_demotions")
        if not self._warned_device_demote:
            self._warned_device_demote = True
            log.warning(
                "device inflate rejected the input; demoting window(s) to "
                "host zlib (reported once per stream)", exc_info=True,
            )

    def __iter__(self) -> Iterator[FlatView]:
        ch = open_channel(self.path)
        if hasattr(ch, "set_plan"):
            # Remote data plane (core/remote_plan.py): the block table IS
            # the exact byte plan — hand it over so the channel coalesces
            # ranged GETs and prefetches in plan order instead of blindly
            # reading ahead of the cursor.
            ch.set_plan(
                (m.start, m.start + m.compressed_size) for m in self.metas
            )
        pool = ThreadPoolExecutor(max_workers=self.depth)

        def produce(group):
            if self.device_copy:
                # Host zlib answers MALFORMED INPUT only: a stream the
                # tokenizer can't take (or a size disagreement) demotes the
                # window, never kills the pipeline. Compiler and device
                # errors are not input errors and propagate.
                try:
                    pending = dispatch_group_device(
                        ch, group, file_total=self.total,
                        inflate_spec=self.inflate_spec,
                    )
                    if pending is not None:
                        return pending
                    # Host entropy phase without the native tokenizer.
                    obs.count("inflate.host_demotions")
                except INPUT_ERRORS:
                    self._demote_warn()
            return inflate_blocks(
                ch, group, file_total=self.total, threads=self.threads
            )

        try:
            pending = [
                pool.submit(produce, g) for g in self.groups[: self.depth]
            ]
            for i in range(len(self.groups)):
                fut = pending.pop(0)
                # --profile: the trace spans one steady window's produce
                # overlap AND its materialize sync (the first whose padded
                # block count has run before), and is closed before the
                # window is yielded so consumer work stays out of it.
                with maybe_profile_window(shape=max(
                        len(self.groups[i]) - 1, 0).bit_length()):
                    # Double-buffer health: time spent blocked on the host
                    # producer is exactly the stall the ``depth`` knob
                    # exists to hide.
                    with obs.span("inflate.stall_ms"):
                        view = fut.result()
                    nxt = i + self.depth
                    if nxt < len(self.groups):
                        pending.append(
                            pool.submit(produce, self.groups[nxt])
                        )
                    if isinstance(view, _PendingDeviceView):
                        # Materialize on the consumer thread: workers are
                        # already tokenizing the NEXT groups while this D2H
                        # syncs (the double-buffering overlap point). An
                        # The footer validation runs here — a window whose
                        # input it rejects demotes to host zlib.
                        try:
                            view = view.materialize()
                        except INPUT_ERRORS:
                            self._demote_warn()
                            view = inflate_blocks(
                                ch, self.groups[i], file_total=self.total,
                                threads=self.threads,
                            )
                if i == len(self.groups) - 1:
                    view.at_eof = True
                yield view
        finally:
            # Wait for in-flight produce calls: they hold zero-copy views of
            # the mmap, and closing it under them raises BufferError (or
            # worse). Queued-but-unstarted work is cancelled.
            pool.shutdown(wait=True, cancel_futures=True)
            ch.close()
