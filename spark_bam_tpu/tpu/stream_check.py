"""Streaming whole-file checking: larger-than-memory BAMs.

This is the production scale path of BASELINE.json's NA12878/WGS configs
*and* the path chip_smoke.py drives — one code path, O(window) host memory.

Design (the double-buffered halo-carry loop):

- The ``InflatePipeline`` produces block-aligned uncompressed windows
  (host-parallel inflate, two windows in flight). Each kernel buffer is
  ``carry + window`` where ``carry`` is the previous buffer's trailing
  ``halo`` bytes, so every owned position has ≥ ``halo`` bytes of
  lookahead for its ``reads_to_check`` chain.
- Ownership tiles the uncompressed stream exactly: a non-final buffer
  owns everything but its halo tail; the halo positions are owned (and
  re-evaluated with full lookahead) by the next buffer.
- Two windows are in flight: window *k+1* is dispatched to the device
  before window *k*'s results are materialized, so host inflate, H2D
  transfer, and the kernel overlap.
- Candidates whose chains outrun even the halo (ultra-long reads — the
  reference bounds a boundary scan by ``maxReadSize`` = 10 MB,
  check/.../package.scala:49-57) *escape*; escaped owned positions are
  deferred into a side buffer of raw bytes that grows until their chains
  can complete, then resolve through the native tri-state walk (verdict
  projections) or the NumPy engine (flag projections). Deferred
  positions are reported ``False`` in their covering span and re-emitted
  as contiguous-run spans once resolved; every resolution is vectorized
  — O(pending) per window, never O(pending²).

The span contract: ``spans()`` yields ``(base, verdict)`` pairs whose
``True`` positions are exactly the record starts of the file. Window
spans tile ``[0, total)`` in order; deferred candidates (``False`` in
their covering span) re-emit later as spans whose ``base`` lies strictly
*behind* the tiling frontier — that, not span length, is how to tell a
re-emission from a window span. The same
window loop also projects ``full_spans()`` (all-19-flag masks — the
full-check workload) and ``read_batches()`` (columnar parses with exact
spill decode — the load workload).

``count_reads()`` never materializes per-position arrays on host: each
window runs one fused kernel whose owned-span count reduces on-chip, the
sums accumulate on device, and a handful of integers cross the wire
per ~2^30 positions, beside one escape count a window, read where the
loop paces anyway (reference workload: count-reads,
docs/benchmarks.md:53-59). On every backend its windows reach the device
as inflated bytes: the pipeline's workers run the native inflate ahead of
the feeding thread, one H2D a window carries the window, and the device
program is the check and its count reduction (``checker.count_window``).
The count's escapes are a list of positions from that program, not
per-position arrays: ``_CountEscapes`` defers them into the same side
buffer, fed from the host buffers of the windows still in flight, and
adds what resolves to the total, so a record longer than the halo costs
its own candidates and not a second pass over the file.
"""

from __future__ import annotations

import time
from typing import Callable, Iterator

import numpy as np

import jax
import jax.numpy as jnp

from spark_bam_tpu import obs
from spark_bam_tpu.bam.header import read_header
from spark_bam_tpu.check.vectorized import check_flat
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.tpu.inflate import DeviceObserver, InflatePipeline


def _next_pow2(n: int) -> int:
    return 1 << max(0, (max(n, 1) - 1).bit_length())


def pad_contig_lengths(lengths: np.ndarray, cmax: int = 1024) -> np.ndarray:
    """Contig lengths zero-padded to a static kernel shape."""
    lens = np.zeros(max(cmax, len(lengths)), dtype=np.int32)
    lens[: len(lengths)] = lengths
    return lens


def halo_windows(pipeline, halo: int, header_end: int):
    """Yield ``(buf, base, own_end, lo, at_eof)`` rows with the halo-carry
    ownership discipline — the single source of truth for seam semantics,
    shared by ``StreamChecker`` (single device) and
    ``parallel.stream_mesh.count_reads_sharded`` (whole mesh):

    - each buffer is ``carry + window`` where carry is the previous
      buffer's trailing ``halo`` bytes, so every owned position has
      ≥ halo bytes of chain lookahead;
    - a non-final buffer owns everything but its halo tail (the next
      buffer re-evaluates those positions with full lookahead); the final
      buffer owns through EOF;
    - ``lo`` clamps the owned span's start past the BAM header, so header
      bytes are never counted as record starts.

    A view that was inflated into a frame (``InflatePipeline.frames``: room
    for a halo in front of its bytes) takes the carry there, and ``buf`` is
    the frame from the carry's start on: nothing else is copied.
    """
    carry = np.empty(0, dtype=np.uint8)
    base_next = 0
    for view in pipeline:
        base = base_next
        frame = getattr(view, "frame", None)
        if not len(carry):
            buf = view.data
        elif frame is None:
            buf = np.concatenate([carry, view.data])
        else:
            start = view.lead - len(carry)
            frame[start: view.lead] = carry
            buf = frame[start: view.lead + len(view.data)]
        n = len(buf)
        at_eof = view.at_eof
        own_end = n if at_eof else max(n - halo, 0)
        lo = min(max(header_end - base, 0), own_end)
        yield buf, base, own_end, lo, at_eof
        carry = buf[own_end:]
        base_next = base + own_end


@jax.jit
def _reduce_span(verdict, escaped, lo, hi):
    """Device-side reduction of one window's owned span → two scalars."""
    i = jnp.arange(verdict.shape[0], dtype=jnp.int32)
    m = (i >= lo) & (i < hi)
    return jnp.sum(m & verdict), jnp.sum(m & escaped)


class _CountEscapes:
    """The count's escaped candidates: the owned positions a window's
    program listed because their chains ran past its buffer, resolved on
    the host from bytes the stream inflates anyway.

    ``settle`` takes the oldest window of the count's ring once its escape
    count is read. The ring still holds that window's host buffer and those
    of the windows dispatched since, so a candidate is deferred with the
    bytes from its own position on (``_Deferred``, the spans path's side
    buffer), grown by one later window at a time and walked after each,
    until nothing is pending. ``starts`` counts the record starts among the
    resolved.
    ``overflowed`` says the pass must start over: the program could not
    list the window's escapes, or one keeps asking for lookahead beyond
    ``(reads_to_check + 2) * max_read_size`` (the mesh's cap,
    ``parallel/stream_mesh._RowGrowth``: adversarial size fields)."""

    def __init__(self, lengths: np.ndarray, config: Config,
                 found: list | None = None):
        self.deferred = StreamChecker._Deferred(lengths, config.reads_to_check)
        self.starts = 0
        self.overflowed = False
        # Where the resolved record starts lie (absolute), for a caller
        # that decodes them (the load); the count wants their number.
        self.found = found
        # A lookahead no real chain asks for is the count's cue to start
        # over. The load has handed rows out by then: it keeps deferring.
        self.cap_bytes = float("inf") if found is not None else (
            (config.reads_to_check + 2) * config.max_read_size)

    def settle(self, escaped: int, ring: list, at_eof: bool) -> None:
        """Pop ``ring[0]``, whose window reported ``escaped`` owned escapes;
        ``at_eof``: the newest window of the ring ends the file."""
        out, base, buf = ring.pop(0)
        deferred = self.deferred
        if not escaped and not len(deferred):
            return
        with obs.span("check.escape_resolve", escaped=escaped) as span:
            if escaped:
                if bool(out["esc_overflow"]):
                    self.overflowed = True
                    return
                at = np.asarray(out["esc_pos"])[:escaped].astype(np.int64)
                deferred.add(base + at, buf, base)
                obs.count("check.escape_candidates", escaped)
            # One later window at a time, and no further than the pending
            # chains ask: most end in the window after their own.
            held = len(deferred.buf)
            if not ring:
                self._walk(at_eof)
            for i, (_out, later_base, later) in enumerate(ring):
                if not len(deferred):
                    break
                deferred.extend(later, later_base)
                held = max(held, len(deferred.buf))
                self._walk(at_eof and i == len(ring) - 1)
            span.set(bytes=held)
            # What is left starts at the earliest unresolved candidate: a
            # chain that this much lookahead did not settle.
            self.overflowed = len(deferred.buf) > self.cap_bytes

    def _walk(self, at_eof: bool) -> None:
        for pos, (verdicts,) in self.deferred.resolve(at_eof, ("verdict",)):
            self.starts += int(verdicts.sum())
            if self.found is not None:
                self.found.extend((pos + np.flatnonzero(verdicts)).tolist())
            obs.count("check.escape_resolved", len(verdicts))


class _LoadObserver(DeviceObserver):
    """The load's windows on the device, taken off the feeding thread as the
    count's are, under a name of their own: ``load.device_ms``."""

    @staticmethod
    def _observe(device_ms: float) -> None:
        obs.observe("load.device_ms", device_ms, unit="ms")


class StreamChecker:
    """Whole-file streaming checker over a fixed device kernel window.

    Parameters mirror the ``spark.bam.*`` config surface: ``window``/``halo``
    from ``Config`` unless overridden; ``use_device=False`` runs the NumPy
    engine (the differential oracle) through the identical control flow.
    ``progress(windows_done, positions_done, total_positions)`` is invoked
    after each window resolves (the bench's per-window stage markers).
    """

    def __init__(
        self,
        path,
        config: Config = Config(),
        window_uncompressed: int | None = None,
        halo: int | None = None,
        use_device: bool = True,
        progress: Callable[[int, int, int], None] | None = None,
        pipeline_threads: int | None = None,
        pipeline_depth: int | None = None,
        metas: list | None = None,
    ):
        self.path = path
        self.config = config
        self.use_device = use_device
        self.progress = progress
        with obs.span("load.open", path=str(path)):
            self.header = read_header(path)
            self.lengths = np.array(
                self.header.contig_lengths.lengths_list(), dtype=np.int32
            )
        fresh = window_uncompressed or config.window_size
        halo = config.halo_size if halo is None else halo
        # The halo must leave room to advance; chains needing more lookahead
        # than the halo escape to the deferral path and still resolve exactly.
        self.halo = min(halo, fresh // 2)
        pipe_kw = {}
        if pipeline_threads is not None:
            pipe_kw["threads"] = pipeline_threads
        if pipeline_depth is not None:
            pipe_kw["depth"] = pipeline_depth
        # ``metas``: reuse a caller's whole-file block-metadata scan (a
        # header walk over every BGZF block — seconds on multi-GB files).
        self.pipeline = InflatePipeline(
            path, window_uncompressed=fresh, metas=metas, **pipe_kw,
        )
        self.total = self.pipeline.total
        # Kernel shape: one power of two covering carry + window, clamped to
        # the file so small inputs compile a small kernel.
        self.kernel_window = _next_pow2(
            min(fresh + self.halo, max(self.total, 1 << 16))
        )
        # Absolute flat offset of the first record: the header's size in
        # uncompressed bytes IS that offset (bam/header.py measures it by
        # position after the contig dictionary).
        self.header_end_abs = self.header.uncompressed_size
        # Flush the device count accumulators to host ints often enough
        # that the int32 sums cannot overflow: ≤ 2^30 positions per chunk
        # (Config.flush_every overrides within that cap).
        self.flush_every = config.flush_every_for(self.kernel_window)
        # Pacing depth of the count's window ring (Config.ring_depth).
        self.ring_depth = max(1, config.ring_depth)
        # Funnel totals across the consuming projection (positions
        # screened by stage 0 / stage-0 survivors); None until a funnelled
        # window lands — the CLI's ``funnel:`` summary line reads this.
        self.funnel_stats: dict | None = None

    # ------------------------------------------------------------ the loop
    def _windows(self, launch):
        """Yield ``(buf, base, own_end, at_eof, launched)`` one window behind
        the device: window *k+1* is dispatched before *k* is yielded, so the
        consumer's host work overlaps the device. Seam semantics live in
        ``halo_windows`` (shared with the mesh streaming path)."""
        prev = None
        for buf, base, own_end, lo, at_eof in halo_windows(
            self.pipeline, self.halo, self.header_end_abs
        ):
            out = launch(buf, len(buf), at_eof, lo, own_end)
            if prev is not None:
                yield prev
            prev = (buf, base, own_end, at_eof, out)
        if prev is not None:
            yield prev

    def _frame_rows(self, size: int):
        """``halo_windows`` over the pipeline's frames of ``size`` bytes
        (``InflatePipeline.frames``: the window's padded operand in place):
        ``(view, buf, base, own_end, lo, at_eof)`` a window, ``view.frame``
        the frame ``buf`` lies in, the consumer's to give back. Closing it
        shuts the pipeline's pool and channel."""
        views: list = []

        def tap():
            for view in self.pipeline.frames(self.halo, size):
                views.append(view)
                yield view

        rows = halo_windows(tap(), self.halo, self.header_end_abs)
        try:
            for row in rows:
                yield views.pop(0), *row
        finally:
            rows.close()

    def _device_inputs(self):
        lens = pad_contig_lengths(self.lengths)
        lens_dev = jax.device_put(jnp.asarray(lens))
        return lens_dev, jnp.int32(len(self.lengths))

    def _funnel_add(self, screened: int, survivors: int, lanes: int):
        """Fold one window's (or chunk's) funnel totals into the stats
        surface and the ``funnel.*`` observability counters: positions
        stage 0 screened, the survivors it left, and the lanes the lane
        stage ran for them (the count sizes that stage by its window's
        survivors; the other projections run the window's whole capacity)."""
        if self.funnel_stats is None:
            self.funnel_stats = {"screened": 0, "survivors": 0, "lanes": 0}
        self.funnel_stats["screened"] += screened
        self.funnel_stats["survivors"] += survivors
        self.funnel_stats["lanes"] += lanes
        if obs.enabled():
            obs.count("funnel.positions", screened)
            obs.count("funnel.survivors", survivors)
            obs.count("funnel.lanes", lanes)

    def _launcher(self, full_masks: bool = False):
        """Full-output launch (the spans path)."""
        if not self.use_device:
            return lambda buf, n, at_eof, lo, own_end: None  # host-lazy
        from spark_bam_tpu.tpu.checker import PAD, make_check_window

        kernel = make_check_window(
            self.kernel_window, self.config.reads_to_check,
            funnel=self.config.funnel_enabled(full_masks),
        )
        lens_dev, nc = self._device_inputs()
        w = self.kernel_window

        def launch(buf, n, at_eof, lo, own_end):
            padded = np.zeros(w + PAD, dtype=np.uint8)
            padded[:n] = buf
            # Fresh buffer per window (never mutated after dispatch): safe
            # under async dispatch even when jnp.asarray aliases zero-copy
            # on the CPU backend.
            return kernel(
                jnp.asarray(padded), lens_dev, nc, jnp.int32(n),
                jnp.bool_(at_eof),
            )

        return launch

    def _materialize(self, buf, at_eof, out) -> dict:
        """One window's per-position results as host arrays."""
        if out is None:
            res = check_flat(
                buf, self.lengths, at_eof=at_eof,
                reads_to_check=self.config.reads_to_check,
            )
            return {
                "verdict": res.verdict, "escaped": res.escaped,
                "exact": res.exact, "fail_mask": res.fail_mask,
                "reads_before": res.reads_before,
            }
        return {k: np.asarray(v) for k, v in out.items()}

    # --------------------------------------------------- deferred candidates
    class _Deferred:
        """Escaped owned positions + the byte stream that will resolve them.

        ``buf`` holds raw bytes from ``base`` (the earliest pending
        position) through the newest window's end; it extends as windows
        arrive and trims as pendings resolve. All operations are
        vectorized over the pending set.
        """

        def __init__(self, lengths: np.ndarray, reads_to_check: int):
            self.lengths = lengths
            self.rtc = reads_to_check
            self.pending = np.empty(0, dtype=np.int64)
            self.base = 0
            self.buf = np.empty(0, dtype=np.uint8)
            # Absolute stream tip at the last whole-buffer chains attempt
            # (the flags-projection resolver); gates re-attempts so the
            # O(retained-span) flag recompute runs only after meaningful
            # growth, not every window.
            self._gate_tip = 0

        def __len__(self):
            return len(self.pending)

        def extend(self, win_buf: np.ndarray, win_base: int):
            """Grow the byte stream with a window's newly-seen bytes."""
            if not len(self.pending):
                return
            tip = self.base + len(self.buf)
            if win_base + len(win_buf) > tip:
                self.buf = np.concatenate(
                    [self.buf, win_buf[max(tip - win_base, 0):]]
                )

        def add(self, positions: np.ndarray, win_buf: np.ndarray, win_base: int):
            if not len(positions):
                return
            if not len(self.pending):
                self.base = int(positions.min())
                self.buf = win_buf[self.base - win_base:].copy()
            self.pending = np.concatenate([self.pending, positions])

        def _retire(self, done: np.ndarray) -> np.ndarray:
            """Drop resolved pendings; trim the buffer to the earliest
            survivor. Returns the retired positions."""
            positions = self.pending[done]
            self.pending = self.pending[~done]
            if not len(self.pending):
                self.buf = np.empty(0, dtype=np.uint8)
            else:
                lo = int(self.pending.min())
                self.buf = self.buf[lo - self.base:]
                self.base = lo
            return positions

        def _resolve_chains(self, at_eof: bool):
            """One sequential-exact pass over pendings; returns (positions
            resolved, their ChainResult rows) and retires them.

            Retirement requires full exactness (``~escaped & exact``) — an
            inexact lane's flags may still change once the buffer grows past
            its chain, so it stays pending (it always converges: with the
            chain span fully in-buffer the re-check is exact, and at EOF
            everything is definitive)."""
            res = check_flat(
                self.buf, self.lengths,
                candidates=self.pending - self.base,
                at_eof=at_eof, reads_to_check=self.rtc,
            )
            done = (~res.escaped) & res.exact
            return self._retire(done), res, done

        @staticmethod
        def _emit_runs(positions: np.ndarray, rows: tuple):
            """Group ascending resolved positions into contiguous runs and
            yield span-style ``(run_start, per-field arrays)`` tuples —
            one emission per run instead of one per position (sub-record
            windows defer whole windows at a time; per-position tuples
            were the re-emission half of the long-read perf cliff)."""
            if not len(positions):
                return
            breaks = np.flatnonzero(np.diff(positions) != 1) + 1
            for seg in np.split(np.arange(len(positions)), breaks):
                yield int(positions[seg[0]]), tuple(r[seg] for r in rows)

        def resolve(self, at_eof: bool, fields: tuple[str, ...]):
            """Re-check pendings against the grown stream; yield
            ``(pos, row)`` — ``row`` holds one array per projected field
            covering a contiguous run of positions from ``pos`` — for
            each pending run now resolved with certainty.

            The verdict-only projection (spans/count) resolves through the
            native tri-state chain walk when built: it touches only the
            ~``reads_to_check`` records each chain actually visits. The
            flag projections need a whole-buffer flag pass per attempt
            (their masks come from the full pass), so attempts are gated:
            only at EOF or once the stream grew by ≥¼ of the retained
            span since the last attempt. Ungated, sub-record windows
            (ultra-long reads) recompute the span every window —
            O(span²) per record."""
            if not len(self.pending):
                return
            obs.count("check.defer_retries")
            if fields == ("verdict",):
                from spark_bam_tpu.native.build import eager_check_window_native

                tri = eager_check_window_native(
                    self.buf, self.pending - self.base, self.lengths,
                    reads_to_check=self.rtc, exact_eof=at_eof,
                )
                if tri is not None:
                    verdicts = tri[tri != 2] == 1
                    positions = self._retire(tri != 2)
                    obs.count("check.defer_resolved", len(positions))
                    yield from self._emit_runs(positions, (verdicts,))
                    return
            tip = self.base + len(self.buf)
            if not at_eof and tip - self._gate_tip < (tip - self.base) // 4:
                return
            self._gate_tip = tip
            positions, res, done = self._resolve_chains(at_eof)
            obs.count("check.defer_resolved", len(positions))
            rows = tuple(np.asarray(getattr(res, f))[done] for f in fields)
            yield from self._emit_runs(positions, rows)

    # ------------------------------------------------------------- consumers
    def _stream(
        self,
        fields: tuple[str, ...],
        defer_inexact: bool,
    ):
        """The shared window loop behind ``spans``/``full_spans``: project
        ``fields`` from each window's results, defer unresolved owned lanes
        (escaped chains; plus inexact ones when the projection includes
        flags), and re-emit them as contiguous-run spans once exact."""
        deferred = self._Deferred(self.lengths, self.config.reads_to_check)
        windows = 0
        funnel = self.use_device and self.config.funnel_enabled(defer_inexact)
        for buf, base, own_end, at_eof, out in self._windows(
            self._launcher(full_masks=defer_inexact)
        ):
            with obs.span("check.window", base=base, own=own_end):
                res = self._materialize(buf, at_eof, out)
                if funnel:
                    self._funnel_add(
                        len(buf), int(res["survivors"]), int(res["lanes"]))
                spans = [res[f][:own_end].copy() for f in fields]
                bad = res["escaped"][:own_end]
                if defer_inexact:
                    bad = bad | ~res["exact"][:own_end]
                deferred.extend(buf, base)
                bad_idx = np.flatnonzero(bad)
                if len(bad_idx):
                    for s in spans:
                        s[bad_idx] = 0  # re-emitted by the deferral path
                    deferred.add(base + bad_idx, buf, base)
            if obs.enabled():
                obs.count("check.windows")
                obs.count("check.deferred", len(bad_idx))
                # The escaped sum is an O(own_end) pass — only pay it
                # under a live registry.
                obs.count(
                    "check.escaped", int(res["escaped"][:own_end].sum())
                )
            yield (base, *spans)
            for pos, row in deferred.resolve(at_eof, fields):
                yield (pos, *row)
            windows += 1
            if self.progress is not None:
                self.progress(windows, base + own_end, self.total)
        assert not len(deferred), "pendings must resolve by EOF"

    def spans(self) -> Iterator[tuple[int, np.ndarray]]:
        """Yield ``(base, verdict)`` spans; see the module contract."""
        yield from self._stream(("verdict",), defer_inexact=False)

    def count_reads(self) -> int:
        """Record count (the count-reads workload).

        On device, each window runs ONE fused kernel (``count_window``: the
        check and its owned-span count reduction), and the per-window
        scalars accumulate *on device* — no count crosses the wire until a
        flush. The windows arrive as inflated bytes: the pipeline's workers
        inflate ``depth`` groups ahead (``inflate.stall_ms`` is this
        thread's wait for them), each into a frame that is the window's
        padded operand too (``InflatePipeline.frames``: room for the carry
        in front, zeros behind, kept from window to window and from pass to
        pass); this thread copies the 4 MiB carry in front, puts the frame
        (``inflate.h2d``) and dispatches (``inflate.device_kernel``). A
        pacing read of the two-windows-old escape count (``check.pace``:
        four bytes) bounds in-flight windows (and HBM), so this thread runs
        up to ``ring_depth`` windows ahead of the device and the device
        waits for it at the head of a pass only. Under a live registry a
        ``DeviceObserver`` takes ``inflate.device_ms`` off this thread,
        which dispatches and waits exactly as it does with the registry
        off. Inside a pass (``obs.pass_span``) the two ends of this loop lie
        under spans of their own, ``load.open`` and ``load.drain``, and each
        dispatch that has returned is marked (``obs.dispatched``).

        Owned candidates whose chains ran past their window's buffer (a
        record longer than the halo: ultra-long reads) come back from that
        read as a short list of positions. They resolve on this thread
        (``check.escape_resolve``: the native tri-state walk over the bytes
        the following windows bring, exact at EOF) and what resolves to a
        record start joins the total: the pass costs its escaped candidates
        and not the file. Only a window that overflows the list or its
        lanes, or a candidate that asks for more lookahead than
        ``(reads_to_check + 2) * max_read_size``, sends the whole file
        through the exact ``spans()`` path (``check.count_escape_retries``).
        """
        if not self.use_device:
            return self._count_via_spans()
        from spark_bam_tpu.tpu.checker import (
            ESCAPE_LIST, PAD, make_count_window,
        )
        from spark_bam_tpu.tpu.inflate import FRAMES

        funnel = self.config.funnel_enabled()
        with obs.span("load.open", program="count_window"):
            kernel = make_count_window(
                self.kernel_window, self.config.reads_to_check,
                funnel=funnel, escapes=ESCAPE_LIST,
            )
            lens_dev, nc = self._device_inputs()
            observer = DeviceObserver.maybe()
        w = self.kernel_window

        total = 0
        acc = None  # the windows' sums since the last flush, on device
        windows = 0
        chunk = 0
        screened = 0
        flush_every = self.flush_every
        # Windows dispatched and not yet read, oldest first, each with the
        # host buffer its escapes would resolve from: at most ring_depth + 1.
        ring: list = []
        escapes = _CountEscapes(self.lengths, self.config)
        # Every window is inflated into a frame that is its padded operand
        # too (carry in front, zeros behind); ``held`` names the frames of
        # the ring's windows.
        held: list = []
        rows = self._frame_rows(self.halo + w + PAD)

        def settle(escaped: int, at_eof: bool):
            """The ring's oldest window: its escapes, then its frame back."""
            escapes.settle(escaped, ring, at_eof)
            FRAMES.give([held.pop(0)])

        def fold():
            """The device sums since the last fold, into the host's."""
            nonlocal total, acc, chunk, screened
            total += int(acc["count"])
            if funnel:
                self._funnel_add(
                    screened, int(acc["survivors"]), int(acc["lanes"]))
            acc, chunk, screened = None, 0, 0

        try:
            while not escapes.overflowed:
                with obs.span("check.window", window=windows):
                    # The pipeline's wait for the host inflate
                    # (``inflate.stall_ms``) is inside this ``next``.
                    row = next(rows, None)
                    if row is None:
                        break
                    view, buf, base, own_end, lo, at_eof = row
                    n = len(buf)
                    t_put = time.perf_counter()
                    with obs.span("inflate.h2d", bytes=w + PAD):
                        # Not written again until the window has left the
                        # ring (its program has run): safe under async
                        # dispatch even when jnp.asarray aliases zero-copy
                        # on the CPU backend.
                        start = view.lead + len(view.data) - n
                        operand = jnp.asarray(
                            view.frame[start: start + w + PAD])
                    obs.count("inflate.h2d_bytes", w + PAD)
                    t_dispatch = time.perf_counter()
                    with obs.span("inflate.device_kernel"):
                        out = kernel(
                            operand, lens_dev, nc, jnp.int32(n),
                            jnp.bool_(at_eof), jnp.int32(lo),
                            jnp.int32(own_end),
                        )
                    obs.dispatched()
                    if observer is not None:
                        observer.window(
                            operand, t_put, out["count"], t_dispatch)
                    acc = {k: out[k] if acc is None else acc[k] + out[k]
                           for k in ("count", "survivors", "lanes")}
                    screened += n
                    ring.append((out, base, buf))
                    held.append(view.frame)
                    if len(ring) > self.ring_depth:
                        with obs.span("check.pace"):
                            escaped = int(ring[0][0]["esc_count"])
                        settle(escaped, at_eof)
                    windows += 1
                    chunk += 1
                    obs.count("check.windows")
                    if self.progress is not None:
                        self.progress(windows, base + own_end, self.total)
                    if chunk >= flush_every:
                        with obs.span("check.flush"):
                            fold()
            # The stream's end: the windows still in flight, oldest first,
            # then the sums.
            with obs.span("check.flush"):
                while ring and not escapes.overflowed:
                    settle(int(ring[0][0]["esc_count"]), True)
                if acc is not None and not escapes.overflowed:
                    fold()
        finally:
            # Closing the generator shuts the pipeline's pool and channel
            # before the exact path (if any) reopens the file.
            with obs.span("load.drain"):
                rows.close()
                if observer is not None:
                    observer.close()
        if escapes.overflowed:
            # The pass that starts over: the spans path resolves every
            # deferral bit-exactly. Suppress progress so consumers don't
            # see the counters restart.
            obs.count("check.escape_overflows")
            obs.count("check.count_escape_retries")
            saved, self.progress = self.progress, None
            try:
                return self._count_via_spans()
            finally:
                self.progress = saved
        assert not len(escapes.deferred), "escapes must resolve by EOF"
        return total + escapes.starts

    def _count_via_spans(self) -> int:
        he = self.header_end_abs
        return sum(
            int(v[max(he - b, 0):].sum()) for b, v in self.spans()
        )

    def full_spans(self) -> Iterator[tuple[int, "np.ndarray", "np.ndarray"]]:
        """Yield ``(base, fail_mask, reads_before)`` spans tiling the file —
        the streaming face of the *full* checker (all 19 flags per position;
        reference full/Checker.scala:17-198) in O(window) memory.

        Exactness discipline: owned lanes whose masks may be incomplete
        (escaped chains or buffer-edge-inexact failures) defer through the
        same side buffer as ``spans()`` — and stay deferred until a re-check
        is fully *exact* — then re-emit as contiguous-run spans (their
        slots in the covering span carry mask 0 / reads_before 0).
        """
        yield from self._stream(
            ("fail_mask", "reads_before"), defer_inexact=True
        )

    def read_batches(self, rows=None) -> Iterator[tuple[int, "object"]]:
        """Columnar ``ReadBatch``es per streaming window — the load path at
        WGS scale (O(window) host memory; reference CanLoadBam.scala:173-243
        loads per split, here per device window), of the records that pass
        ``rows`` (``parser.RowFilter``: loci and flag masks; every record
        without one).

        Yields ``(abs_base, batch)``: ``batch.starts`` index ``batch.buf``,
        a copy of the window's bytes from the first row's start to the last
        row's end, and ``abs_base + batch.starts`` are the rows' flat
        offsets. A window none of whose records passes yields nothing.

        The stream is the count's (``count_reads``): frames inflated by the
        pipeline's workers, one put a window, ``ring_depth`` windows ahead
        of the device, one read of a few integers a window where the loop
        paces anyway (``check.pace``). The program is ``load_window``: the
        count's check, and at the lanes it accepts the record parsed, tested
        and kept in a table on the device; the host reads the table's head,
        as many columns as rows passed rounded up to a power of two, one
        window later still (``load.batch``), so that read queues behind a
        program and the chip has the next one to run meanwhile. Nothing as
        wide as the window's positions comes back.

        Records that start in an owned span but extend past the window's
        lookahead (longer than the halo), and the rest of the candidates the
        program listed as escaped (``_CountEscapes``), are decoded exactly
        from a seekable stream and yielded with ``abs_base=-1`` (their
        ``starts`` index their own buffer), tested on the host. A window
        whose escapes the program could not list is checked and parsed on
        the host (``check.fused_demotions``), and so is every window
        without a device (``use_device=False``).
        """
        from spark_bam_tpu.tpu.parser import RowFilter

        rows = RowFilter.of() if rows is None else rows
        spill_abs: list[int] = []

        def spilled():
            """The spills so far, decoded and tested."""
            obs.count("load.spilled_records", len(spill_abs))
            for batch in self._decode_spills(sorted(spill_abs)):
                batch.columns["valid"] = rows.passes(batch.columns)
                yield -1, batch
            spill_abs.clear()

        windows = (self._load_windows if self.use_device
                   else self._host_windows)
        for item in windows(rows, spill_abs):
            yield item
            # Bound spill memory: flush in chunks during the stream, never
            # one unbounded EOF batch (ultra-long-read files spill often).
            if len(spill_abs) >= 4096:
                yield from spilled()
        if spill_abs:
            yield from spilled()

    def _host_rows(self, buf, base, lo, own_end, at_eof, rows):
        """One window checked and parsed without ``load_window``: ``(batch
        or None, escaped)``, the rows of its owned span that pass and the
        window positions of its owned escapes."""
        from spark_bam_tpu.tpu.parser import parse_flat_records

        res = check_flat(
            buf, self.lengths, at_eof=at_eof,
            reads_to_check=self.config.reads_to_check)
        starts = lo + np.flatnonzero(res.verdict[lo:own_end])
        escaped = lo + np.flatnonzero(res.escaped[lo:own_end])
        obs.count("load.records_parsed", len(starts))
        if not len(starts):
            return None, escaped
        batch = parse_flat_records(buf, starts)
        batch.columns["valid"] = rows.passes(batch.columns)
        obs.count("load.rows_out", len(batch))
        return (batch if len(batch) else None), escaped

    def _host_windows(self, rows, spill_abs: list):
        """``read_batches`` on the NumPy engine: the control flow of
        ``_load_windows`` one window at a time."""
        escapes = _CountEscapes(self.lengths, self.config, found=spill_abs)
        windows = 0
        for buf, base, own_end, lo, at_eof in halo_windows(
                self.pipeline, self.halo, self.header_end_abs):
            batch, escaped = self._host_rows(
                buf, base, lo, own_end, at_eof, rows)
            # What earlier windows left pending sees this one's bytes.
            escapes.deferred.extend(buf, base)
            escapes.settle(len(escaped), [(
                {"esc_overflow": False, "esc_pos": escaped}, base, buf)],
                at_eof)
            windows += 1
            obs.count("check.windows")
            if batch is not None:
                yield base, batch
            if self.progress is not None:
                self.progress(windows, base + own_end, self.total)
        assert not len(escapes.deferred), "escapes must resolve by EOF"

    def _load_windows(self, rows, spill_abs: list):
        """``read_batches`` on the device; see there. Appends to
        ``spill_abs`` the absolute starts that resolved off the device."""
        from spark_bam_tpu.tpu.checker import (
            LOAD_STATS, PAD, make_load_window, table_head,
        )
        from spark_bam_tpu.tpu.inflate import FRAMES
        from spark_bam_tpu.tpu.parser import ReadBatch, unpack_rows

        with obs.span("load.open", program="load_window"):
            kernel = make_load_window(
                self.kernel_window, self.config.reads_to_check)
            lens_dev, nc = self._device_inputs()
            rows_dev = jax.device_put(rows)
            observer = _LoadObserver.maybe()
        w = self.kernel_window
        stat = {name: i for i, name in enumerate(LOAD_STATS)}
        escapes = _CountEscapes(self.lengths, self.config, found=spill_abs)
        # Windows dispatched whose integers are unread, oldest first
        # (``count_reads``' ring), and behind it the windows whose rows are
        # on their way: the head of the table dispatched, the frame held.
        ring: list = []
        reading: list = []
        windows = 0
        stream = self._frame_rows(self.halo + w + PAD)

        def settle(at_eof: bool):
            """The ring's oldest window: its integers, its escapes, and
            the head of its table sent for."""
            out, base, buf = ring[0]
            with obs.span("check.pace"):
                stats = np.asarray(out["stats"])
            d2h = stats.nbytes
            batch = None
            if stats[stat["esc_overflow"]]:
                # More escapes than the list holds, or more survivors than
                # lanes: nothing of this window's is the device's.
                obs.count("check.fused_demotions")
                lo, own_end, eof, _n = out["span"]
                # A copy: the batch outlives the frame its bytes lie in.
                batch, at = self._host_rows(
                    buf.copy(), base, lo, own_end, eof, rows)
                ring[0] = ({"esc_overflow": False, "esc_pos": at}, base, buf)
                escaped, n_rows = len(at), 0
            else:
                escaped = int(stats[stat["esc_count"]])
                n_rows = int(stats[stat["rows"]])
                obs.count("load.records_parsed", int(stats[stat["count"]]))
                if escaped:
                    d2h += out["esc_pos"].nbytes
            self._funnel_add(
                out["span"][3], int(stats[stat["survivors"]]),
                int(stats[stat["lanes"]]))
            escapes.settle(escaped, ring, at_eof)
            head = None
            if n_rows:
                # Queued behind the programs dispatched since: read when
                # the next window has been dispatched behind it in turn.
                head = table_head(out["table"], _next_pow2(max(n_rows, 256)))
                head.copy_to_host_async()
                d2h += head.nbytes
            obs.count("load.d2h_bytes", d2h)
            reading.append((head, n_rows, base, buf, batch, out["frame"]))

        def rows_of():
            """The oldest window of ``reading`` as a batch (None without
            rows), and its frame given back."""
            head, n_rows, base, buf, batch, frame = reading.pop(0)
            if n_rows:
                with obs.span("load.batch", rows=n_rows):
                    columns, starts, fixups = unpack_rows(
                        np.asarray(head)[:, :n_rows], buf, rows)
                    obs.count("load.cigar_host_fixups", fixups)
                    if len(starts):
                        first = int(starts[0])
                        end = int(starts[-1]) + 4 + int(
                            columns["block_size"][-1])
                        columns["name_offset"] = (
                            columns["name_offset"] - first)
                        batch = ReadBatch(
                            columns, starts - first, buf[first:end].copy())
                        base += first
                        obs.count("load.rows_out", len(starts))
            FRAMES.give([frame], keep=FRAMES.KEEP + 1)
            return None if batch is None else (base, batch)

        try:
            while True:
                with obs.span("check.window", window=windows):
                    row = next(stream, None)
                    if row is None:
                        break
                    view, buf, base, own_end, lo, at_eof = row
                    n = len(buf)
                    t_put = time.perf_counter()
                    with obs.span("inflate.h2d", bytes=w + PAD):
                        start = view.lead + len(view.data) - n
                        operand = jnp.asarray(
                            view.frame[start: start + w + PAD])
                    obs.count("inflate.h2d_bytes", w + PAD)
                    t_dispatch = time.perf_counter()
                    with obs.span("inflate.device_kernel"):
                        out = dict(kernel(
                            operand, lens_dev, nc, jnp.int32(n),
                            jnp.bool_(at_eof), jnp.int32(lo),
                            jnp.int32(own_end), rows_dev,
                        ))
                    obs.dispatched()
                    if observer is not None:
                        observer.window(
                            operand, t_put, out["stats"], t_dispatch)
                    # Beside the program's outputs: the window's span and
                    # frame, and that its escape list is whole
                    # (``_CountEscapes`` asks; a window whose list is not is
                    # replaced in ``settle``).
                    out.update(span=(lo, own_end, at_eof, n),
                               frame=view.frame, esc_overflow=False)
                    ring.append((out, base, buf))
                    ready = []
                    if len(ring) > self.ring_depth:
                        settle(at_eof)
                        while len(reading) > 1:
                            ready.append(rows_of())
                    windows += 1
                    obs.count("check.windows")
                    if self.progress is not None:
                        self.progress(windows, base + own_end, self.total)
                yield from filter(None, ready)
            # The stream's end: the windows still in flight, oldest first.
            with obs.span("check.flush"):
                while ring:
                    settle(True)
                ready = [rows_of() for _ in range(len(reading))]
            yield from filter(None, ready)
        finally:
            with obs.span("load.drain"):
                stream.close()
                if observer is not None:
                    observer.close()
        assert not len(escapes.deferred), "escapes must resolve by EOF"

    def _decode_spills(self, positions: list[int], chunk_bytes: int = 64 << 20):
        """Exact single-record decode for starts whose bytes outran their
        window: read each record via the seekable stream and batch-parse in
        ≤``chunk_bytes`` buffers (bounded memory; offsets stay far inside
        the parser's int32 range)."""
        from spark_bam_tpu.bgzf.flat import metas_block_table, pos_of_flat_tables
        from spark_bam_tpu.bgzf.stream import (
            SeekableBlockStream,
            SeekableUncompressedBytes,
        )
        from spark_bam_tpu.core.channel import open_channel
        from spark_bam_tpu.core.pos import Pos
        from spark_bam_tpu.tpu.parser import parse_flat_records

        block_starts, block_flat = metas_block_table(self.pipeline.metas)
        stream = SeekableUncompressedBytes(
            SeekableBlockStream(open_channel(self.path))
        )
        try:
            parts: list[bytes] = []
            starts: list[int] = []
            off = 0
            for pos in positions:
                stream.seek(
                    Pos(*pos_of_flat_tables(block_starts, block_flat, pos))
                )
                size_bytes = stream.read(4)
                size = int.from_bytes(size_bytes, "little")
                parts.append(size_bytes + stream.read(size))
                starts.append(off)
                off += 4 + size
                if off >= chunk_bytes:
                    buf = np.frombuffer(b"".join(parts), dtype=np.uint8)
                    yield parse_flat_records(
                        buf, np.array(starts, dtype=np.int64)
                    )
                    parts, starts, off = [], [], 0
            if parts:
                buf = np.frombuffer(b"".join(parts), dtype=np.uint8)
                yield parse_flat_records(buf, np.array(starts, dtype=np.int64))
        finally:
            stream.close()

    def record_starts(self) -> Iterator[np.ndarray]:
        """Absolute flat offsets of record starts, one array per span, in
        stream order (deferred resolutions may append out of order)."""
        he = self.header_end_abs
        for base, verdict in self.spans():
            idx = base + np.flatnonzero(verdict)
            idx = idx[idx >= he]
            if len(idx):
                yield idx


def full_check_summary_streaming(
    path,
    config: Config = Config(),
    window_uncompressed: int | None = None,
    halo: int | None = None,
    use_device: bool = True,
    progress: Callable[[int, int, int], None] | None = None,
    metas: list | None = None,
) -> dict:
    """The full-check workload's aggregations at arbitrary scale: per-flag
    totals, considered-position count, and the critical (exactly one check
    failed) / two-check positions with their masks — computed from
    ``full_spans`` in O(window) memory (reference FullCheck.scala:112-417;
    BASELINE.json config "full-check split-point scan … all candidate
    offsets"). The CLI's in-memory path keeps the golden-output report for
    fixture-sized files; this is the WGS-scale library face.
    """
    from spark_bam_tpu.check.flags import (
        FLAG_NAMES,
        considered_mask,
        num_failing_fields,
    )

    checker = StreamChecker(
        path, config, window_uncompressed, halo, use_device, progress,
        metas=metas,
    )
    per_flag = np.zeros(len(FLAG_NAMES), dtype=np.int64)
    considered_total = 0
    crit_pos: list[np.ndarray] = []
    crit_mask: list[np.ndarray] = []
    two_pos: list[np.ndarray] = []
    two_mask: list[np.ndarray] = []
    for base, fm, rb in checker.full_spans():
        considered = considered_mask(fm, rb)
        considered_total += int(considered.sum())
        masked = fm[considered]
        for i in range(len(FLAG_NAMES)):
            per_flag[i] += int(((masked >> i) & 1).sum())
        nf = num_failing_fields(fm, rb)
        ones = np.flatnonzero(considered & (nf == 1))
        twos = np.flatnonzero(considered & (nf == 2))
        if len(ones):
            crit_pos.append(base + ones)
            crit_mask.append(fm[ones])
        if len(twos):
            two_pos.append(base + twos)
            two_mask.append(fm[twos])

    def cat_sorted(pos_parts, mask_parts):
        """Concatenate site arrays and restore ascending position order.

        Deferred re-emissions land *behind* the tiling frontier (the span
        contract above), so emission order is not ascending whenever any
        position resolved through the deferral path — sort here so the
        streaming summary's site order matches the in-memory path's.
        """
        pos = (
            np.concatenate(pos_parts) if pos_parts
            else np.empty(0, dtype=np.int64)
        )
        mask = (
            np.concatenate(mask_parts) if mask_parts
            else np.empty(0, dtype=np.int32)
        )
        if len(pos) > 1 and np.any(np.diff(pos) < 0):
            order = np.argsort(pos, kind="stable")
            pos, mask = pos[order], mask[order]
        return pos, mask

    if obs.enabled():
        # Distinct name from check_flat's ``check.flag_refutations.*``:
        # these totals are restricted to *considered* sites (and the device
        # path never passes through check_flat), so the two would
        # double-count under one name on the NumPy engine.
        for i, name in enumerate(FLAG_NAMES):
            # lint: allow[obs-contract] suffix bounded by FLAG_NAMES
            obs.count(f"check.flag_fail_sites.{name}", int(per_flag[i]))

    crit_pos_a, crit_mask_a = cat_sorted(crit_pos, crit_mask)
    two_pos_a, two_mask_a = cat_sorted(two_pos, two_mask)
    return {
        "per_flag": {
            name: int(per_flag[i]) for i, name in enumerate(FLAG_NAMES)
        },
        "considered": considered_total,
        "critical_positions": crit_pos_a,
        "critical_masks": crit_mask_a,
        "two_check_positions": two_pos_a,
        "two_check_masks": two_mask_a,
        "positions": checker.total,
    }


# ----------------------------------------------------------- module wrappers

def stream_verdicts(
    path,
    config: Config = Config(),
    window_uncompressed: int | None = None,
    halo: int | None = None,
    use_device: bool = True,
    progress: Callable[[int, int, int], None] | None = None,
) -> Iterator[tuple[int, np.ndarray]]:
    """Yield (base, verdict) spans tiling the file (see ``StreamChecker``)."""
    yield from StreamChecker(
        path, config, window_uncompressed, halo, use_device, progress
    ).spans()


def count_reads_streaming(
    path,
    config: Config = Config(),
    window_uncompressed: int | None = None,
    halo: int | None = None,
    use_device: bool = True,
    progress: Callable[[int, int, int], None] | None = None,
) -> int:
    """Record count via the streaming checker (the count-reads scale path)."""
    return StreamChecker(
        path, config, window_uncompressed, halo, use_device, progress
    ).count_reads()
