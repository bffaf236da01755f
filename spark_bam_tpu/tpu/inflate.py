"""BGZF inflate feeding the device: windows of inflated bytes, made on the host.

The native table-driven inflater (``bgzf/flat.inflate_blocks``; zlib where
the native library is missing, and for any member the native inflater
rejects) decodes AND copies on a thread pool, and flat windows of inflated
bytes go to HBM, where the device does nothing but check them
(``checker.count_window``; on the mesh ``count_step``). One 24 MiB window
inflates in 14-50 ms on eight threads (sandbox CPU); handing the device a
member's LZ77 tokens to copy instead cost 5.2-5.9 s a window on a v5e and
lost every benchmark cell (``PERF.md`` §6, PRs 28 and 29), so there is one
way to inflate.

``InflatePipeline`` overlaps the stages: worker threads inflate up to
``depth`` window groups ahead while the consumer feeds the previous window
to the device; for the count they inflate into ``FRAMES``, host buffers
that are the windows' padded operands and outlive the pass.
``DeviceObserver`` times a window's H2D and program off the
feeding thread under a live registry, and ``maybe_profile_window`` captures
one steady window for ``--profile``.
"""

from __future__ import annotations

import contextlib
import logging
import os
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator

from spark_bam_tpu import obs

log = logging.getLogger(__name__)

import jax
import numpy as np

from spark_bam_tpu.bgzf.block import Metadata
from spark_bam_tpu.bgzf.flat import FlatView, inflate_blocks
from spark_bam_tpu.core.channel import open_channel


def attribute_ms(h2d_ms=None, device_ms=None) -> None:
    """Per-window H2D-vs-device attribution: each phase lands in an ms-unit
    histogram and, for ``top``, a gauge (last window + peak). No-op without
    a live registry."""
    r = obs.registry()
    if r is None:
        return
    for name, v in (("inflate.h2d_ms", h2d_ms),
                    ("inflate.device_ms", device_ms)):
        if v is not None:
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
    ``inflate.device_ms = t_ready(k) - max(t_dispatch(k), t_ready(k-1))``.
    ``device_ms`` is therefore the program's time plus whatever of its
    operand's H2D the previous program did not hide (all of it on the
    first window of a pass)."""

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

        # Started inside a pass: whatever it emits joins the pass's trace.
        self._threads.append(threading.Thread(
            target=obs.trace.carried(run), name=name, daemon=True))
        self._threads[-1].start()
        return q

    def window(self, operand, t_put: float, out, t_dispatch: float) -> None:
        """``operand``: the H2D array (None when the transfer happened on
        a producer thread); ``out``: an output of the dispatch to wait on
        (the count scalar, a step's totals)."""
        if operand is not None:
            if self._h2d is None:
                self._h2d = self._start("obs-h2d", self._on_h2d)
            self._h2d.put((operand, t_put))
        self._dev.put((out, t_dispatch))

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

    def _on_device(self, out, t_dispatch: float) -> None:
        out.block_until_ready()
        t_ready = time.perf_counter()
        self._observe((t_ready - max(t_dispatch, self._t_ready)) * 1e3)
        self._t_ready = t_ready

    @staticmethod
    def _observe(device_ms: float) -> None:
        attribute_ms(device_ms=device_ms)


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


class _Frames:
    """The host buffers a pass inflates into and puts from, kept from one
    window or step, and from one pass, to the next.

    A count window's buffer is 36 MiB, a mesh step's 32 MiB a row and as
    much again for check-bam's truth. Allocated anew each time they are
    fresh memory as often as not (glibc hands back what it freed, or maps
    new pages, by the state of its heap; at 32 MiB it always maps), and
    writing fresh memory costs page faults: 26 ms a window against 4 on a
    v5e host, 47 ms to set a row's 70,000 bytes of truth against 2, which
    is time the chips wait (``PERF.md`` section 6, PRs 31 and 43).

    What is kept is sets of arrays, found again by their shapes and dtypes,
    each with the note its user gave it back with; all of one kind, the
    newest given (a pass at another width drops what the one before it
    left), and at most ``keep`` of them, which the user gives as what one
    pass has in flight. The count's stream keeps ``KEEP`` frames (``depth``
    being inflated, one being put, the count's ring: 218 MiB); the mesh
    steps three steps' blocks a device (``parallel/stream_mesh``: 771 MiB
    for check-bam on four chips, 387 MiB for the count there)."""

    KEEP = 6

    def __init__(self):
        self._lock = threading.Lock()
        self._free: list = []  # (key, arrays, note), every key the same

    @staticmethod
    def _key(specs) -> list:
        return [(tuple(shape), np.dtype(dtype)) for shape, dtype in specs]

    def take(self, specs, make=None):
        """Arrays of ``specs`` (a ``(shape, dtype)`` each): ``(arrays,
        note)``, a kept set with the note it came back with, or arrays made
        anew (``make(shape, dtype)``, ``np.empty`` unless given) and no
        note."""
        key = self._key(specs)
        with self._lock:
            for i, (k, arrays, note) in enumerate(self._free):
                if k == key:
                    del self._free[i]
                    return arrays, note
        make = make or np.empty
        return [make(shape, dtype) for shape, dtype in key], None

    def give(self, arrays, keep: int = KEEP, note=None) -> None:
        """Hand back arrays nothing reads any more (the program they were
        put for has run, and its result is on the host). Sets of another
        kind than this one go."""
        key = self._key((a.shape, a.dtype) for a in arrays)
        with self._lock:
            same = [e for e in self._free if e[0] == key]
            self._free = same[: keep - 1] + [(key, arrays, note)]


FRAMES = _Frames()


class InflatePipeline:
    """Double-buffered host-inflate → device-window stream: worker threads
    inflate up to ``depth`` window groups ahead of the consumer, so the
    inflate of window k+1 overlaps the device's check of window k. The
    first group has the host to itself until the consumer comes back for
    the second: nothing runs on the device before the first window is put
    and dispatched, and the groups behind it are not needed for a window's
    time yet (their workers' Python, which holds the GIL, would otherwise
    stand in the feeding thread's way just then)."""

    def __init__(
        self,
        path,
        window_uncompressed: int = 64 << 20,
        threads: int = 8,
        depth: int = 2,
        metas: list | None = None,
    ):
        from spark_bam_tpu.bgzf.index_blocks import blocks_metadata

        self.path = path
        # ``metas``: reuse a prior metadata scan (whole-file header walk)
        # when the caller already has one.
        if metas is None:
            with obs.span("bgzf.read", kind="metadata_scan", path=str(path)):
                metas = list(blocks_metadata(path))
        self.metas = metas
        self.total = sum(m.uncompressed_size for m in self.metas)
        self.groups = window_plan(self.metas, window_uncompressed)
        self.threads = threads
        # Window groups in flight at once: >1 fans the produce stage out
        # across groups (on top of each group's internal block-slice
        # parallelism), keeping every host core busy while the device runs.
        self.depth = max(1, depth)

    def __iter__(self) -> Iterator[FlatView]:
        return self._views(None)

    def frames(self, lead: int, size: int) -> Iterator[FlatView]:
        """The same views, each inflated into a frame of ``size`` bytes
        (``FRAMES``) from offset ``lead`` on, zeros behind it: room in
        front for the window's carry, and the padded operand in place. The
        consumer gives a view's ``frame`` back once nothing reads it. A
        group must fit: ``lead`` + its bytes + 8 within ``size``."""
        return self._views((lead, size))

    def _views(self, framed: tuple[int, int] | None) -> Iterator[FlatView]:
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
        # Set once the consumer is back for the second view: by then the
        # first window is on its way to the device.
        first_taken = threading.Event()

        def produce(i):
            if i:
                first_taken.wait()
            group = self.groups[i]
            into = None
            if framed is not None:
                lead, size = framed
                total = sum(m.uncompressed_size for m in group)
                if lead + total + 8 > size:
                    # The native inflater writes where it is told to.
                    raise ValueError(
                        f"a group of {total} bytes does not fit a frame of "
                        f"{size} with {lead} in front")
                (frame,), _note = FRAMES.take([((size,), np.uint8)])
                into = (frame, lead)
            return inflate_blocks(
                ch, group, file_total=self.total, threads=self.threads,
                into=into,
            )

        try:
            # The pool's threads begin with an empty context: each group
            # takes the submitter's (``inflate.window`` in the pass's trace).
            pending = [
                pool.submit(obs.trace.carried(produce), i)
                for i in range(min(self.depth, len(self.groups)))
            ]
            for i in range(len(self.groups)):
                fut = pending.pop(0)
                # --profile: the trace spans one steady window's produce
                # overlap (the first whose padded block count has run
                # before), and is closed before the window is yielded so
                # consumer work stays out of it.
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
                            pool.submit(obs.trace.carried(produce), nxt))
                if i == len(self.groups) - 1:
                    view.at_eof = True
                yield view
                first_taken.set()
        finally:
            # Wait for in-flight produce calls: they hold zero-copy views of
            # the mmap, and closing it under them raises BufferError (or
            # worse). Queued-but-unstarted work is cancelled.
            first_taken.set()
            pool.shutdown(wait=True, cancel_futures=True)
            ch.close()
