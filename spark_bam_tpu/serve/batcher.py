"""Request batcher: coalesce concurrent window rows into one device tick,
and keep one tick ahead of the chip.

Scan-class requests (count/fleet) are expanded by the service into
window-row tasks; the batcher gathers rows arriving within ``tick_ms``
of the first, pads to the FIXED batch shape ``(batch_rows, window+PAD)``
and dispatches the mesh-cached serve step exactly once per tick. Fixed
shape + cached step ⇒ one trace at warm-up, zero re-traces in steady
state (docs/serving.md).

Rows from different files coalesce in one tick: the serve step takes
per-row contig dictionaries, so batching is purely shape-keyed.

One thread, two ticks deep (``Batcher._cycle``): tick k+1 is packed, put
and launched BEFORE tick k's result is read. The step is dispatched
asynchronously and donates no operand, so the runtime queues k+1 behind k
and the host's part of a turn (pack, put, launch, the result's copy, the
scatter, the threads the results wake) lies under the running step. With a
tick in flight and no row queued the batcher waits for the first of a row
(gathered as ever and launched under the running step) and the tick's
result (``_Tick.ready``): an idle daemon holds no result back, a thin
queue loses no overlap. The depth is the code's, not a setting.
"""

from __future__ import annotations

import threading
import time
from collections import Counter, deque
from concurrent.futures import Future

import numpy as np

from spark_bam_tpu import obs
from spark_bam_tpu.obs import account as obs_account
from spark_bam_tpu.obs import trace as obs_trace
from spark_bam_tpu.serve.config import MAX_CONTIGS


class RowTask:
    """One window row awaiting a device verdict.

    ``future`` resolves to ``(boundary_count, escaped_count)`` for the
    row's owned span, or to ``TimeoutError`` when the owning request's
    deadline passed while the row was still queued (load shedding).

    Rows capture the submitting thread's trace context at creation: a
    tick batches rows from many requests (many traces), so the dispatch
    emits one synthetic span event per row, parented under that row's
    request span rather than the shared tick. The request's cost
    accumulator (obs/account.py) rides along the same way — a shared
    tick bills each request its own rows.
    """

    __slots__ = ("window", "n", "at_eof", "lo", "own", "lengths", "nc",
                 "deadline_ts", "enqueued_ts", "future", "trace_id", "pspan",
                 "cost")

    def __init__(self, window, n, at_eof, lo, own, lengths, nc,
                 deadline_ts=None):
        self.window = window          # (W+PAD,) uint8, already padded
        self.n = int(n)
        self.at_eof = bool(at_eof)
        self.lo = int(lo)
        self.own = int(own)
        self.lengths = lengths        # (MAX_CONTIGS,) int32
        self.nc = int(nc)
        self.deadline_ts = deadline_ts  # monotonic seconds or None
        self.enqueued_ts = time.monotonic()
        self.future: Future = Future()
        ctx = obs_trace.current()
        self.trace_id = ctx.trace_id if ctx is not None else None
        self.pspan = ctx.span_id if ctx is not None else None
        self.cost = obs_account.current()


class _Tick:
    """A tick that is launched and not yet delivered: its rows, the step's
    result (still on its way), and where its own time starts."""

    __slots__ = ("batch", "shape", "out", "packed_ts", "t_put")

    def __init__(self, batch, shape, out, packed_ts, t_put):
        self.batch = batch
        self.shape = shape
        self.out = out
        self.packed_ts = packed_ts    # monotonic: the rows left the queue
        self.t_put = t_put            # (perf_counter, time) at its put

    def ready(self) -> bool:
        """The step's result is computed (a result without ``is_ready``,
        a numpy array, always is)."""
        is_ready = getattr(self.out, "is_ready", None)
        return is_ready is None or is_ready()


#: With a tick in flight the batcher looks at its result this often while
#: it waits for rows (the condition's wait is cut to it).
_RESULT_POLL_S = 0.0002


class Batcher:
    """Tick loop turning queued :class:`RowTask`s into serve-step calls."""

    def __init__(self, steps, width: int, batch_rows: int, tick_ms: float,
                 reads_to_check: int = 10, funnel: bool = False):
        ndev = steps.mesh.devices.size
        self.steps = steps
        self.ndev = int(ndev)
        self.width = int(width)                      # window + PAD
        self.batch_rows = -(-int(batch_rows) // ndev) * ndev
        self.tick_s = float(tick_ms) / 1000.0
        self._step = steps.serve_step(
            reads_to_check=reads_to_check, funnel=funnel,
        )
        self._queue: "deque[RowTask]" = deque()
        self._cond = threading.Condition()
        self._paused = False
        self._closed = False
        # The batcher thread's own: the tick launched and not yet
        # delivered, and (perf_counter, time) of the last result read.
        self._flight: "_Tick | None" = None
        self._last_result = (0.0, 0.0)
        self.batch_sizes: "Counter[int]" = Counter()
        self._thread = threading.Thread(
            target=self._loop, name="serve-batcher", daemon=True
        )
        self._thread.start()

    def submit(self, task: RowTask) -> Future:
        with self._cond:
            if self._closed:
                raise RuntimeError("batcher is closed")
            self._queue.append(task)
            self._cond.notify()
        return task.future

    def backlog(self) -> int:
        """Rows queued but not yet dispatched — the ``stats`` op exposes
        this so operators (and brownout postmortems) can see queue
        pressure building BEFORE latency percentiles move."""
        with self._cond:
            return len(self._queue)

    def set_batch_rows(self, batch_rows: int) -> int:
        """Retarget rows-per-tick at runtime (the ``tune`` op / fabric
        autoscaler). Rounded up to a mesh-size multiple as at startup, so
        the set of dispatch shapes — hence compiled executables — stays
        small and mesh-aligned. Returns the applied (rounded) value."""
        rows = -(-max(1, int(batch_rows)) // self.ndev) * self.ndev
        with self._cond:
            self.batch_rows = rows
            self._cond.notify()
        return rows

    def set_tick_ms(self, tick_ms: float) -> float:
        """Retarget the gather window (host-side only — no recompile).
        Written under the condition so the batcher thread's in-progress
        ``_take_batch`` never reads a torn/stale tick mid-gather."""
        tick_ms = max(0.0, float(tick_ms))
        with self._cond:
            self.tick_s = tick_ms / 1000.0
            self._cond.notify()
        return tick_ms

    def pause(self) -> None:
        """Hold the next launch (tests use this to force a full-batch
        coalesce). A tick already launched is still delivered."""
        with self._cond:
            self._paused = True

    def resume(self) -> None:
        with self._cond:
            self._paused = False
            self._cond.notify()

    def close(self) -> None:
        """The tick in flight is delivered and the rows queued are run;
        whatever the thread leaves behind fails."""
        with self._cond:
            self._closed = True
            self._paused = False
            self._cond.notify()
        self._thread.join(timeout=10)
        with self._cond:
            left = list(self._queue)
            self._queue.clear()
        self._fail(left, RuntimeError("batcher closed"))

    # ------------------------------------------------------------------

    def _wait(self, flight: "_Tick | None", left: float) -> bool:
        """One wait on the condition; with a tick in flight, cut to the
        poll of its result. True, and no wait, once that result is ready."""
        if flight is not None:
            if flight.ready():
                return True
            left = min(left, _RESULT_POLL_S)
        self._cond.wait(left)
        return False

    def _take_batch(self, flight: "_Tick | None") -> "list[RowTask]":
        """Rows for the next tick. Block for the first row, then gather up
        to ``batch_rows`` rows arriving within one tick. With a tick in
        flight both waits also end when its result is ready, so that the
        turn can deliver it: [] if no row came first. While paused no row
        is taken. [] with nothing in flight only at close."""
        with self._cond:
            while not self._queue or self._paused:
                if flight is None and self._closed:
                    return []
                if self._wait(flight, 0.05):
                    return []
            deadline = time.monotonic() + self.tick_s
            while len(self._queue) < self.batch_rows:
                left = deadline - time.monotonic()
                if left <= 0 or self._wait(flight, left):
                    break
            batch = []
            while self._queue and len(batch) < self.batch_rows:
                batch.append(self._queue.popleft())
            return batch

    def _loop(self) -> None:
        while True:
            # One span over the whole turn, so that what falls between its
            # phases (the GIL handed to the threads a tick's results woke)
            # is still the batcher's in a capture.
            with obs.span("serve.cycle"):
                if not self._cycle():
                    return

    def _cycle(self) -> bool:
        """One turn: wait for rows or for the result of the tick in
        flight, shed, launch the next tick, THEN read and scatter the one
        in flight. False at close, with nothing in flight."""
        flight = self._flight
        with obs.span("serve.batch_wait"):
            batch = self._take_batch(flight)
        if not batch and flight is None:
            return False
        # Shed rows whose request deadline already passed.
        now = time.monotonic()
        live = []
        for t in batch:
            if t.deadline_ts is not None and now > t.deadline_ts:
                obs.count("serve.shed")
                t.future.set_exception(
                    TimeoutError("deadline expired in serve queue")
                )
            else:
                live.append(t)
        self._flight = None
        if live:
            try:
                self._flight = self._launch(live)
            except BaseException as exc:  # this tick's rows, no other's
                self._fail(live, exc)
            else:
                if flight is not None:
                    obs.count("serve.ticks_overlapped")
        if flight is not None:
            try:
                self._deliver(flight)
            except BaseException as exc:
                self._fail(flight.batch, exc)
        return True

    @staticmethod
    def _fail(batch: "list[RowTask]", exc: BaseException) -> None:
        for t in batch:
            if not t.future.done():
                t.future.set_exception(exc)

    def _launch(self, batch: "list[RowTask]") -> _Tick:
        """Pack, put, launch: the step returns at once, queued behind the
        tick in flight if there is one. Fresh operands a tick: the put of
        this tick may still read them while the step before runs."""
        # Pad to the CURRENT target, or up to the next mesh multiple of the
        # gathered rows when a ``tune`` shrank batch_rows after this batch
        # was taken — the dispatch shape must always cover the batch.
        B = max(self.batch_rows, -(-len(batch) // self.ndev) * self.ndev)
        width = self.width
        with obs.span("serve.batch_pack", rows=len(batch), shape=B):
            ws = np.zeros((B, width), dtype=np.uint8)
            ns = np.zeros(B, dtype=np.int32)
            eofs = np.zeros(B, dtype=bool)
            los = np.zeros(B, dtype=np.int32)
            owns = np.zeros(B, dtype=np.int32)
            lens = np.zeros((B, MAX_CONTIGS), dtype=np.int32)
            ncs = np.ones(B, dtype=np.int32)  # benign dict for padding rows
            now = time.monotonic()
            for i, t in enumerate(batch):
                ws[i, : len(t.window)] = t.window
                ns[i] = t.n
                eofs[i] = t.at_eof
                los[i] = t.lo
                owns[i] = t.own
                lens[i, : len(t.lengths)] = t.lengths
                ncs[i] = t.nc
                obs.observe("serve.queue_ms", (now - t.enqueued_ts) * 1000.0)
        # Padding rows keep lo == own == 0: empty owned span, zero counts.
        put = self.steps.put
        t_put = (time.perf_counter(), time.time())
        with obs.span("serve.h2d"):
            operands = [put(a) for a in (ws, ns, eofs, los, owns, lens, ncs)]
        with obs.span("serve.step"):
            out = self._step(*operands)
        return _Tick(batch, B, out, now, t_put)

    def _deliver(self, tick: _Tick) -> None:
        """Wait for the tick's result, read it, scatter it. The tick's own
        time runs from the later of its put and the result of the tick
        before (it was queued behind that one's step: not its time) to its
        result in host memory; ``serve.tick`` carries it (``Span.took``)
        and the rows share it. A lone tick: put to result."""
        batch = tick.batch
        with obs.span("serve.tick", rows=len(batch), shape=tick.shape) as sp:
            with obs.span("serve.d2h"):
                res = np.asarray(tick.out)
            done = (time.perf_counter(), time.time())
            t0, t_wall = max(tick.t_put, self._last_result)
            tick_ms = (done[0] - t0) * 1000.0
            sp.took(tick_ms, t_wall)
        self._last_result = done
        with obs.span("serve.scatter", rows=len(batch)):
            self._scatter(batch, res, tick.packed_ts, tick_ms, t_wall)

    def _scatter(self, batch: "list[RowTask]", res, now: float,
                 tick_ms: float, t_wall: float) -> None:
        """After the tick: counters, each row's cost share and per-trace
        event, and the rows' futures."""
        self.batch_sizes[len(batch)] += 1
        obs.count("serve.batches")
        obs.observe("serve.batch_rows", len(batch))
        obs.count("serve.h2d_bytes", sum(len(t.window) for t in batch))
        # The funnel's evidence, as the count's paths name it: the rows'
        # stage-0 survivors and the lanes the step ran for them (each row
        # its own blocks; a padding row runs none).
        lanes = int(res[:, 3].sum())
        obs.count("funnel.survivors", int(res[:, 2].sum()))
        obs.count("funnel.lanes", lanes)
        obs.observe("serve.tick_lanes", lanes)
        # Per-row cost attribution: the same queue_ms the histogram saw,
        # an even 1/rows share of the tick's OWN time (``_deliver``: not
        # the step it was queued behind), and the row's own window bytes —
        # shares sum back to serve.tick / the serve.h2d_bytes counter
        # exactly (the bench conservation gate).
        share_ms = tick_ms / len(batch)
        for t in batch:
            if t.cost is not None:
                t.cost.add(
                    queue_ms=(now - t.enqueued_ts) * 1000.0,
                    device_ms=share_ms,
                    h2d_bytes=len(t.window),
                    rows=1,
                )
        # One synthetic dispatch event per traced row: the tick is shared
        # across requests, so each row's event parents under ITS request
        # span — this is the cross-process hop that makes a serve request
        # read router → worker → tick → device dispatch as one tree.
        reg = obs.registry()
        if reg is not None:
            for t in batch:
                if t.trace_id is not None:
                    reg.emit_span_event(
                        "serve.device_dispatch", tick_ms,
                        trace_id=t.trace_id, parent_span_id=t.pspan,
                        t_wall=t_wall, rows=len(batch),
                        queue_ms=round((now - t.enqueued_ts) * 1000.0, 3),
                    )
        for i, t in enumerate(batch):
            if not t.future.done():
                t.future.set_result((int(res[i, 0]), int(res[i, 1])))
