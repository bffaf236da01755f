"""A witness of the host, inside the program.

A whole-file pass on a one-chip machine is the host's from end to end, so
every stop and every slow stretch of the HOST is in its time, and a span
tree cannot say so: the span that was open when the machine stood still
reads long, whatever it was doing. The witness is the instrument that tells
the two apart: a thread that does nothing but wait ``TICK_S`` at a time. At
every wake it

- observes how late the wake came, in ms, less what Python's collector
  held it back (below): ``host.overshoot_ms``;
- where that is ``STOP_MS`` or more, counts ``host.stops`` and records a
  ``host.stop`` span event of that length, starting where the wait should
  have ended, in the trace of every pass open at that instant
  (``Registry.host_event``);
- times one fixed unit of work, a CRC of 64 KiB, in µs (``host.pace_us``):
  the host's speed, which moves a rate where no stop is seen;

and each wait is a ``host.sleep`` annotation, so that a profiler capture
has the witness on a line of its own, on the device's clock: a stop is one
overlong ``host.sleep`` that an idle gap of a device plane can be laid
against.

While it runs a ``gc.callbacks`` hook times every collection of Python's
collector and records the full ones (generation 2) and any of ``GC_MS`` or
more as ``host.gc`` span events, attached to the open passes the same way.
A collection holds the interpreter lock, which the witness's wake needs: a
full collection over a pass's heap takes 30-50 ms on the chips' hosts and
would read as a stop of the machine a few times a window. So the part of a
collection that ran past the end of the wait is taken out of that wake's
lateness (``_gc_held_ms``; the collection is as a rule still open when the
wake comes, and is counted up to the wake): a ``host.stop`` is time in which
this process did not run and its collector was not why.

What it sees is that this process did not run, not why. Another thread that
holds the interpreter lock for 40 ms reads as a stop too: a pass's own
account (``load.cpu_ms`` beside ``load.stop_ms``) tells that case apart
(``docs/observability.md``, "The host").

The registry starts it at its first root (``Registry._root_entered``) and
``Registry.close`` (``obs.shutdown()``) stops and joins it; nothing here
runs without a live registry. ``TICK_S`` and ``STOP_MS`` are the values of
the sleeper the long-read cells' stops were first found with (``PERF.md``
§2), so its tables stay comparable.
"""

from __future__ import annotations

import gc
import threading
import time
import zlib

from spark_bam_tpu.obs.registry import _annotation

TICK_S = 0.02
STOP_MS = 40.0
GC_MS = 1.0
THREAD_NAME = "spark-bam-host-witness"

_PACE_UNIT = bytes(64 << 10)


class Witness:
    """One witness thread and one collector hook for ``registry``.
    ``wait(seconds)`` is the wait between wakes (the stop event's, so that
    ``stop`` ends it at once); a test hands in its own and calls ``tick``
    itself."""

    def __init__(self, registry, wait=None):
        self.registry = registry
        self._halt = threading.Event()
        self._wait = wait if wait is not None else self._halt.wait
        self._thread: threading.Thread | None = None
        # Where the wait in progress should end; the collector's time past
        # that point in the collections that have ended (ms: the hook adds,
        # ``tick`` takes it out); and the collection in progress, if any.
        # Re-entrant: a collection may start on the witness's own thread.
        self._lock = threading.RLock()
        self._due = 0.0
        self._gc_late_ms = 0.0
        self._gc_t0 = 0.0
        self._gc_open = False

    def start(self) -> None:
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(
            target=self._run, name=THREAD_NAME, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._halt.set()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if self._thread is not None:
            self._thread.join(timeout=5.0)

    def _run(self) -> None:
        while not self._halt.is_set():
            self.tick()

    def tick(self) -> None:
        """One wait and what the wake found."""
        reg = self.registry
        wall = time.time()
        with self._lock:
            self._due = time.perf_counter() + TICK_S
            self._gc_late_ms = 0.0
        with _annotation("host.sleep", {}):
            self._wait(TICK_S)
        now = time.perf_counter()
        late_ms = (now - self._due) * 1e3 - self._gc_held_ms(now)
        if self._halt.is_set():
            return  # woken to end, not by the clock
        reg.histogram("host.overshoot_ms", unit="ms").observe(
            max(late_ms, 0.0))
        if late_ms >= STOP_MS:
            reg.counter("host.stops").inc()
            reg.host_event("host.stop", late_ms, wall + TICK_S)
        t0 = time.perf_counter()
        zlib.crc32(_PACE_UNIT)
        reg.histogram("host.pace_us", unit="us").observe(
            (time.perf_counter() - t0) * 1e6)

    def _gc_held_ms(self, now: float) -> float:
        """What of the collector's work lies between the end of the wait in
        progress and ``now``. The collection that held this wake back is as
        a rule still OPEN here: the interpreter hands the lock over as the
        hook's second call begins, before a line of it has run."""
        with self._lock:
            held = self._gc_late_ms
            if self._gc_open:
                held += max(0.0, now - max(self._gc_t0, self._due)) * 1e3
        return held

    def _on_gc(self, phase: str, info: dict) -> None:
        """Python's collector calls this at both ends of a collection, on
        the thread that triggered it, every other thread held off the
        interpreter meanwhile."""
        now = time.perf_counter()
        with self._lock:
            if phase == "start":
                self._gc_t0, self._gc_open = now, True
                return
            self._gc_open = False
            ms = (now - self._gc_t0) * 1e3
            self._gc_late_ms += max(
                0.0, now - max(self._gc_t0, self._due)) * 1e3
        if info["generation"] == 2 or ms >= GC_MS:
            self.registry.host_event(
                "host.gc", ms, time.time() - ms / 1e3,
                generation=info["generation"])
