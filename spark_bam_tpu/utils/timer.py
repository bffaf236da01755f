"""Timers and heartbeats — thin shims over ``obs``.

The reference's observability is wall-clock ``Timer.time`` blocks and
heartbeat logging (SURVEY.md §5: ComputeSplits.scala:74-106,
IndexBlocks.scala:34-45; its docs admit "no profiling having been done").
These helpers predate the unified observability layer
(``spark_bam_tpu.obs``) and are kept as shims: a named ``Timer`` feeds
its duration into the live registry's ``timer.<name>`` histogram, and
heartbeats bump ``progress.beats``. New instrumentation should use
``obs.span``/``obs.counter`` directly; a device trace is ``--profile``
(``tpu/inflate.maybe_profile_window``, docs/observability.md).
"""

from __future__ import annotations

import contextlib
import logging
import time

from spark_bam_tpu import obs

log = logging.getLogger(__name__)


class Timer:
    """Named stage timer: ``with Timer() as t: ...; t.seconds / t.ms``.

    ``seconds`` is the measured float duration; ``ms`` derives from it
    (also float — the old int truncation erased sub-millisecond stages
    entirely).
    """

    def __init__(self, name: str = "", echo=None):
        self.name = name
        self.echo = echo
        self.seconds = 0.0

    @property
    def ms(self) -> float:
        return self.seconds * 1e3

    def __enter__(self) -> "Timer":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        if self.name:
            # lint: allow[obs-contract] timer names are literal strings at
            # Timer(...) construction sites — a fixed, code-reviewed set
            obs.observe(f"timer.{self.name}", self.ms, unit="ms")
        if self.echo is not None and self.name:
            self.echo(f"{self.name}: {self.ms:.3f}ms")


@contextlib.contextmanager
def heartbeat(what: str, interval_seconds: float = 10.0):
    """Yields a callable ``beat(progress)``; logs at most every interval."""
    last = time.monotonic()

    def beat(progress):
        nonlocal last
        obs.count("progress.beats")
        now = time.monotonic()
        if now - last >= interval_seconds:
            log.info("%s: %s", what, progress)
            last = now

    yield beat


@contextlib.contextmanager
def heartbeat_progress(
    what: str, unit: str = "step", interval_seconds: float = 10.0
):
    """Heartbeat shaped as the streaming APIs' ``progress`` callback
    (``(k, done, total)`` — StreamChecker windows / sharded steps): yields
    a callable suitable for their ``progress=`` kwarg."""
    with heartbeat(what, interval_seconds) as beat:
        yield lambda k, done, total: beat(
            f"{unit} {k}, {done}/{total} positions"
        )
