"""Process-wide metrics registry and span tracing.

The reference's only observability is ad-hoc ``Timer.time`` blocks and
heartbeat log lines (SURVEY.md §5; its docs admit "no profiling having
been done"). This subsystem replaces that shape with first-class,
artifact-producing instrumentation for the BGZF→inflate→check→load hot
path:

- **Metrics**: labeled ``Counter`` / ``Gauge`` / ``Histogram`` series in
  one process-wide ``Registry`` (``obs.counter("bgzf.blocks_read")``).
- **Spans**: ``with obs.span("inflate.window", blocks=n):`` context
  managers that nest (contextvar stack — per asyncio task, per thread),
  record wall time, emit one structured JSONL event each, and feed a
  per-name duration histogram so aggregate timings survive even when the
  raw trace is capped.
- **Passes**: ``with obs.pass_span("load.count", path=p):`` is the root
  span of one whole-file pass: a trace of its own (every span of the pass
  carries its id), its account at its exit (``load.head_ms`` /
  ``load.drain_ms``, and what the host did to it: ``load.stop_ms`` /
  ``load.gc_ms`` / ``load.cpu_ms``), and the eight slowest passes of each
  root name kept with their spans summed by name
  (``snapshot()["slowest_passes"]``: the slowest; ``["slow_passes"]``).
- **The host** (``obs.witness``): from its first root on (a pass, or the
  daemon's ``serve.request``) a live registry keeps a witness thread and a
  hook on Python's collector: ``host.stop`` / ``host.gc`` events in the
  trace of every pass they fell into, ``host.overshoot_ms`` /
  ``host.pace_us`` over the window.
- **Exporters** (``obs.exporters``): JSONL trace file, Prometheus
  text-format snapshot, and a human summary in the reference's stats
  format (``core/stats.py``).

Disabled by default: until ``configure()`` installs a live registry,
every entry point returns a shared no-op singleton — no allocation, no
locking, no timestamps — so instrumented hot loops cost one attribute
load + one ``is None`` test. ``--metrics-out PATH`` on any CLI
subcommand (or the ``SPARK_BAM_METRICS_OUT`` env var) enables it for
that run and writes the trace on exit.

Span naming convention: dotted ``layer.stage`` names — ``bgzf.read``,
``inflate.window``, ``check.window``, ``load.partition``, ``mesh.step``
— so reports group naturally by hot-path layer.
"""

from __future__ import annotations

import contextvars
import json
import os
import random
import threading
import time
import zlib
from typing import Iterator

from spark_bam_tpu.obs import trace as _trace

# The open-span stack rides the execution CONTEXT, not the thread: on an
# asyncio loop every task shares one thread, and a thread-local stack
# would parent task B's span under whatever span task A still has open —
# grafting B onto A's trace and, once interleaved exits leak an entry,
# poisoning every later span on that thread. Immutable tuples + contextvar
# give each task (and each thread — fresh threads start with an empty
# context) its own properly-nested stack.
_SPAN_STACK: "contextvars.ContextVar[tuple]" = contextvars.ContextVar(
    "spark_bam_span_stack", default=()
)
# The whole-file pass open on this thread (``pass_span``): the thread that
# feeds the chip marks its dispatches on it (``dispatched``).
_PASS: "contextvars.ContextVar[PassSpan | None]" = contextvars.ContextVar(
    "spark_bam_pass", default=None
)

# Histograms keep raw samples (for reference-style stats rendering) up to
# this many observations; beyond it a uniform reservoir (algorithm R)
# replaces slots at random so long serve runs stay bounded while p50/p99
# remain stable. count/sum/min/max stay exact throughout.
_HIST_SAMPLE_CAP = 4096
# The JSONL trace buffer stops appending events past this; dropped events
# are counted and still feed the per-name duration histograms.
_TRACE_EVENT_CAP = 200_000
# The slowest passes kept a root name (``Registry._keep_slowest``).
_SLOW_PASSES = 8
# The spans that are roots: the first one a live registry sees starts its
# witness of the host (``obs.witness``). A pass is a root by its type.
_ROOT_SPANS = frozenset({"serve.request"})


_SCALARS = (int, float, str, bool)
_TraceAnnotation = None


def _annotation(name: str, attrs: dict):
    """A ``jax.profiler.TraceAnnotation`` of the span: while a profiler
    session runs, the span is an event on its thread's line of the
    capture's host plane, on the clock the device's operations are on
    (scalar attributes ride along as the event's stats). With no session
    it is a TraceMe that records nothing, a few hundred nanoseconds.
    jax is imported on the first span of a live registry, never by the
    no-op path."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(
        name, **{k: v for k, v in attrs.items() if isinstance(v, _SCALARS)}
    )


def _label_key(labels: dict) -> tuple:
    return tuple(sorted(labels.items()))


class Counter:
    """Monotonic counter; ``inc`` is the only mutator."""

    __slots__ = ("name", "labels", "value")

    def __init__(self, name: str, labels: dict):
        self.name = name
        self.labels = labels
        self.value = 0

    def inc(self, n: int = 1) -> None:
        self.value += n


class Gauge:
    """Last-write-wins scalar, plus a running max (peak tracking)."""

    __slots__ = ("name", "labels", "value", "max")

    def __init__(self, name: str, labels: dict):
        self.name = name
        self.labels = labels
        self.value = 0.0
        self.max = None

    def set(self, v) -> None:
        self.value = v
        if self.max is None or v > self.max:
            self.max = v


class Histogram:
    """Sample distribution: exact count/sum/min/max; raw values retained
    up to ``_HIST_SAMPLE_CAP``, then reservoir-downsampled (algorithm R)
    so hot serve paths never grow memory while quantiles stay a uniform
    sample of the full stream. The RNG is seeded from the series name so
    quantile renders are reproducible run-to-run.

    Two optional attachments (both None until something asks for them,
    so the default observe path pays nothing):

    - ``ring``: a bounded deque of ``(t, value)`` recent observations,
      installed when a time-series RingStore attaches to the registry —
      the source for quantile-over-window queries (obs/timeseries.py).
    - ``exemplars``: top-K ``[value, trace_id, t]`` triples pinned by
      the tail sampler (obs/sampler.py), linking a burning percentile
      to the exact trace that burned it.
    """

    __slots__ = ("name", "labels", "count", "sum", "min", "max", "values",
                 "_rng", "ring", "exemplars")

    def __init__(self, name: str, labels: dict):
        self.name = name
        self.labels = labels
        self.count = 0
        self.sum = 0.0
        self.min = None
        self.max = None
        self.values: list[float] = []
        self._rng = None
        self.ring = None
        self.exemplars = None

    def observe(self, v: float) -> None:
        self.count += 1
        self.sum += v
        if self.min is None or v < self.min:
            self.min = v
        if self.max is None or v > self.max:
            self.max = v
        r = self.ring
        if r is not None:
            r.append((time.time(), v))
        if len(self.values) < _HIST_SAMPLE_CAP:
            self.values.append(v)
        else:
            rng = self._rng
            if rng is None:
                seed = zlib.crc32(repr((self.name, self.labels)).encode())
                rng = self._rng = random.Random(seed)
            j = rng.randrange(self.count)
            if j < _HIST_SAMPLE_CAP:
                self.values[j] = v

    def add_exemplar(self, v: float, trace_id: str,
                     cap: int = 8) -> None:
        """Pin ``(v, trace_id)``, keeping the top-``cap`` by value."""
        ex = self.exemplars
        if ex is None:
            ex = self.exemplars = []
        ex.append([round(float(v), 3), trace_id, round(time.time(), 3)])
        if len(ex) > cap:
            ex.sort(key=lambda e: -e[0])
            del ex[cap:]


class Span:
    """One timed, nesting unit of work. Use via ``obs.span(name, **attrs)``.

    When a :mod:`spark_bam_tpu.obs.trace` context is bound (a serve
    request carried a trace_id across the wire), the span joins that
    trace: it mints its own span_id, parents under the caller's span
    (or the enclosing local span), and rebinds the trace contextvar for
    its duration so nested work — including threads that capture the
    context at the seam — lands in the same tree. With no trace bound,
    spans behave exactly as before (local name-parenting only).

    Every span is also a profiler annotation (``_annotation``), so a
    ``jax.profiler`` capture shows the program's spans beside the device's
    operations, on one clock.
    """

    __slots__ = ("registry", "name", "attrs", "parent", "depth", "_t0",
                 "t_wall", "trace_id", "span_id", "parent_span_id",
                 "_ctx_token", "_stack_token", "_annotation", "_took")

    def __init__(self, registry: "Registry", name: str, attrs: dict):
        self.registry = registry
        self.name = name
        self.attrs = attrs
        self.parent = None
        self.depth = 0
        self._took = None
        self._t0 = 0.0
        self.t_wall = 0.0
        self.trace_id = None
        self.span_id = None
        self.parent_span_id = None
        self._ctx_token = None
        self._stack_token = None
        self._annotation = None

    def set(self, **attrs) -> None:
        """Attach attributes mid-span (e.g. measured device time)."""
        self.attrs.update(attrs)

    def took(self, ms: float, t_wall: float) -> None:
        """The span's length and start as its caller measured them, for
        work that began before the span could open: a tick of the
        batcher's pipeline is put and launched a turn before its result is
        waited for. The histogram and the event take these; the profiler
        annotation stays what was open on the thread."""
        self._took = float(ms)
        self.t_wall = t_wall

    def __enter__(self) -> "Span":
        stack = _SPAN_STACK.get()
        if stack:
            top = stack[-1]
            self.parent = top.name
            self.depth = len(stack)
            if self.trace_id is None and top.trace_id is not None:
                self.trace_id = top.trace_id
                self.parent_span_id = top.span_id
        if self.trace_id is None:
            ctx = _trace.current()
            if ctx is not None:
                self.trace_id = ctx.trace_id
                self.parent_span_id = ctx.span_id
        if self.trace_id is not None:
            self.span_id = _trace.new_id()
            self._ctx_token = _trace.set_current(
                _trace.TraceContext(self.trace_id, self.span_id)
            )
        self._stack_token = _SPAN_STACK.set(stack + (self,))
        self._annotation = _annotation(self.name, self.attrs)
        self.t_wall = time.time()
        self._t0 = time.perf_counter()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._annotation.__exit__(None, None, None)
        ms = self._took
        if ms is None:
            ms = (time.perf_counter() - self._t0) * 1e3
        # reset() restores the exact entry-time stack — exits from
        # interleaved asyncio tasks can't pop each other's spans.
        _SPAN_STACK.reset(self._stack_token)
        if self._ctx_token is not None:
            _trace.reset(self._ctx_token)
            self._ctx_token = None
        self._finish(ms)

    def _finish(self, ms: float) -> None:
        self.registry._finish_span(self, ms)


class PassSpan(Span):
    """The root span of one whole-file pass (``load.count``,
    ``load.check_bam``): a trace of its own, so that every span of the
    pass, on the threads it starts too (``obs.trace.carried``), carries
    one identifier and the JSONL reads as one tree a pass.

    The feeding thread marks each dispatch that has returned
    (``dispatched``). While it is open the registry counts it among its
    open passes, and what the witness of the host sees falls into its trace
    and its account (``Registry.host_event``). At its exit the pass
    observes ``load.head_ms``, its start to the return of its first
    dispatch (what the chip waits for at the head of a pass, on the host's
    clock), and ``load.drain_ms``, the return of its last dispatch to its
    end; then what the host did to it, ``load.stop_ms`` (the machine's
    stops inside it; 0 in a clean pass), ``load.gc_ms`` (the collector's
    pauses) and ``load.cpu_ms`` (the CPU time of every thread of the
    process over the pass, the native inflater's too: a pass that took
    longer on the same CPU time was kept off the cores); and hands itself
    to the registry, which keeps the slowest passes of each root name with
    their spans summed by name (``Registry._keep_slowest``)."""

    __slots__ = ("_t_first", "_t_last", "_pass_token", "_mark", "_cpu0",
                 "host_ms")

    def __init__(self, registry: "Registry", name: str, attrs: dict):
        super().__init__(registry, name, attrs)
        self._t_first = self._t_last = None
        self._cpu0 = 0.0
        # Summed milliseconds of the host's events inside the pass; the
        # witness's thread and the collector's hook add to it.
        self.host_ms = {"host.stop": 0.0, "host.gc": 0.0}

    def __enter__(self) -> "PassSpan":
        self.trace_id = _trace.new_id()
        self._pass_token = _PASS.set(self)
        self._mark = self.registry._event_mark()
        super().__enter__()
        self._cpu0 = time.process_time()
        self.registry._root_entered(self)
        return self

    def dispatched(self) -> None:
        self._t_last = time.perf_counter()
        if self._t_first is None:
            self._t_first = self._t_last

    def _finish(self, ms: float) -> None:
        _PASS.reset(self._pass_token)
        r = self.registry
        r._pass_left(self)
        if self._t_first is not None:
            r.histogram("load.head_ms", unit="ms").observe(
                (self._t_first - self._t0) * 1e3)
            r.histogram("load.drain_ms", unit="ms").observe(
                ms - (self._t_last - self._t0) * 1e3)
        account = self.account()
        r.histogram("load.stop_ms", unit="ms").observe(account["stop_ms"])
        r.histogram("load.gc_ms", unit="ms").observe(account["gc_ms"])
        r.histogram("load.cpu_ms", unit="ms").observe(account["cpu_ms"])
        super()._finish(ms)
        r._keep_slowest(self, ms, account)

    def account(self) -> dict:
        """What the host did to the pass so far, in ms."""
        return {
            "stop_ms": round(self.host_ms["host.stop"], 3),
            "gc_ms": round(self.host_ms["host.gc"], 3),
            "cpu_ms": round((time.process_time() - self._cpu0) * 1e3, 3),
        }


class _NoopMetric:
    """Shared do-nothing Counter/Gauge/Histogram stand-in."""

    __slots__ = ()

    def inc(self, n: int = 1) -> None:
        pass

    def set(self, v=None, **attrs) -> None:
        pass

    def observe(self, v: float) -> None:
        pass

    def took(self, ms: float, t_wall: float) -> None:
        pass

    # Context-manager face: span() returns this same singleton when
    # observability is disabled — zero allocation on the hot path.
    def __enter__(self) -> "_NoopMetric":
        return self

    def __exit__(self, *exc) -> None:
        pass


NOOP = _NoopMetric()


class Registry:
    """Process-wide metric store + span trace buffer (thread-safe)."""

    def __init__(self, max_events: int = _TRACE_EVENT_CAP):
        # Re-entrant: Python's collector may call the witness's hook
        # (``host_event``) on a thread that is inside one of these blocks.
        self._lock = threading.RLock()
        self._counters: dict[tuple, Counter] = {}
        self._gauges: dict[tuple, Gauge] = {}
        self._hists: dict[tuple, Histogram] = {}
        self._events: list[dict] = []
        self._dropped = 0
        self._max_events = max_events
        self.t_start = time.time()
        # Time-series attachment (obs/timeseries.py): once a RingStore
        # attaches, new and existing histograms grow an observation ring
        # so quantile-over-window queries have raw samples to read.
        self.rings = None
        self._ring_obs_cap = 0
        # Tail-sampled trace drops are batched: ids land in this set and
        # the event buffer compacts once the set is large enough, so a
        # dropped request costs one set-add, not an O(events) sweep.
        self._dropped_traces: set = set()
        self._compactions = 0
        # The slowest passes seen of each root name, slowest first
        # (``_keep_slowest``).
        self._slowest: dict[str, list[dict]] = {}
        # The passes open now, and the witness of the host that the first
        # root starts (``_root_entered``, ``host_event``, ``close``).
        self._open: set[PassSpan] = set()
        self._witness = None

    # ------------------------------------------------------------- metrics
    def _get(self, table: dict, cls, name: str, labels: dict):
        key = (name, _label_key(labels))
        m = table.get(key)
        if m is None:
            with self._lock:
                m = table.setdefault(key, cls(name, labels))
            if (cls is Histogram and self._ring_obs_cap
                    and m.ring is None):
                from collections import deque

                m.ring = deque(maxlen=self._ring_obs_cap)
        return m

    def attach_rings(self, store) -> None:
        """Install a time-series RingStore: existing and future
        histograms get bounded ``(t, value)`` observation rings."""
        from collections import deque

        self.rings = store
        with self._lock:
            self._ring_obs_cap = int(store.obs_cap)
            hists = list(self._hists.values())
        for h in hists:
            if h.ring is None:
                h.ring = deque(maxlen=self._ring_obs_cap)

    def counter(self, name: str, **labels) -> Counter:
        return self._get(self._counters, Counter, name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get(self._gauges, Gauge, name, labels)

    def histogram(self, name: str, **labels) -> Histogram:
        return self._get(self._hists, Histogram, name, labels)

    # --------------------------------------------------------------- spans
    def span(self, name: str, **attrs) -> Span:
        if name in _ROOT_SPANS and self._witness is None:
            self._root_entered()
        return Span(self, name, attrs)

    def _finish_span(self, span: Span, ms: float) -> None:
        self.histogram(span.name, unit="ms").observe(ms)
        event = {
            "e": "span",
            "name": span.name,
            "ms": round(ms, 3),
            "t": round(span.t_wall, 6),
            "depth": span.depth,
        }
        if span.parent is not None:
            event["parent"] = span.parent
        if span.trace_id is not None:
            event["trace"] = span.trace_id
            event["span"] = span.span_id
            if span.parent_span_id is not None:
                event["pspan"] = span.parent_span_id
        if span.attrs:
            event["attrs"] = {
                k: (v if isinstance(v, (int, float, str, bool, type(None)))
                    else str(v))
                for k, v in span.attrs.items()
            }
        self._append_event(event)

    def _append_event(self, event: dict) -> None:
        with self._lock:
            if len(self._events) < self._max_events:
                self._events.append(event)
            else:
                self._dropped += 1

    def emit_span_event(self, name: str, ms: float, *,
                        trace_id: str | None = None,
                        span_id: str | None = None,
                        parent_span_id: str | None = None,
                        t_wall: float | None = None,
                        **attrs) -> str | None:
        """Record a pre-timed span event without entering a context.

        The batcher uses this: one device tick serves rows from many
        traces, so the tick itself is a normal span while each row gets
        a synthetic per-trace event parented under its request span.
        Returns the (possibly minted) span_id.
        """
        self.histogram(name, unit="ms").observe(ms)
        return self._timed_event(name, ms, trace_id, span_id,
                                 parent_span_id, t_wall, attrs)

    def _timed_event(self, name: str, ms: float, trace_id, span_id,
                     parent_span_id, t_wall, attrs: dict) -> str | None:
        event = {
            "e": "span",
            "name": name,
            "ms": round(ms, 3),
            "t": round(t_wall if t_wall is not None else time.time(), 6),
            "depth": 0,
        }
        if trace_id is not None:
            if span_id is None:
                span_id = _trace.new_id()
            event["trace"] = trace_id
            event["span"] = span_id
            if parent_span_id is not None:
                event["pspan"] = parent_span_id
        if attrs:
            event["attrs"] = {
                k: (v if isinstance(v, (int, float, str, bool, type(None)))
                    else str(v))
                for k, v in attrs.items()
            }
        self._append_event(event)
        return span_id

    # ---------------------------------------------------------------- host
    def _root_entered(self, root: "PassSpan | None" = None) -> None:
        """A root has begun: ``root`` counts among the open passes, and the
        first root of this registry starts its witness of the host. Not
        ``configure()``: a registry made for one counter starts no
        thread."""
        with self._lock:
            if root is not None:
                self._open.add(root)
            if self._witness is not None:
                return
            from spark_bam_tpu.obs.witness import Witness

            self._witness = Witness(self)
            self._witness.start()

    def _pass_left(self, root: "PassSpan") -> None:
        with self._lock:
            self._open.discard(root)

    def host_event(self, name: str, ms: float, t_wall: float,
                   **attrs) -> None:
        """What the witness saw of the host (``host.stop``, ``host.gc``):
        the histogram ``name`` observed ONCE, and a span event of ``ms``
        from ``t_wall`` in the trace of EVERY pass open now, under its root
        span and into its account; with no pass open, one event without a
        trace."""
        self.histogram(name, unit="ms").observe(ms)
        with self._lock:
            passes = list(self._open)
        for p in passes:
            p.host_ms[name] += ms
            self._timed_event(name, ms, p.trace_id, None, p.span_id,
                              t_wall, attrs)
        if not passes:
            self._timed_event(name, ms, None, None, None, t_wall, attrs)

    def close(self) -> None:
        """Stop and join the witness, if one runs (``obs.shutdown()``)."""
        with self._lock:
            witness = self._witness
        if witness is not None:
            witness.stop()  # joins: not under the lock its thread takes

    # -------------------------------------------------------------- passes
    def _event_mark(self) -> tuple:
        """Where the event buffer stands: a pass's events lie behind the
        mark taken at its start (unless the buffer was compacted since)."""
        return len(self._events), self._compactions

    def _keep_slowest(self, root: PassSpan, ms: float, account: dict) -> None:
        """Keep ``root`` if it is among the ``_SLOW_PASSES`` longest passes
        of its name so far, slowest first: ``{root, ms, t, at_s, trace,
        stop_ms, gc_ms, cpu_ms, spans: {name: [count, summed ms, max
        ms]}}`` (``at_s``: its start, in seconds of this registry's life;
        the three after it: the pass's account of the host), the pass's span
        events (those that carry its trace, ``host.stop`` and ``host.gc``
        among them, the root's own left out) summed by name. Summed once,
        here, and only for a pass that enters the list; the other passes
        leave nothing but what the histograms hold. What the trace buffer
        dropped for its cap is not in the sums."""
        kept = self._slowest.get(root.name, ())
        if len(kept) == _SLOW_PASSES and kept[-1]["ms"] >= ms:
            return
        start, compactions = root._mark
        with self._lock:
            if compactions != self._compactions:
                start = 0
            mine = [e for e in self._events[start:]
                    if e.get("trace") == root.trace_id
                    and e.get("span") != root.span_id]
        spans: dict[str, list] = {}
        for e in mine:
            row = spans.setdefault(e["name"], [0, 0.0, 0.0])
            row[0] += 1
            row[1] = round(row[1] + e["ms"], 3)
            row[2] = max(row[2], e["ms"])
        record = {
            "root": root.name, "ms": round(ms, 3),
            "t": round(root.t_wall, 6),
            "at_s": round(root.t_wall - self.t_start, 3),
            "trace": root.trace_id, **account, "spans": spans,
        }
        with self._lock:
            kept = self._slowest.setdefault(root.name, [])
            kept.append(record)
            kept.sort(key=lambda p: -p["ms"])  # stable: ties keep their order
            del kept[_SLOW_PASSES:]

    # ------------------------------------------------------------ snapshot
    def snapshot(self) -> dict:
        """A point-in-time copy of every series (no trace events).
        ``slowest_passes``: the slowest pass a root name; ``slow_passes``:
        the ``_SLOW_PASSES`` slowest a root name, slowest first."""
        def copied(p: dict) -> dict:
            return {**p, "spans": {k: list(v) for k, v in p["spans"].items()}}

        with self._lock:
            # Lists first: the collector's hook may add a series on this
            # thread while the dicts below are built.
            counters = list(self._counters.values())
            gauges = list(self._gauges.values())
            hists = list(self._hists.values())
            slow = [copied(p) for kept in self._slowest.values() for p in kept]
            return {
                "counters": [
                    {"name": c.name, "labels": c.labels, "value": c.value}
                    for c in counters
                ],
                "gauges": [
                    {"name": g.name, "labels": g.labels, "value": g.value,
                     "max": g.max}
                    for g in gauges
                ],
                "hists": [
                    {"name": h.name, "labels": h.labels, "count": h.count,
                     "sum": h.sum, "min": h.min, "max": h.max,
                     "values": list(h.values),
                     **({"exemplars": [list(e) for e in h.exemplars]}
                        if h.exemplars else {})}
                    for h in hists
                ],
                "dropped_events": self._dropped,
                "slowest_passes": [
                    copied(kept[0]) for kept in self._slowest.values()],
                "slow_passes": slow,
            }

    def events(self) -> list[dict]:
        with self._lock:
            if not self._dropped_traces:
                return list(self._events)
            dropped = self._dropped_traces
            return [e for e in self._events
                    if e.get("trace") not in dropped]

    # -------------------------------------------------- tail-sample pruning
    #: pending trace drops before the event buffer compacts.
    _DROP_COMPACT = 64

    def drop_trace(self, trace_id: str) -> None:
        """Prune one trace's span events (tail sampling's drop verdict,
        obs/sampler.py). Batched: the id is noted now, the buffer
        compacts every ``_DROP_COMPACT`` drops; ``events()`` filters
        pending ids so readers never see a half-dropped state."""
        with self._lock:
            self._dropped_traces.add(trace_id)
            if len(self._dropped_traces) >= self._DROP_COMPACT:
                dropped = self._dropped_traces
                self._events = [
                    e for e in self._events
                    if e.get("trace") not in dropped
                ]
                self._dropped_traces = set()
                self._compactions += 1


# ------------------------------------------------------- module-level state

_active: Registry | None = None
_lock = threading.Lock()


def configure(max_events: int = _TRACE_EVENT_CAP) -> Registry:
    """Install (or return) the process-wide live registry."""
    global _active
    with _lock:
        if _active is None:
            _active = Registry(max_events=max_events)
        return _active


def shutdown() -> None:
    """Drop the live registry; instrumentation reverts to no-ops. Its
    witness of the host, if a root started one, is stopped and joined."""
    global _active
    with _lock:
        r, _active = _active, None
    if r is not None:
        r.close()


def enabled() -> bool:
    return _active is not None


def registry() -> Registry | None:
    """The live registry, or None when observability is disabled."""
    return _active


def counter(name: str, **labels):
    r = _active
    return NOOP if r is None else r.counter(name, **labels)


def gauge(name: str, **labels):
    r = _active
    return NOOP if r is None else r.gauge(name, **labels)


def histogram(name: str, **labels):
    r = _active
    return NOOP if r is None else r.histogram(name, **labels)


def span(name: str, **attrs):
    """A nesting wall-clock span; the shared no-op when disabled."""
    r = _active
    return NOOP if r is None else r.span(name, **attrs)


def annotate(name: str, **attrs) -> None:
    """Attach attributes to the innermost span called ``name`` that is open
    on this thread: for code that runs inside a span its caller opened.
    Nothing without a live registry, or with no such span open."""
    if _active is not None:
        for s in reversed(_SPAN_STACK.get()):
            if s.name == name:
                s.set(**attrs)
                return


def pass_span(name: str, **attrs):
    """The root span of a whole-file pass (``PassSpan``); the shared no-op
    when disabled."""
    r = _active
    return NOOP if r is None else PassSpan(r, name, attrs)


def dispatched() -> None:
    """The feeding thread's mark that a dispatch of the pass open on it
    has returned (``PassSpan.dispatched``). Nothing without a live
    registry, or outside a pass."""
    if _active is not None:
        p = _PASS.get()
        if p is not None:
            p.dispatched()


def count(name: str, n: int = 1) -> None:
    """One-shot unlabeled counter bump — the hot-loop shorthand."""
    r = _active
    if r is not None:
        r.counter(name).inc(n)


def observe(name: str, v: float, **labels) -> None:
    """One-shot histogram observation."""
    r = _active
    if r is not None:
        r.histogram(name, **labels).observe(v)


def export_jsonl(path, reg: Registry | None = None) -> str:
    """Write a registry's trace + final metric snapshot as JSONL.

    One JSON object per line: a ``meta`` header, every span event in
    completion order, then ``counter``/``gauge``/``hist`` snapshot lines
    and, a root name that ran, one ``slowest_pass`` line and up to eight
    ``slow_pass`` lines (the slowest first, the ``slowest_pass`` among them).
    Exports the live registry by default (safe to call with observability
    disabled — writes an empty-run file); pass ``reg`` to export an
    explicit instance (per-worker test registries).
    """
    r = reg if reg is not None else _active
    lines: list[str] = []
    meta = {
        "e": "meta",
        "version": 1,
        "t": round(time.time(), 6),
        "enabled": r is not None,
        "pid": os.getpid(),
    }
    lines.append(json.dumps(meta))
    if r is not None:
        for ev in r.events():
            lines.append(json.dumps(ev))
        snap = r.snapshot()
        for c in snap["counters"]:
            lines.append(json.dumps({"e": "counter", **c}))
        for g in snap["gauges"]:
            lines.append(json.dumps({"e": "gauge", **g}))
        for h in snap["hists"]:
            lines.append(json.dumps({"e": "hist", **h}))
        for p in snap["slowest_passes"]:
            lines.append(json.dumps({"e": "slowest_pass", **p}))
        for p in snap["slow_passes"]:
            lines.append(json.dumps({"e": "slow_pass", **p}))
        if snap["dropped_events"]:
            lines.append(json.dumps(
                {"e": "dropped", "count": snap["dropped_events"]}
            ))
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    return str(path)


def resolve_metrics_path(raw) -> "str | None":
    """Expand a ``--metrics-out`` / ``SPARK_BAM_METRICS_OUT`` value for
    THIS process: a ``{pid}`` placeholder is substituted, and a
    directory grows a ``trace-<pid>.jsonl`` inside it — so N fabric
    workers inheriting one env var write N distinct trace files instead
    of clobbering each other. Plain file paths pass through unchanged."""
    if not raw:
        return None
    raw = str(raw)
    if "{pid}" in raw:
        return raw.replace("{pid}", str(os.getpid()))
    if raw.endswith(os.sep) or os.path.isdir(raw):
        return os.path.join(raw, f"trace-{os.getpid()}.jsonl")
    return raw


def read_jsonl(path) -> Iterator[dict]:
    """Parse a JSONL trace back into event dicts (blank lines skipped)."""
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                yield json.loads(line)
