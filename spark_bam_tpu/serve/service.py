"""The split service: warm state + handlers behind an admission gate.

Long-running counterpart of the one-shot CLI paths. Three resident
tiers do the work the one-shot paths rebuild per invocation:

- ``MeshSteps`` (parallel/mesh.py): jit'd ``shard_map`` steps compiled
  once at warm-up, reused for every dispatch — no per-request re-trace.
- The file tier: a ``_FileState`` for every open file (header, contig
  dictionary, the member walk's block table, lazy record starts: no
  payload) and ``_Segments``, the inflated bytes: runs of whole rows of
  one file, inflated on first use, least recently used first out,
  bounded by ``ServeConfig.flat_cache`` bytes across all files.
- The shared ``.sbi`` ``CacheStore`` (sbi/store.shared_store): repeat
  plan requests resolve entirely from the sidecar index — zero
  ``load.split_resolutions``.

Scan-class requests are cut into window rows and answered through the
:class:`~spark_bam_tpu.serve.batcher.Batcher`; plan-class requests run
on a small worker pool against the index tier. Admission, deadlines and
shedding are described in docs/serving.md.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeout

import numpy as np

from spark_bam_tpu import obs
from spark_bam_tpu.bam.header import read_header
from spark_bam_tpu.obs import account as obs_account
from spark_bam_tpu.obs import flight
from spark_bam_tpu.obs import trace as obs_trace
from spark_bam_tpu.obs.sampler import TailSampler
from spark_bam_tpu.obs.slo import SloEngine
from spark_bam_tpu.obs.timeseries import RingStore
from spark_bam_tpu.bgzf.block import Metadata
from spark_bam_tpu.bgzf.flat import (
    inflate_blocks,
    metas_block_table,
    pos_of_flat_tables,
)
from spark_bam_tpu.bgzf.stream import scan_metadata
from spark_bam_tpu.core.channel import open_channel
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.core.faults import LatencyTracker
from spark_bam_tpu.core.guard import INPUT_ERRORS, ResourceExhausted
from spark_bam_tpu.parallel.mesh import make_mesh, mesh_steps
from spark_bam_tpu.serve.admission import CLASS_OF, AdmissionGate
from spark_bam_tpu.serve.batcher import Batcher, RowTask
from spark_bam_tpu.serve.config import MAX_CONTIGS, ServeConfig
from spark_bam_tpu.serve.protocol import encode, error_response, ok_response
from spark_bam_tpu.tpu.checker import PAD
from spark_bam_tpu.tpu.stream_check import pad_contig_lengths

#: Retry-After fallback before the latency tracker has enough samples.
_RETRY_AFTER_DEFAULT_MS = 50.0

#: Per-op latency window behind the ``stats`` percentiles (p50/p99) —
#: the numbers the fabric autoscaler and operators both read.
_LATENCY_WINDOW = 512


def _percentile(samples, q: float) -> "float | None":
    """Nearest-rank percentile over a small sample window."""
    if not samples:
        return None
    s = sorted(samples)
    i = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
    return round(s[i], 3)


class ServiceError(Exception):
    """Handler failure with a stable wire ``error`` type (docs/serving.md)."""

    def __init__(self, error: str, message: str, **extra):
        self.error = error
        self.extra = extra
        super().__init__(message)


def _norm_tags(raw) -> "tuple[str, ...]":
    """Normalize a request's ``tags_required`` (string or list) into the
    tuple of two-char tag names ``_apply_filter`` takes. Raises
    ``ValueError`` on malformed names so callers map it to a
    ProtocolError before any work happens."""
    if not raw:
        return ()
    if isinstance(raw, str):
        raw = [t for t in raw.replace(";", ",").split(",") if t]
    tags = tuple(str(t).strip() for t in raw)
    for t in tags:
        if len(t) != 2:
            raise ValueError(f"tag names are exactly two chars: {t!r}")
    return tags


#: A segment is this many ticks' rows (``batch_rows`` rows of ``window -
#: halo`` owned bytes each, and the last row's halo). One: a request
#: inflates the segments its rows lie in, whole, so the shorter the
#: segment the less it inflates beyond its rows (a 2 MiB split of twelve
#: rows lies in two or three segments of eight, 15 to 23 MiB inflated, and
#: in one or two of sixteen, 15 to 30); two ticks read 5% slower on the
#: chip (PERF.md, PR 47). Whole ticks, so that a cold request has a
#: tick's rows to hand the batcher after each inflate.
SEGMENT_TICKS = 1

#: The segment index of a file inflated whole (``batch``, ``aggregate``).
_WHOLE = -1

#: Files kept open at most, least recently asked for first out with its
#: segments: a file's tables are 24 B a member (24 MB for a 60 GB file),
#: so a cohort of thousands does not grow the daemon without end.
FILES_OPEN = 1024

_tokens = itertools.count()


class _FileState:
    """What is kept of every open file, none of it payload: header,
    contig dictionary, the member walk's block table, lazy starts."""

    def __init__(self, path: str):
        self.path = str(path)
        self.token = next(_tokens)  # names this file's segments
        st = os.stat(self.path)
        self.stamp = (st.st_size, st.st_mtime_ns)
        header = read_header(self.path)
        self.header = header
        self.contigs = [
            (name, length)
            for _, (name, length) in sorted(header.contig_lengths.items())
        ]
        lens_list = header.contig_lengths.lengths_list()
        if len(lens_list) > MAX_CONTIGS:
            raise ServiceError(
                "Unsupported",
                f"{self.path}: {len(lens_list)} contigs exceeds the serve "
                f"step's fixed dictionary ({MAX_CONTIGS}); use the one-shot "
                "CLI path",
            )
        self.lengths = pad_contig_lengths(
            np.asarray(lens_list, dtype=np.int32), cmax=MAX_CONTIGS
        )
        self.nc = len(lens_list)
        self.header_end = header.uncompressed_size
        with open_channel(self.path) as ch, obs.span(
            "bgzf.read", kind="metadata_scan", path=self.path
        ):
            metas = scan_metadata(ch)
        self.block_starts, self.block_flat = metas_block_table(metas)
        self.block_csize = np.array(
            [m.compressed_size for m in metas], dtype=np.int64
        )
        #: flat size of the whole file
        self.size = sum(m.uncompressed_size for m in metas)
        self._starts: "np.ndarray | None" = None
        self._starts_lock = threading.Lock()

    def fresh(self) -> bool:
        try:
            st = os.stat(self.path)
        except OSError:
            return False
        return (st.st_size, st.st_mtime_ns) == self.stamp

    def starts(self, config: Config) -> np.ndarray:
        """Exact whole-file record starts (cache-aware; the escape /
        plan-exactness fallback). Computed once, kept warm."""
        with self._starts_lock:
            if self._starts is None:
                from spark_bam_tpu.load.tpu_load import record_starts

                self._starts = np.asarray(
                    record_starts(self.path, config).starts, dtype=np.int64
                )
            return self._starts

    def members(self, lo: int, hi: int) -> "tuple[int, int]":
        """The members ``[i, j)`` that hold flat ``[lo, hi)``."""
        i = int(np.searchsorted(self.block_flat, lo, side="right")) - 1
        j = int(np.searchsorted(self.block_flat, hi, side="left"))
        return max(i, 0), j

    def flat_at(self, i: int) -> int:
        """Flat offset of member ``i``'s first byte (the file's flat size
        past the last)."""
        return int(self.block_flat[i]) if i < len(self.block_flat) else self.size

    def inflate(self, i: int, j: int) -> np.ndarray:
        """Members ``[i, j)`` inflated: ``out[0]`` is flat ``flat_at(i)``."""
        usize = np.diff(self.block_flat[i:j], append=self.flat_at(j))
        metas = [
            Metadata(int(s), int(c), int(u)) for s, c, u in
            zip(self.block_starts[i:j], self.block_csize[i:j], usize)
        ]
        with open_channel(self.path) as ch:
            return inflate_blocks(ch, metas).data


class _Segment:
    """Inflated bytes of a run of one file's members: ``data[0]`` is the
    file's flat offset ``base``. ``pins`` counts the requests in flight
    that cut rows of it; ``ready`` is set when the one inflate is done."""

    def __init__(self, key: tuple, base: int, nbytes: int):
        self.key = key
        self.base = base
        self.nbytes = nbytes
        self.data: "np.ndarray | None" = None
        self.error: "BaseException | None" = None
        self.pins = 0
        self.ready = threading.Event()


class _WholeFile(_Segment):
    """The segment of the ops that need a file whole (``batch``,
    ``aggregate``). Its parsed planes and encoded frames live and die
    with it, as they did with the whole-file view."""

    #: distinct query shapes kept hot per file.
    _FRAME_CACHE_SLOTS = 8

    def __init__(self, key: tuple, base: int, nbytes: int):
        super().__init__(key, base, nbytes)
        self._read_batch = None
        self._read_batch_lock = threading.Lock()
        # Encoded-frame cache: query shape → (frames tuple, rows). Valid
        # by the SAME determinism invariant the resume token rests on —
        # an unchanged file + query always encodes the same frame list
        # (a changed file drops its segments: ``file_state``).
        self._frame_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._frame_cache_lock = threading.Lock()

    def frame_cache_get(self, key: tuple):
        with self._frame_cache_lock:
            hit = self._frame_cache.get(key)
            if hit is not None:
                self._frame_cache.move_to_end(key)
            return hit

    def frame_cache_put(self, key: tuple, chunks: tuple, rows: int) -> None:
        with self._frame_cache_lock:
            self._frame_cache[key] = (chunks, rows)
            self._frame_cache.move_to_end(key)
            while len(self._frame_cache) > self._FRAME_CACHE_SLOTS:
                self._frame_cache.popitem(last=False)

    def read_batch(self, starts: np.ndarray):
        """Warm parsed ``ReadBatch`` over the flat bytes (the ``batch``
        op's third resident tier: repeat region queries re-filter the
        cached planes — zero re-parse, zero split resolutions)."""
        with self._read_batch_lock:
            if self._read_batch is None:
                from spark_bam_tpu.tpu.parser import parse_flat_records

                with obs.span("serve.parse", records=len(starts)):
                    self._read_batch = parse_flat_records(self.data, starts)
            return self._read_batch


class _Segments:
    """The inflated tier: every resident segment of every file, least
    recently used first out, ``budget`` bytes in all.

    A missing segment is entered (and its bytes counted) before it is
    inflated, so whoever else needs it waits for that one inflate. A
    segment that a request in flight holds is not evicted: ``resident``
    passes the budget by no more than what those requests pin, and comes
    back under it when they let go. The most recently used segment stays
    whatever its size, so a file larger than the budget still answers
    ``batch`` warm."""

    def __init__(self, budget: int):
        self.budget = int(budget)
        self.resident = 0
        self.peak = 0
        self._lru: "OrderedDict[tuple, _Segment]" = OrderedDict()
        self._lock = threading.Lock()
        self._held = threading.local()

    def __len__(self) -> int:
        return len(self._lru)

    def lease(self) -> None:
        """From here to ``release`` this thread's segments are pinned."""
        self._held.segments = []

    def release(self) -> None:
        held = getattr(self._held, "segments", None)
        self._held.segments = None
        if held:
            with self._lock:
                for seg in held:
                    seg.pins -= 1
                self.resident -= self._evict()

    def get(self, fs: _FileState, index: int, lo: int, hi: int,
            kind=_Segment) -> _Segment:
        """Segment ``index`` of ``fs``, which holds flat ``[lo, hi)``:
        resident, or inflated here, or waited for."""
        held = getattr(self._held, "segments", None)
        key = (fs.token, index)
        with self._lock:
            seg = self._lru.get(key)
            mine = seg is None
            if mine:
                i, j = fs.members(lo, hi)
                base = fs.flat_at(i)
                seg = self._lru[key] = kind(key, base, fs.flat_at(j) - base)
                self.resident += seg.nbytes
            self._lru.move_to_end(key)
            if held is not None:
                seg.pins += 1
                held.append(seg)
            if mine:
                self.resident -= self._evict()
                self.peak = max(self.peak, self.resident)
                resident = self.resident
        if mine:
            obs.count("serve.segment_misses")
            obs.observe("serve.flat_resident_mib", resident / 2**20)
            try:
                with obs.span("serve.segment_inflate", bytes=seg.nbytes,
                              members=j - i):
                    seg.data = fs.inflate(i, j)
            except BaseException as exc:
                seg.error = exc
                self._drop([key])
                raise
            finally:
                seg.ready.set()
        elif seg.ready.is_set():
            obs.count("serve.segment_hits")
        else:
            obs.count("serve.segment_waits")
            seg.ready.wait()
        if seg.error is not None:
            raise seg.error
        return seg

    def drop_file(self, fs: _FileState) -> None:
        """A file that changed: none of its segments answers again."""
        self._drop([k for k in list(self._lru) if k[0] == fs.token])

    def _drop(self, keys: list) -> None:
        with self._lock:
            for key in keys:
                seg = self._lru.pop(key, None)
                if seg is not None:
                    self.resident -= seg.nbytes

    def _evict(self) -> int:
        """Under the lock: least recently used first, but for the pinned
        and the newest, until the budget holds. The bytes that went."""
        over, freed = self.resident - self.budget, 0
        if over > 0:
            for key in list(self._lru)[:-1]:
                seg = self._lru[key]
                if seg.pins:
                    continue
                del self._lru[key]
                freed += seg.nbytes
                obs.count("serve.segment_evictions")
                if freed >= over:
                    break
        return freed


class SplitService:
    """Handlers + warm tiers; see module docstring. Thread-safe."""

    def __init__(self, config: Config = Config(), mesh=None):
        self.config = config
        self.serve_cfg: ServeConfig = config.serve_config
        self.policy = config.fault_policy
        # Zero-copy transport knobs the ACCEPT LOOP reads when answering
        # ``hello`` (serve/server.py) — the service only carries them.
        self.shm_enabled = bool(self.serve_cfg.shm)
        self.shm_bytes = int(self.serve_cfg.shm_bytes)
        self.shm_wait_ms = float(self.serve_cfg.shm_wait_ms)
        self.shm_chaos = self._build_shm_chaos(config)
        self.mesh = mesh if mesh is not None else make_mesh()
        self.steps = mesh_steps(self.mesh)
        self.batcher = Batcher(
            self.steps,
            width=self.serve_cfg.window + PAD,
            batch_rows=self.serve_cfg.batch_rows,
            tick_ms=self.serve_cfg.tick_ms,
            reads_to_check=config.reads_to_check,
            funnel=config.funnel_enabled(),
        )
        self.gate = AdmissionGate({
            "plan": self.serve_cfg.plan_queue,
            "scan": self.serve_cfg.scan_queue,
            # Durable-job control ops: cheap table lookups + thread
            # spawns; real capacity gating lives in the JobManager.
            "control": 8,
        })
        from spark_bam_tpu.jobs.manager import JobManager

        self.jobs = JobManager(config=config, alert_fn=self._job_alert)
        self.pool = ThreadPoolExecutor(
            max_workers=self.serve_cfg.workers, thread_name_prefix="serve-worker"
        )
        # Split resolution fans out beneath a plan handler; a separate pool
        # keeps that nesting from deadlocking the request workers.
        self.resolve_pool = ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="serve-resolve"
        )
        self.latency = LatencyTracker()
        self._files: "OrderedDict[str, _FileState]" = OrderedDict()
        self._files_lock = threading.Lock()
        self._open_lock = threading.Lock()  # one open (member walk) at a time
        self.segments = _Segments(self.serve_cfg.flat_cache)
        #: rows of ``window - halo`` a segment holds (and the last one's halo)
        self.segment_rows = SEGMENT_TICKS * self.batcher.batch_rows
        self.served = 0
        # op → [requests, rows, bytes, ms] — the per-op throughput ledger
        # ``stats`` reports (docs/serving.md "Observability").
        self._op_stats: "dict[str, list]" = {}
        # op → recent latencies (ms) behind the stats p50/p99.
        self._op_lat: "dict[str, deque]" = {}
        self._op_lock = threading.Lock()
        self._closed = False
        self.draining = False
        # Observability stage 2 (docs/observability.md): cost accounting
        # always runs (pure Python, no registry needed); the ring scraper,
        # SLO engine and tail sampler start when obs is configured.
        self.accountant = obs_account.Accountant()
        self.rings: "RingStore | None" = None
        self.slo_engine: "SloEngine | None" = None
        self.sampler: "TailSampler | None" = None
        self.start_observability()

    @staticmethod
    def _build_shm_chaos(config: Config):
        """Seeded shm-seam fault source (fabric/chaos.py) when the fabric
        ``chaos=`` spec carries any ``shm_*`` rate — the serve accept
        loop rolls it per frame record. Lazy import so an unconfigured
        service never pulls the fabric stack."""
        arg = config.fabric_config.chaos
        if not arg:
            return None
        from spark_bam_tpu.fabric.chaos import FabricChaos, parse_fabric_chaos

        seed, spec = parse_fabric_chaos(arg)
        if not (spec.shm_crc or spec.shm_trunc or spec.shm_unlink):
            return None
        return FabricChaos(seed, spec)

    def start_observability(self) -> bool:
        """Idempotently start the time-series ring scraper, SLO engine
        and tail sampler. Needs a configured registry — called at init
        and again by harnesses that ``obs.configure()`` after building
        the service (the bench A/B legs). Returns whether the stack is
        live."""
        if self.rings is not None:
            return True
        reg = obs.registry()
        if reg is None:
            return False
        scfg = self.config.slo_config
        rings = RingStore(reg, cadence_ms=scfg.every_ms)
        engine = SloEngine(scfg, lambda: self.rings) if scfg.enabled else None
        # Tail sampling only when an ``--slo`` spec opted in (even a
        # knob-only ``"sample=0.5"`` counts): a bare ``--metrics-out``
        # run must keep every trace, not a default 10% of them.
        sampler = None
        if self.config.slo:
            sampler = TailSampler(
                fraction=scfg.sample, seed=scfg.seed,
                slow_ms=scfg.sampler_slow_ms(),
                alerting=(
                    (lambda: self.slo_engine and self.slo_engine.alerting)
                    if engine is not None else None
                ),
            )
        with self._files_lock:
            self.rings = rings
            self.slo_engine = engine
            self.sampler = sampler
        rings.start(
            on_scrape=engine.evaluate if engine is not None else None
        )
        return True

    def stop_observability(self) -> None:
        """Tear the ring/engine/sampler stack down so a later
        :meth:`start_observability` rebinds to the CURRENT registry —
        the bench telemetry A/B flips obs off and on around a live
        service, and a stale RingStore would keep scraping the dead
        registry from before the flip."""
        with self._files_lock:
            rings, self.rings = self.rings, None
            self.slo_engine = None
            self.sampler = None
        if rings is not None:
            rings.stop()

    def _job_alert(self, name: str, **fields) -> None:
        """A paused job pages where burn-rate alerts land: the SLO
        ledger when the engine is live, the flight recorder always."""
        engine = self.slo_engine
        if engine is not None:
            engine.note_event(name, **fields)
        else:
            flight.record("slo_alert", objective=name, state="firing",
                          **fields)

    # ------------------------------------------------------------ lifecycle
    def close(self) -> None:
        self._closed = True
        self.jobs.close(timeout=1.0)
        if self.rings is not None:
            self.rings.stop()
        self.batcher.close()
        self.pool.shutdown(wait=False, cancel_futures=True)
        self.resolve_pool.shutdown(wait=False, cancel_futures=True)

    # ------------------------------------------------------------ admission
    def retry_after_ms(self) -> float:
        med = self.latency.median()
        return med if med is not None else _RETRY_AFTER_DEFAULT_MS

    def submit(self, req: dict, conn=None) -> "Future[dict]":
        """Admit ``req`` and return a future resolving to the full response
        dict. Raises :class:`Overloaded` synchronously when the request
        class is at its inflight limit; every other failure becomes a typed
        error *response* on the future. ``conn`` is the accept loop's
        per-connection transport state — unused here (the loop itself
        answers ``hello`` and encodes frame records), accepted so the
        loop can pass it to any service uniformly."""
        fut: "Future[dict]" = Future()
        op = req.get("op")
        if op == "ping":
            fut.set_result(ok_response(req, pong=True,
                                       devices=int(self.mesh.devices.size)))
            return fut
        if op == "stats":
            fut.set_result(ok_response(req, **self.stats()))
            return fut
        if op == "drain":
            fut.set_result(ok_response(req, **self.drain()))
            return fut
        if op == "tune":
            try:
                fut.set_result(ok_response(req, **self.tune(req)))
            except (KeyError, TypeError, ValueError) as exc:
                fut.set_result(error_response(req, "ProtocolError", str(exc)))
            return fut
        if op == "telemetry":
            fut.set_result(ok_response(req, **self.telemetry(req)))
            return fut
        if op == "alerts":
            fut.set_result(ok_response(req, **self.alerts()))
            return fut
        klass = CLASS_OF[op]
        if self._closed:
            raise RuntimeError("service is closed")
        if self.draining:
            # Graceful drain: in-flight work finishes unshed, new work is
            # refused with a typed error the fabric router reroutes on.
            fut.set_result(error_response(
                req, "Draining", "service is draining; route elsewhere",
            ))
            return fut
        self.gate.admit(klass, self.retry_after_ms())  # may raise Overloaded
        obs.count("serve.requests")
        deadline_ms = req.get("deadline_ms")
        if deadline_ms is not None:
            deadline_ts = time.monotonic() + float(deadline_ms) / 1000.0
        elif self.policy.deadline is not None:
            deadline_ts = time.monotonic() + self.policy.deadline
        else:
            deadline_ts = None
        t0 = time.monotonic()
        self.pool.submit(self._run, op, req, fut, klass, deadline_ts, t0)
        return fut

    def _run(self, op, req, fut, klass, deadline_ts, t0) -> None:
        obs.observe("serve.worker_wait_ms", (time.monotonic() - t0) * 1000.0)
        handler = getattr(self, f"_handle_{op}")
        # Rebind the caller's trace context (if the request carried one)
        # around the request span, so every span this handler opens —
        # including the batcher rows it fans out — joins the same
        # cross-process trace (docs/observability.md).
        ctx = obs_trace.from_carrier(req.get("trace"))
        token = obs_trace.set_current(ctx) if ctx is not None else None
        flight.record("request", op=op, id=req.get("id"),
                      trace=ctx.trace_id if ctx else None)
        # The cost accumulator travels by contextvar exactly like the
        # trace: RowTask captures it at creation, the batcher attributes
        # per-row queue/device/h2d costs at dispatch (obs/account.py).
        cost = self.accountant.begin(op, req.get("tenant"))
        cost_token = obs_account.bind(cost)
        # What the handler cuts rows of stays resident until it is done.
        self.segments.lease()
        try:
            with obs.span("serve.request", op=op):
                if deadline_ts is not None and time.monotonic() > deadline_ts:
                    obs.count("serve.shed")
                    raise ServiceError(
                        "DeadlineExceeded",
                        f"{op} deadline expired before service started",
                    )
                resp = ok_response(req, **handler(req, deadline_ts))
        except ServiceError as exc:
            resp = error_response(req, exc.error, str(exc), **exc.extra)
        except TimeoutError as exc:
            obs.count("serve.shed")
            resp = error_response(req, "DeadlineExceeded", str(exc))
        except ResourceExhausted as exc:
            # Retryable environment exhaustion (disk/memory), typed so
            # clients and the fabric router can pace a retry instead of
            # treating it as an Internal failure.
            resp = error_response(
                req, "ResourceExhausted", str(exc),
                retry_after_ms=round(getattr(
                    exc, "retry_after_ms", self.retry_after_ms()
                ), 3),
            )
        except FileNotFoundError as exc:
            resp = error_response(req, "NotFound", str(exc))
        except Exception as exc:
            resp = error_response(
                req, "Internal", f"{type(exc).__name__}: {exc}"
            )
        finally:
            self.segments.release()
            self.gate.release(klass)
            obs_account.reset(cost_token)
            if token is not None:
                obs_trace.reset(token)
        ms = (time.monotonic() - t0) * 1000.0
        ok = bool(resp.get("ok"))
        self.latency.record(ms)
        obs.observe("serve.latency_ms", ms)
        nbytes = self._note_op(op, ms, resp)
        self.accountant.finish(cost, ms, nbytes, ok=ok)
        if not ok:
            obs.count("serve.errors")
            flight.record("error", op=op, id=req.get("id"),
                          error=resp.get("error"),
                          message=resp.get("message"))
        if self.sampler is not None:
            # Tail decision at completion: prune dropped traces, pin
            # slow/errored exemplars on the latency histogram.
            self.sampler.note(ctx.trace_id if ctx else None, ms,
                              error=not ok)
        # Under the op lock: ``+=`` from concurrent pool threads loses
        # updates, and ``served`` feeds the autoscaler's served-changed
        # hysteresis — a stuck count reads as "no fresh samples" and
        # holds tuning moves forever.
        with self._op_lock:
            self.served += 1
        fut.set_result(resp)

    def _note_op(self, op: str, ms: float, resp: dict) -> int:
        """Per-op request/row/byte accounting. Rows come from whichever
        cardinality the op reports (``rows``/``count``/``total``); bytes
        are the encoded JSON line plus any binary frames (returned, so
        the cost accountant bills the same number)."""
        rows = 0
        if resp.get("ok"):
            for key in ("rows", "count", "total"):
                if isinstance(resp.get(key), int):
                    rows = resp[key]
                    break
        chunks = resp.get("_binary") or ()
        nbytes = sum(len(c) for c in chunks)
        nbytes += len(encode(
            {k: v for k, v in resp.items() if k != "_binary"}
        ))
        with self._op_lock:
            acc = self._op_stats.setdefault(op, [0, 0, 0, 0.0])
            acc[0] += 1
            acc[1] += rows
            acc[2] += nbytes
            acc[3] += ms
            lat = self._op_lat.get(op)
            if lat is None:
                lat = self._op_lat[op] = deque(maxlen=_LATENCY_WINDOW)
            lat.append(ms)
        return nbytes

    # -------------------------------------------------------------- admin ops
    def drain(self) -> dict:
        """Stop admitting work ops; in-flight requests and queued batcher
        ticks complete unshed. ping/stats/tune keep answering so the
        control plane can watch inflight drop to zero before detaching."""
        self.draining = True
        return {"draining": True, "inflight": self.gate.inflight()}

    def tune(self, req: dict) -> dict:
        """Runtime retargeting of the batching/admission knobs — the
        fabric autoscaler's actuator (bounded by ITS floors/ceilings;
        the service applies whatever it is told). Returns the applied
        values (batch_rows after mesh rounding)."""
        applied: dict = {}
        if req.get("batch_rows") is not None:
            applied["batch_rows"] = self.batcher.set_batch_rows(
                int(req["batch_rows"])
            )
        if req.get("tick_ms") is not None:
            applied["tick_ms"] = self.batcher.set_tick_ms(
                float(req["tick_ms"])
            )
        for key, klass in (("plan_queue", "plan"), ("scan_queue", "scan")):
            if req.get(key) is not None:
                applied[key] = self.gate.set_limit(klass, int(req[key]))
        if not applied:
            raise ValueError(
                "tune needs at least one of batch_rows/tick_ms/"
                "plan_queue/scan_queue"
            )
        obs.count("serve.tuned")
        return {"applied": applied, **self._knobs()}

    def alerts(self) -> dict:
        """The SLO engine's full status — per-objective burn rates, the
        firing set, and the bounded alert ledger. ``{"enabled": False}``
        when no objectives are configured (``--slo``/``SPARK_BAM_SLO``)."""
        if self.slo_engine is None:
            return {"slo": {"enabled": False, "objectives": [],
                            "firing": [], "ledger": []}}
        return {"slo": self.slo_engine.status()}

    def telemetry(self, req: "dict | None" = None) -> dict:
        """One scrape's worth of worker observability: the live obs
        snapshot (None when metrics are disabled), a tail of recent span
        events, the time-series ring snapshot, the SLO status, the
        accounting rollups, the flight-recorder ring, and the same stats
        dict the ``stats`` op serves — everything the router's fleet
        collector and the ``top`` CLI need in a single round-trip."""
        req = req or {}
        max_spans = int(req.get("max_spans") or 256)
        reg = obs.registry()
        spans: list = []
        snap = None
        if reg is not None:
            snap = reg.snapshot()
            spans = reg.events()[-max_spans:]
        return {
            "pid": os.getpid(),
            "telemetry_enabled": reg is not None,
            "snapshot": snap,
            "spans": spans,
            "series": self.rings.snapshot() if self.rings else None,
            "slo": (self.slo_engine.status()
                    if self.slo_engine is not None else None),
            "accounting": self.accountant.snapshot(),
            "flight": flight.recorder().events(),
            "stats": self.stats(),
        }

    def _knobs(self) -> dict:
        return {
            "batch_rows": int(self.batcher.batch_rows),
            "tick_ms": round(self.batcher.tick_s * 1000.0, 3),
            "limits": dict(self.gate.limits),
        }

    # ------------------------------------------------------------ warm tier
    def file_state(self, path) -> _FileState:
        """The open file: built once whoever asks meanwhile, and again,
        its segments dropped, when its stamp has changed."""
        path = str(path)
        with self._files_lock:
            fs = self._files.get(path)
            if fs is not None:
                self._files.move_to_end(path)
        if fs is not None and fs.fresh():
            return fs
        with self._open_lock:
            with self._files_lock:
                fs = self._files.get(path)
            if fs is not None:
                if fs.fresh():
                    return fs
                # Changed or gone: nothing of it answers again, and a path
                # that no longer opens leaves no entry behind.
                self.segments.drop_file(fs)
                with self._files_lock:
                    del self._files[path]
            with obs.span("serve.file_open", path=path):
                fs = _FileState(path)
            with self._files_lock:
                self._files[path] = fs
                closed = [self._files.popitem(last=False)[1]
                          for _ in range(len(self._files) - FILES_OPEN)]
            for old in closed:
                self.segments.drop_file(old)
        return fs

    def _whole_file(self, fs: _FileState) -> _WholeFile:
        return self.segments.get(fs, _WHOLE, 0, fs.size, kind=_WholeFile)

    # ------------------------------------------------------------- handlers
    def _handle_plan(self, req: dict, deadline_ts) -> dict:
        from spark_bam_tpu.load.api import split_starts

        path = req["path"]
        size = req.get("split_size")
        splits = split_starts(
            path, split_size=size, config=self.config, pool=self.resolve_pool
        )
        return {
            "path": str(path),
            "splits": [
                {
                    "start": s.start,
                    "end": s.end,
                    "pos": None if p is None else [p.block_pos, p.offset],
                    "vpos": None if p is None else p.to_htsjdk(),
                }
                for s, p in splits
            ],
        }

    def _handle_record_starts(self, req: dict, deadline_ts) -> dict:
        fs = self.file_state(req["path"])
        starts = fs.starts(self.config)
        limit = int(req.get("limit", 0))
        head = starts[:limit] if limit else starts[:0]
        blocks, offs = pos_of_flat_tables(fs.block_starts, fs.block_flat, head)
        return {
            "path": fs.path,
            "count": int(len(starts)),
            "vpos": [
                (int(b) << 16) | int(o) for b, o in zip(blocks, offs)
            ],
        }

    def _handle_count(self, req: dict, deadline_ts) -> dict:
        fs = self.file_state(req["path"])
        lo, hi = self._flat_range(fs, req)
        tasks = self._scan_rows(fs, lo, hi, deadline_ts)
        count, escaped = self._gather(tasks, deadline_ts)
        exact_fallback = False
        if escaped:
            count = self._exact_count(fs, lo, hi)
            exact_fallback = True
        return {
            "path": fs.path,
            "count": int(count),
            "escaped": int(escaped),
            "exact_fallback": exact_fallback,
        }

    def _handle_fleet(self, req: dict, deadline_ts) -> dict:
        paths = req["paths"]
        if not isinstance(paths, list) or not paths:
            raise ServiceError("ProtocolError", "fleet needs a non-empty 'paths' list")
        # Submit every file's rows before waiting on any: rows from the
        # whole fleet coalesce into shared batcher ticks.
        per_path = []
        for p in paths:
            fs = self.file_state(p)
            lo, hi = fs.header_end, fs.size
            per_path.append((fs, lo, hi, self._scan_rows(fs, lo, hi, deadline_ts)))
        counts = {}
        total = 0
        for fs, lo, hi, tasks in per_path:
            count, escaped = self._gather(tasks, deadline_ts)
            if escaped:
                count = self._exact_count(fs, lo, hi)
            counts[fs.path] = int(count)
            total += int(count)
        return {"paths": counts, "total": total}

    def _handle_rewrite(self, req: dict, deadline_ts) -> dict:
        """Re-block + re-compress ``path`` into ``out`` through the write
        path (cli/rewrite.py): the device compressor when the service
        config (or the request's ``deflate`` spec) enables it, sidecars
        emitted during the write when ``index`` is set. Scan-class: the
        compressor competes with count/fleet for the device, so it shares
        their inflight cap."""
        from spark_bam_tpu.cli.rewrite import rewrite_bam
        from spark_bam_tpu.compress.config import DeflateConfig

        path = req["path"]
        out = req.get("out")
        if not out:
            raise ServiceError("ProtocolError", "rewrite needs an 'out' path")
        deflate = req.get("deflate")
        if deflate is not None:
            try:
                DeflateConfig.parse(deflate)
            except ValueError as exc:
                raise ServiceError("ProtocolError", str(exc)) from exc
        # ``resume_from`` (the streaming-failover token) is accepted and
        # ignored here: rewrite emits no frames — its idempotency is the
        # atomic output commit, so a failover simply re-runs the rewrite
        # and overwrites, never interleaves.
        try:
            block_payload = int(req.get("block_payload") or 0xFF00)
            level = int(req.get("level") or 6)
        except (TypeError, ValueError) as exc:
            raise ServiceError("ProtocolError", str(exc)) from exc
        with obs.span("serve.rewrite", path=str(path)):
            res = rewrite_bam(
                path, out,
                block_payload=block_payload, level=level, deflate=deflate,
                index=bool(req.get("index")), config=self.config,
            )
        return {
            "path": str(path),
            "out": str(out),
            "count": res.count,
            "n_blocks": res.n_blocks,
            "bytes_out": res.bytes_out,
            "sidecars": dict(res.sidecars),
        }

    # ----------------------------------------------------------- job plane
    #: request fields forwarded into a job spec, per job op.
    _JOB_FIELDS = ("path", "out", "block_payload", "level", "deflate",
                   "index", "columns", "batch_rows")

    def _handle_submit(self, req: dict, deadline_ts) -> dict:
        """Admit a durable job (jobs/manager.py). ``job`` selects the
        runner (rewrite/export/transcode); the spec fields mirror the
        one-shot ops. Deterministic job ids make retries idempotent —
        resubmitting a spec whose journal survives RESUMES it."""
        from spark_bam_tpu.jobs.runner import RUNNERS

        job = req.get("job")
        if job not in RUNNERS:
            raise ServiceError(
                "ProtocolError",
                f"submit needs job ∈ {{{', '.join(sorted(RUNNERS))}}}, "
                f"got {job!r}",
            )
        spec = {"op": job}
        spec.update(
            (k, req[k]) for k in self._JOB_FIELDS
            if req.get(k) is not None
        )
        try:
            status = self.jobs.submit(spec)
        except ValueError as exc:
            raise ServiceError("ProtocolError", str(exc)) from exc
        return status

    def _job_or_404(self, req: dict) -> str:
        jid = req.get("job_id")
        if not jid:
            raise ServiceError("ProtocolError", "missing 'job_id'")
        return str(jid)

    def _handle_job_status(self, req: dict, deadline_ts) -> dict:
        status = self.jobs.status(self._job_or_404(req))
        if status is None:
            raise ServiceError(
                "NotFound", f"no job {req.get('job_id')!r} on this worker"
            )
        return status

    def _handle_job_cancel(self, req: dict, deadline_ts) -> dict:
        status = self.jobs.cancel(self._job_or_404(req))
        if status is None:
            raise ServiceError(
                "NotFound", f"no job {req.get('job_id')!r} on this worker"
            )
        return status

    def _handle_batch(self, req: dict, deadline_ts) -> dict:
        """Columnar record batches for a (possibly interval/flag-filtered)
        file, staged as native-container frames (columnar/native.py) for
        the server to stream length-prefixed. Reuses the warm flat view
        and parsed planes, so a repeat region query does zero split
        resolutions and zero re-parses; the frame stream is byte-identical
        to ``load.api.export(fmt="native")`` for the same query
        (docs/analytics.md)."""
        from spark_bam_tpu.columnar.from_parser import (
            read_batch_to_record_batches,
        )
        from spark_bam_tpu.columnar.native import (
            batch_frame,
            container_head,
            container_meta,
            end_frame,
        )
        from spark_bam_tpu.columnar.schema import normalize_columns
        from spark_bam_tpu.load.tpu_load import _apply_filter
        from spark_bam_tpu.tpu.parser import ReadBatch

        fs = self.file_state(req["path"])
        ccfg = self.config.columnar_config
        try:
            columns = normalize_columns(req.get("columns") or ccfg.columns)
        except ValueError as exc:
            raise ServiceError("ProtocolError", str(exc)) from exc
        batch_rows = int(req.get("batch_rows") or ccfg.batch_rows)
        if batch_rows <= 0:
            raise ServiceError("ProtocolError", "batch_rows must be positive")
        wire = str(req.get("wire") or "sbcr")
        if wire not in ("sbcr", "arrow"):
            raise ServiceError(
                "ProtocolError",
                f"wire must be 'sbcr' or 'arrow', got {wire!r}",
            )
        if wire == "arrow":
            from spark_bam_tpu.columnar.arrow_ipc import arrow_available

            if not arrow_available():
                raise ServiceError(
                    "Unsupported",
                    "wire=arrow needs pyarrow (the [arrow] extra); "
                    "the default sbcr wire has no dependencies",
                )
        loci = req.get("intervals") or None
        flags_required = int(req.get("flags_required") or 0)
        flags_forbidden = int(req.get("flags_forbidden") or 0)
        tags_required = _norm_tags(req.get("tags_required"))
        # Encoded frames are a pure function of (file, query) — the same
        # determinism invariant resume rests on — so repeat queries skip
        # filter + encode entirely and the transport is the only cost.
        cache_key = (wire, columns, batch_rows, repr(loci), flags_required,
                     flags_forbidden, tags_required, ccfg.codec, ccfg.level)
        whole = self._whole_file(fs)
        cached = whole.frame_cache_get(cache_key)
        if cached is not None:
            obs.count("serve.frame_cache_hits")
            chunks, rows = list(cached[0]), cached[1]
        else:
            obs.count("serve.frame_cache_misses")
            warm = whole.read_batch(fs.starts(self.config))
            if deadline_ts is not None and time.monotonic() > deadline_ts:
                obs.count("serve.shed")
                raise ServiceError(
                    "DeadlineExceeded", "batch deadline expired during parse"
                )
            # _apply_filter narrows ``valid`` in place: work on a copy so
            # the warm tier keeps the unfiltered mask for the next request.
            batch = ReadBatch(dict(warm.columns), warm.starts, buf=warm.buf)
            batch.columns["valid"] = np.array(
                warm.columns["valid"], copy=True
            )
            if loci or flags_required or flags_forbidden or tags_required:
                _apply_filter(
                    batch, fs.header, loci, flags_required, flags_forbidden,
                    tags_required=tags_required,
                )
            if wire == "arrow":
                from spark_bam_tpu.columnar.arrow_ipc import stream_frames

                with obs.span("serve.batch_encode", path=fs.path):
                    chunks, rows = stream_frames(batch, batch_rows, columns)
            else:
                meta = container_meta(
                    columns, codec=ccfg.codec, level=ccfg.level,
                    contigs=fs.contigs,
                )
                chunks = [container_head(meta)]
                rows = 0
                with obs.span("serve.batch_encode", path=fs.path):
                    for rb in read_batch_to_record_batches(
                        batch, batch_rows, columns
                    ):
                        chunks.append(batch_frame(rb, meta))
                        rows += rb.num_rows
                chunks.append(end_frame(rows, len(chunks) - 1))
            whole.frame_cache_put(cache_key, tuple(chunks), rows)
        total_frames = len(chunks)
        # Frame-sequence resume token (docs/robustness.md): the chunk
        # list is deterministic for an unchanged file + query, so a
        # replacement worker re-encodes and serves only the tail — the
        # delivered sequence is byte-identical to an undisturbed run.
        resume_from = int(req.get("resume_from") or 0)
        out = {}
        if resume_from:
            if not 0 <= resume_from < total_frames:
                raise ServiceError(
                    "ProtocolError",
                    f"resume_from={resume_from} out of range "
                    f"(0..{total_frames - 1})",
                )
            chunks = chunks[resume_from:]
            out["resume_from"] = resume_from
            out["total_frames"] = total_frames
        nbytes = sum(len(c) for c in chunks)
        obs.count("columnar.rows", rows)
        obs.count("columnar.bytes_out", nbytes)
        if wire == "arrow":
            # Only the non-default wire is echoed: sbcr responses stay
            # byte-identical to every earlier release.
            out["wire"] = wire
        out.update({
            "path": fs.path,
            "rows": int(rows),
            "columns": list(columns),
            "batch_rows": int(batch_rows),
            "binary_frames": len(chunks),
            "binary_bytes": int(nbytes),
            "_binary": chunks,
        })
        return out

    def _handle_aggregate(self, req: dict, deadline_ts) -> dict:
        """Fused on-device aggregation over the warm parsed planes
        (agg/kernels.py): the same predicate pushdown as ``batch``
        (intervals / flag masks / tag presence) narrows ``valid``, then
        the whole plan reduces inside the compiled mesh tick and only
        the int64 result vectors come back — kilobytes instead of a
        record stream, byte-equal to the host oracle
        (docs/analytics.md "Aggregation"). Scan-class: the reduction
        holds the device like count/batch do."""
        from spark_bam_tpu.agg.host import host_aggregate
        from spark_bam_tpu.agg.kernels import aggregate_planes
        from spark_bam_tpu.agg.plan import AggConfig, encode_result
        from spark_bam_tpu.load.tpu_load import _apply_filter
        from spark_bam_tpu.tpu.parser import ReadBatch

        fs = self.file_state(req["path"])
        try:
            plan = AggConfig.parse(req.get("agg") or self.config.agg)
            tags_required = _norm_tags(req.get("tags_required"))
            chunk = req.get("chunk")
            if chunk is not None:
                chunk = int(chunk)
                if chunk < 1:
                    raise ValueError(f"agg chunk must be >= 1: {chunk}")
        except (TypeError, ValueError) as exc:
            raise ServiceError("ProtocolError", str(exc)) from exc
        loci = req.get("intervals") or None
        flags_required = int(req.get("flags_required") or 0)
        flags_forbidden = int(req.get("flags_forbidden") or 0)
        warm = self._whole_file(fs).read_batch(fs.starts(self.config))
        if deadline_ts is not None and time.monotonic() > deadline_ts:
            obs.count("serve.shed")
            raise ServiceError(
                "DeadlineExceeded", "aggregate deadline expired during parse"
            )
        batch = ReadBatch(dict(warm.columns), warm.starts, buf=warm.buf)
        batch.columns["valid"] = np.array(warm.columns["valid"], copy=True)
        if loci or flags_required or flags_forbidden or tags_required:
            _apply_filter(
                batch, fs.header, loci, flags_required, flags_forbidden,
                tags_required=tags_required,
            )
        rows = int(np.count_nonzero(batch.columns["valid"]))
        with obs.span("agg.reduce", path=fs.path):
            try:
                vectors = aggregate_planes(
                    batch.columns, plan, fs.nc,
                    steps=self.steps, chunk=chunk,
                )
            except INPUT_ERRORS:
                # Planes the device reduction rejects as input: the numpy
                # oracle answers identically, just slower, counted so the
                # dashboard surfaces it. Compiler and device runtime
                # errors are not input errors and fail the request.
                obs.count("agg.host_fallbacks")
                vectors = host_aggregate(batch.columns, plan, fs.nc)
        with obs.span("agg.encode", path=fs.path):
            meta, payload = encode_result(plan, fs.nc, fs.contigs, vectors)
        chunks = [payload]
        total_frames = len(chunks)
        # Same frame-sequence resume token as ``batch``: a single
        # deterministic frame, so a failover either re-serves it or
        # serves nothing (the client already holds it).
        resume_from = int(req.get("resume_from") or 0)
        out = {}
        if resume_from:
            if not 0 <= resume_from < total_frames:
                raise ServiceError(
                    "ProtocolError",
                    f"resume_from={resume_from} out of range "
                    f"(0..{total_frames - 1})",
                )
            chunks = chunks[resume_from:]
            out["resume_from"] = resume_from
            out["total_frames"] = total_frames
        nbytes = sum(len(c) for c in chunks)
        obs.count("agg.requests")
        obs.count("agg.rows", rows)
        obs.count("agg.bytes_out", nbytes)
        out.update({
            "path": fs.path,
            "rows": rows,
            "agg": plan.canonical(),
            "result": meta,
            "binary_frames": len(chunks),
            "binary_bytes": int(nbytes),
            "_binary": chunks,
        })
        return out

    # ------------------------------------------------------------- scanning
    def _flat_range(self, fs: _FileState, req: dict) -> "tuple[int, int]":
        """Flat [lo, hi) for a request: whole file, or the blocks whose
        compressed starts land in the request's compressed [start, end)."""
        start, end = req.get("start"), req.get("end")
        bs = fs.block_starts
        lo = fs.header_end
        hi = fs.size
        if start is not None:
            i = int(np.searchsorted(bs, int(start), side="left"))
            lo = max(fs.header_end, fs.flat_at(i))
        if end is not None:
            hi = fs.flat_at(int(np.searchsorted(bs, int(end), side="left")))
        return lo, max(lo, hi)

    def _scan_rows(self, fs: _FileState, lo: int, hi: int,
                   deadline_ts) -> "list[RowTask]":
        """Cut [lo, hi) into batcher rows with ``batch_windows``'s exact
        tiling (same step/ownership arithmetic ⇒ byte-identical verdicts
        vs the one-shot path). Row ``k`` starts at ``k * step``; the row
        whose window reaches the file's end is the last and owns to it.
        Rows are views of their segment, ``segment_rows`` rows each."""
        window = self.serve_cfg.window
        halo = self.serve_cfg.halo
        step = max(window - halo, 1)
        n_total = fs.size
        tasks: "list[RowTask]" = []
        if lo >= hi:
            return tasks
        last = max(-(-(n_total - window) // step), 0)
        per = self.segment_rows
        j, seg = -1, None
        # From the row that owns ``lo`` on: every row has bytes of its own.
        for k in range(min(lo // step, last), last + 1):
            s = k * step
            if s >= hi:
                break
            e = min(s + window, n_total)
            own_end = e if k == last else s + step
            if k // per != j:
                j = k // per
                seg = self.segments.get(
                    fs, j, j * per * step,
                    min((j + 1) * per * step + halo, n_total))
            t = RowTask(
                window=seg.data[s - seg.base: e - seg.base],
                n=e - s,
                at_eof=(e == n_total),
                lo=max(lo, s) - s,
                own=min(hi, own_end) - s,
                lengths=fs.lengths,
                nc=fs.nc,
                deadline_ts=deadline_ts,
            )
            self.batcher.submit(t)
            tasks.append(t)
        return tasks

    def _gather(self, tasks: "list[RowTask]",
                deadline_ts) -> "tuple[int, int]":
        count = escaped = 0
        for t in tasks:
            left = None
            if deadline_ts is not None:
                left = max(deadline_ts - time.monotonic(), 0.001)
            try:
                c, esc = t.future.result(timeout=left)
            except FutureTimeout:
                # concurrent.futures.TimeoutError is NOT the builtin
                # TimeoutError before 3.11; normalize so the deadline
                # maps to DeadlineExceeded, not Internal.
                raise TimeoutError(
                    "deadline expired waiting for device verdict"
                ) from None
            count += c
            escaped += esc
        return count, escaped

    def _exact_count(self, fs: _FileState, lo: int, hi: int) -> int:
        starts = fs.starts(self.config)
        return int(np.searchsorted(starts, hi, side="left")
                   - np.searchsorted(starts, lo, side="left"))

    # ---------------------------------------------------------------- stats
    def stats(self) -> dict:
        with self._op_lock:
            ops = {
                op: {
                    "requests": int(n),
                    "rows": int(rows),
                    "bytes": int(nbytes),
                    "ms": round(ms, 3),
                    "rows_per_s": round(rows / (ms / 1000.0), 1) if ms else 0.0,
                    "bytes_per_s": round(nbytes / (ms / 1000.0), 1) if ms else 0.0,
                    "p50_ms": _percentile(self._op_lat.get(op), 0.50),
                    "p99_ms": _percentile(self._op_lat.get(op), 0.99),
                }
                for op, (n, rows, nbytes, ms) in sorted(self._op_stats.items())
            }
            all_lat = [v for d in self._op_lat.values() for v in d]
        inflight = self.gate.inflight()
        # The warm-tier proof read per-WORKER, so the fabric router's
        # spill-to-cold-worker behavior doesn't poison a global counter
        # (bench serve/fabric legs assert on this). None when obs is off.
        reg = obs.registry()
        resolutions = (
            int(reg.counter("load.split_resolutions").value)
            if reg is not None else None
        )
        return {
            "served": int(self.served),
            "inflight": inflight,
            "queue_depth": int(sum(inflight.values())),
            "backlog": int(self.batcher.backlog()),
            "draining": bool(self.draining),
            "files_resident": len(self._files),  # files open
            "segments_resident": len(self.segments),
            "flat_resident_bytes": int(self.segments.resident),
            "flat_resident_peak_bytes": int(self.segments.peak),
            "batch_sizes": {
                str(k): int(v)
                for k, v in sorted(self.batcher.batch_sizes.items())
            },
            "devices": int(self.mesh.devices.size),
            "latency_p50_ms": _percentile(all_lat, 0.50),
            "latency_p99_ms": _percentile(all_lat, 0.99),
            "split_resolutions": resolutions,
            "ops": ops,
            # Durable-job table: id → state (full detail via job_status).
            "jobs": {
                j["job_id"]: j["state"] for j in self.jobs.jobs()
            },
            "accounting": self.accountant.snapshot(),
            # The compact SLO block the fabric autoscaler steers on
            # (max_burn_fast + firing objective names); None without
            # configured objectives.
            "slo": (self.slo_engine.summary()
                    if self.slo_engine is not None else None),
            **self._knobs(),
        }
