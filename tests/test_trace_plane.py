"""One clock for host and chip: the names inside the jitted programs, obs
spans inside a profiler capture, a live registry that leaves the count
loop's schedule alone, and the batcher's spans around a tick."""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_bam_tpu import obs
from spark_bam_tpu.native.build import load_native
from spark_bam_tpu.obs.names import NAMES, PROGRAMS, SCOPES


@pytest.fixture
def registry():
    obs.shutdown()
    reg = obs.configure()
    try:
        yield reg
    finally:
        obs.shutdown()


# ------------------------------------------------------------ scope names

W, HALO = 128 << 10, 32 << 10


def _lower_count_window():
    from spark_bam_tpu.tpu.checker import PAD, count_window

    i32 = jnp.int32
    return count_window.lower(
        jnp.zeros(W + PAD, jnp.uint8), jnp.zeros(8, i32), i32(1), i32(1000),
        jnp.bool_(True), i32(0), i32(1000), window=W, funnel=True,
    )


def _lower_load_window():
    from spark_bam_tpu.tpu.checker import PAD, load_window
    from spark_bam_tpu.tpu.parser import RowFilter

    i32 = jnp.int32
    return load_window.lower(
        jnp.zeros(W + PAD, jnp.uint8), jnp.zeros(8, i32), i32(1), i32(1000),
        jnp.bool_(True), i32(0), i32(1000), RowFilter.of(None, 0, 1796),
        window=W,
    )


def _lower_count_step():
    from spark_bam_tpu.parallel.mesh import local_mesh, make_shard_map_count_step
    from spark_bam_tpu.tpu.checker import PAD

    mesh = local_mesh()
    b = mesh.devices.size
    i32 = jnp.int32
    return make_shard_map_count_step(mesh, funnel=True).lower(
        jnp.zeros(b * (W + PAD), jnp.uint8), jnp.zeros(b, i32),
        jnp.zeros(b, bool), jnp.zeros(b, i32), jnp.zeros(b, i32),
        jnp.zeros(8, i32), i32(1),
    )


def _lower_serve_step():
    from spark_bam_tpu.parallel.mesh import local_mesh, make_shard_map_serve_step
    from spark_bam_tpu.serve.config import MAX_CONTIGS
    from spark_bam_tpu.tpu.checker import PAD

    mesh = local_mesh()
    b = mesh.devices.size
    i32 = jnp.int32
    return make_shard_map_serve_step(mesh, funnel=True).lower(
        jnp.zeros((b, W + PAD), jnp.uint8), jnp.zeros(b, i32),
        jnp.zeros(b, bool), jnp.zeros(b, i32), jnp.zeros(b, i32),
        jnp.zeros((b, MAX_CONTIGS), i32), jnp.ones(b, i32),
    )


def _lower_agg_update():
    from spark_bam_tpu.agg.kernels import state_zeros, update_fn
    from spark_bam_tpu.agg.plan import AggConfig

    plan = AggConfig.parse("count")
    planes = {k: jnp.zeros(64, jnp.int32) for k in ("flag", "l_seq")}
    planes["valid"] = jnp.zeros(64, bool)
    return update_fn(plan, 1).lower(state_zeros(plan, 1), planes)


def _lower_confusion_step():
    from spark_bam_tpu.parallel.mesh import (
        local_mesh, make_shard_map_confusion_step,
    )
    from spark_bam_tpu.tpu.checker import PAD

    mesh = local_mesh()
    b = mesh.devices.size
    i32 = jnp.int32
    return make_shard_map_confusion_step(mesh, funnel=True).lower(
        jnp.zeros((b, W + PAD), jnp.uint8), jnp.zeros(b, i32),
        jnp.zeros(b, bool), jnp.zeros((b, W), bool), jnp.zeros(b, i32),
        jnp.zeros(b, i32), jnp.zeros(8, i32), i32(1),
    )


CHECK = {"check", "flags", "funnel", "chain_walk"}
PROGRAM_SCOPES = [
    ("count_window", _lower_count_window, CHECK | {"reduce"}),
    ("load_window", _lower_load_window,
     CHECK | {"reduce", "parse", "filter"}),
    ("count_step", _lower_count_step, CHECK | {"reduce"}),
    ("serve_step", _lower_serve_step, CHECK | {"reduce", "scatter"}),
    ("confusion_step", _lower_confusion_step,
     CHECK | {"reduce", "scatter", "collect"}),
    ("agg_update", _lower_agg_update, {"agg_reduce"}),
]


@pytest.mark.parametrize("program,lower,scopes", PROGRAM_SCOPES,
                         ids=[p[0] for p in PROGRAM_SCOPES])
def test_scopes_are_in_the_lowered_program(program, lower, scopes):
    """Every scope of the catalogue is a component of some operation's name
    path in the program that should hold it, and the program carries its
    catalogued name (``jit_<name>`` is what a device trace shows)."""
    assert program in PROGRAMS and scopes <= SCOPES
    text = lower().as_text(debug_info=True)
    assert f"@jit_{program}" in text
    for scope in sorted(scopes):
        # ``check/flags/...`` or, directly under a vmap, ``vmap(reduce)/...``.
        assert any(f"{before}{scope}{after}/" in text for before, after in
                   (("/", ""), ('"', ""), ("(", ")"))), scope
    if "scatter" in scopes:
        # ``check_window``'s verdicts back over every position, told from
        # the lane stage: a child of ``check``.
        assert "check/scatter/" in text
    if program == "count_window":
        # Stage 0 directly under ``check``; the lane stage's three scopes
        # inside its block loops (``bench/readers/trace_scope.py`` finds a
        # scope anywhere on the path).
        assert "check/flags/" in text and "check/funnel/" in text
        for scope in ("funnel", "flags", "chain_walk"):
            assert f"check/while/body/{scope}/" in text
    if program == "load_window":
        # The walk under ``check`` inside its block loop; the fold beside
        # it there, under its own names and not under ``check``'s, so that
        # a trace tells the check's time from the parse's and the filter's.
        assert "while/body/check/chain_walk/" in text
        for scope in ("parse", "filter"):
            assert f"while/body/{scope}/" in text
            assert f"check/{scope}/" not in text
            assert f"check/while/body/{scope}/" not in text


def test_the_programs_cover_the_catalogue():
    assert set().union(*(p[2] for p in PROGRAM_SCOPES)) == SCOPES


# --------------------------------------------- spans in a profiler capture

def _host_events(profile_dir):
    """``{line: [(name, start, end, stats)]}`` of the host planes."""
    from jax.profiler import ProfileData

    newest = sorted(profile_dir.rglob("*.xplane.pb"))[-1]
    out = {}
    for plane in ProfileData.from_file(str(newest)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out[(plane.name, line.name)] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats)) for e in line.events]
    return out


def test_a_span_is_an_event_of_the_capture(tmp_path, registry):
    jax.profiler.start_trace(str(tmp_path))
    with obs.span("check.window", window=3, members=7, path=tmp_path):
        with obs.span("inflate.h2d", bytes=12):
            jnp.arange(64).sum().block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    lines = _host_events(tmp_path)
    found = [(line, ev) for line, evs in lines.items() for ev in evs
             if ev[0] in ("check.window", "inflate.h2d")]
    assert sorted(ev[0] for _, ev in found) == ["check.window", "inflate.h2d"]
    (line_a, outer), (line_b, inner) = sorted(found, key=lambda f: f[1][0])
    assert line_a == line_b  # one thread, one line
    assert outer[1] <= inner[1] and inner[2] <= outer[2]  # nested
    # Scalar attributes ride along; anything else stays in the JSONL event.
    assert outer[3] == {"window": 3, "members": 7}
    assert inner[3] == {"bytes": 12}
    # The JSONL event is what it was.
    events = {e["name"]: e for e in registry.events()}
    assert events["inflate.h2d"]["parent"] == "check.window"
    assert events["check.window"]["attrs"]["path"] == str(tmp_path)


def test_no_registry_no_annotation(tmp_path):
    obs.shutdown()
    assert obs.span("check.window", window=1) is obs.NOOP
    jax.profiler.start_trace(str(tmp_path))
    with obs.span("check.window", window=1):
        jnp.arange(64).sum().block_until_ready()
    jax.profiler.stop_trace()
    names = {ev[0] for evs in _host_events(tmp_path).values() for ev in evs}
    assert names and "check.window" not in names


# ------------------------- the count loop's schedule, registry on and off

class _Scalar:
    """A device scalar that records who waits on it."""

    def __init__(self, log, label, value=0):
        self.log, self.label, self.value = log, label, value

    def _seen(self, how):
        self.log.append((how, self.label, threading.current_thread().name))

    def block_until_ready(self):
        self._seen("block")
        return self

    def __int__(self):
        self._seen("int")
        return self.value

    def __add__(self, other):
        return _Scalar(self.log, f"({self.label}+{other.label})",
                       self.value + other.value)


class _Operand(_Scalar):
    """The window's H2D operand."""


def _count_schedule(monkeypatch, path):
    """Runs ``count_reads`` over ``path`` with a kernel and an H2D that
    compute nothing and record every dispatch and every wait with the
    thread that made it."""
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.tpu import checker, stream_check

    log: list = []
    real_asarray = jnp.asarray

    def asarray(x, *a, **kw):
        if isinstance(x, np.ndarray) and x.dtype == np.uint8 and x.ndim == 1:
            log.append(("h2d", x.size, threading.current_thread().name))
            return _Operand(log, f"window{x.size}")
        return real_asarray(x, *a, **kw)

    def make_kernel(*_a, **_kw):
        def kernel(window, lengths, nc, n, at_eof, lo, own):
            k = sum(1 for e in log if e[0] == "dispatch")
            assert isinstance(window, _Operand)
            log.append(("dispatch", (int(n), bool(at_eof), int(lo), int(own)),
                        threading.current_thread().name))
            return {"count": _Scalar(log, f"count{k}", 1),
                    "esc_count": _Scalar(log, f"esc{k}"),
                    "survivors": _Scalar(log, f"surv{k}", 2),
                    "lanes": _Scalar(log, f"lanes{k}", 4)}
        return kernel

    monkeypatch.setattr(checker, "make_count_window", make_kernel)
    monkeypatch.setattr(stream_check.jnp, "asarray", asarray)
    ck = stream_check.StreamChecker(
        path, Config(), window_uncompressed=128 << 10, halo=32 << 10)
    return ck.count_reads(), log


@pytest.mark.skipif(load_native() is None, reason="native runtime unavailable")
def test_a_live_registry_leaves_the_count_loops_schedule_alone(
        tmp_path, monkeypatch):
    from tests.bam_factories import random_bam

    path = tmp_path / "s.bam"
    random_bam(path, 5, contigs=(("chr1", 5_000_000),), n_records=(900, 1000))
    obs.shutdown()
    total_off, log_off = _count_schedule(monkeypatch, path)
    reg = obs.configure()
    try:
        total_on, log_on = _count_schedule(monkeypatch, path)
        snap = reg.snapshot()
        events = reg.events()
    finally:
        obs.shutdown()
    main = threading.current_thread().name

    def on_main(log):
        return [e for e in log if e[2] == main]

    windows = sum(1 for e in log_off if e[0] == "dispatch")
    assert windows >= 6 and total_off == total_on == windows
    # Same H2Ds, dispatches and waits, in the same order, on the feeding
    # thread; with the registry off nothing else waits at all.
    assert on_main(log_on) == on_main(log_off) == log_off
    assert not any(e[0] == "block" and e[1].startswith("window")
                   for e in on_main(log_on))
    # The observers waited instead, once a window each.
    others = [e for e in log_on if e[2] != main]
    assert sorted(e[2] for e in others if e[0] == "block") == (
        ["obs-device"] * windows + ["obs-h2d"] * windows)

    def hist(name):
        return sum(h["count"] for h in snap["hists"] if h["name"] == name)

    for name in ("inflate.device_ms", "inflate.h2d_ms", "inflate.stall_ms",
                 "inflate.h2d", "inflate.device_kernel"):
        assert hist(name) == windows, name
    # One span a window, and the one that finds the stream's end.
    assert hist("check.window") == windows + 1
    assert [c["value"] for c in snap["counters"]
            if c["name"] == "check.windows"] == [windows]
    assert hist("check.pace") >= 1 and hist("check.flush") == 1
    assert hist("check.escape_resolve") == 0  # no escape, no resolver
    parents = {e["name"]: e.get("parent") for e in events}
    for child in ("inflate.stall_ms", "inflate.h2d", "inflate.device_kernel",
                  "check.pace"):
        assert parents[child] == "check.window", child


# ----------------------------------------------------- the batcher's cycle

class _Steps:
    """What a Batcher needs of ``MeshSteps``, computing nothing."""

    class mesh:  # noqa: N801
        devices = np.zeros(1)

    put = staticmethod(lambda a: a)

    def serve_step(self, **_kw):
        def step(ws, ns, *_rest):
            time.sleep(0.002)
            zero = np.zeros_like(ns)  # escapes, survivors, lanes
            return np.stack([ns, zero, zero, zero], axis=1)
        return step


def _row(n=10):
    from spark_bam_tpu.serve.batcher import RowTask

    return RowTask(np.zeros(64, np.uint8), n, True, 0, 10,
                   np.zeros(4, np.int32), 1)


def test_the_batchers_spans_around_a_tick(registry):
    from spark_bam_tpu.serve.batcher import Batcher

    batcher = Batcher(_Steps(), width=64, batch_rows=4, tick_ms=500.0)
    try:
        for tick in range(2):
            futures = [batcher.submit(_row(10 + i)) for i in range(4)]
            assert [f.result(timeout=10)[0] for f in futures] == [
                10, 11, 12, 13]
    finally:
        batcher.close()
    events = registry.events()

    def named(name):
        return [e for e in events if e["name"] == name]

    ticks = named("serve.tick")
    assert len(ticks) == 2 and all(
        e["attrs"] == {"rows": 4, "shape": 4} for e in ticks)
    for name in ("serve.batch_pack", "serve.scatter", "serve.h2d",
                 "serve.step", "serve.d2h"):
        assert len(named(name)) == 2, name
    # A lone tick takes two turns, one that launches it and one that
    # delivers it: a wait each, and the one that met the close.
    assert len(named("serve.batch_wait")) == len(named("serve.cycle")) == 5
    # The tick's own time: from its put (nothing ran before it) to its
    # result. Its span opens at the wait for the result, its event starts
    # at the put.
    for put, step, d2h, tick in zip(named("serve.h2d"), named("serve.step"),
                                    named("serve.d2h"), ticks):
        assert d2h["parent"] == "serve.tick"
        assert tick["t"] <= put["t"] + 1e-4 and put["t"] <= step["t"] + 1e-4
        assert tick["ms"] >= put["ms"] + step["ms"] + d2h["ms"] - 1e-2
        assert d2h["t"] + d2h["ms"] / 1e3 <= (
            tick["t"] + tick["ms"] / 1e3 + 1e-3)
    for phase in ("serve.batch_wait", "serve.batch_pack", "serve.h2d",
                  "serve.step", "serve.tick", "serve.scatter"):
        assert {e["parent"] for e in named(phase)} == {"serve.cycle"}
    # In a turn: wait, then the launch of the next tick (pack, put, step),
    # then the delivery of the one in flight (tick, scatter).
    order = [e["name"] for e in events if e["name"] in (
        "serve.batch_wait", "serve.batch_pack", "serve.h2d", "serve.step",
        "serve.tick", "serve.scatter")]
    lone = ["serve.batch_wait", "serve.batch_pack", "serve.h2d",
            "serve.step", "serve.batch_wait", "serve.tick", "serve.scatter"]
    assert order == lone * 2 + ["serve.batch_wait"]
    snap = registry.snapshot()
    queue = [h for h in snap["hists"] if h["name"] == "serve.queue_ms"]
    assert sum(h["count"] for h in queue) == 8  # one a row, as before
    assert {e["name"] for e in events} <= NAMES


class _Result:
    """A step's result that is computed when the test says so."""

    def __init__(self, value, log, k):
        self.value, self.log, self.k = value, log, k
        self.computed = threading.Event()
        self.error = None

    def is_ready(self):
        return self.computed.is_set()

    def finish(self, error=None):
        self.error = error
        self.computed.set()

    def __array__(self, dtype=None, copy=None):
        self.log.append(("read", self.k))
        assert self.computed.wait(10)
        if self.error is not None:
            raise self.error
        return self.value


class _GatedSteps(_Steps):
    """``_Steps`` whose step returns at once, as a dispatch does, with a
    ``_Result``; ``log`` has every put, launch and read in their order."""

    def __init__(self, finish_after=None):
        self.log, self.results = [], []
        self.fail_launch = ()             # the launches that raise
        self.finish_after = finish_after  # seconds, or None: the test does

    def put(self, a):
        if a.ndim == 2 and a.dtype == np.uint8:
            self.log.append(("put", len(self.results)))
        return a

    def serve_step(self, **_kw):
        def step(ws, ns, *_rest):
            k = len(self.results)
            self.log.append(("launch", k))
            zero = np.zeros_like(ns)
            out = _Result(np.stack([ns, zero, zero, zero], axis=1),
                          self.log, k)
            self.results.append(out)
            if k in self.fail_launch:
                raise RuntimeError(f"launch {k}")
            if self.finish_after is not None:
                threading.Timer(self.finish_after, out.finish).start()
            return out
        return step

    def logged(self, entry, timeout=10.0):
        end = time.monotonic() + timeout
        while entry not in self.log and time.monotonic() < end:
            time.sleep(0.001)
        return entry in self.log


def _counter(registry, name):
    return sum(c["value"] for c in registry.snapshot()["counters"]
               if c["name"] == name)


def _tick_ahead(batcher, steps, registry):
    """Rows for two ticks queued: tick 1 is put and launched before tick
    0's result is read, and counts as overlapped."""
    batcher.pause()
    futures = [batcher.submit(_row(10 + i)) for i in range(4)]
    batcher.resume()
    assert steps.logged(("read", 0))
    assert steps.log == [("put", 0), ("launch", 0), ("put", 1),
                         ("launch", 1), ("read", 0)]
    assert not any(f.done() for f in futures)
    steps.results[0].finish()
    assert [f.result(timeout=10)[0] for f in futures[:2]] == [10, 11]
    # With no row left to launch the batcher looks at tick 1's result
    # (``is_ready``) and reads it once it is computed.
    time.sleep(0.02)
    assert ("read", 1) not in steps.log and not futures[2].done()
    steps.results[1].finish()
    assert [f.result(timeout=10)[0] for f in futures[2:]] == [12, 13]
    assert _counter(registry, "serve.batches") == 2
    assert _counter(registry, "serve.ticks_overlapped") == 1


def _lone_ticks(batcher, steps, registry):
    """One client, one request of a tick's rows at a time: each tick is
    delivered as soon as its result is computed, with no row behind it to
    wait for, and none counts as overlapped."""
    for k in range(3):
        futures = [batcher.submit(_row(10 + i)) for i in range(2)]
        assert steps.logged(("launch", k))
        t0 = time.monotonic()
        steps.results[k].finish()
        assert [f.result(timeout=10)[0] for f in futures] == [10, 11]
        # Not the 0.5 s of the gather window, nor the 50 ms of an idle wait.
        assert time.monotonic() - t0 < 0.04
    assert _counter(registry, "serve.batches") == 3
    assert _counter(registry, "serve.ticks_overlapped") == 0


def _a_failed_result(batcher, steps, registry):
    """A result that cannot be read fails its tick's rows, not the rows of
    the tick launched behind it."""
    batcher.pause()
    futures = [batcher.submit(_row(10 + i)) for i in range(4)]
    batcher.resume()
    assert steps.logged(("read", 0))
    steps.results[0].finish(error=ValueError("tick 0"))
    steps.results[1].finish()
    for f in futures[:2]:
        with pytest.raises(ValueError, match="tick 0"):
            f.result(timeout=10)
    assert [f.result(timeout=10)[0] for f in futures[2:]] == [12, 13]


def _a_failed_launch(batcher, steps, registry):
    """A launch that fails fails its own rows; the tick in flight is
    delivered, and the batcher goes on."""
    steps.fail_launch = (1,)
    batcher.pause()
    futures = [batcher.submit(_row(10 + i)) for i in range(4)]
    batcher.resume()
    for f in futures[2:]:
        with pytest.raises(RuntimeError, match="launch 1"):
            f.result(timeout=10)
    steps.results[0].finish()
    assert [f.result(timeout=10)[0] for f in futures[:2]] == [10, 11]
    more = [batcher.submit(_row(20 + i)) for i in range(2)]
    assert steps.logged(("launch", 2))
    steps.results[2].finish()
    assert [f.result(timeout=10)[0] for f in more] == [20, 21]
    assert _counter(registry, "serve.ticks_overlapped") == 0


def _close_delivers(batcher, steps, registry):
    """``close()`` waits for the tick in flight and delivers it."""
    futures = [batcher.submit(_row(10 + i)) for i in range(2)]
    assert steps.logged(("launch", 0))
    closing = threading.Thread(target=batcher.close)
    closing.start()
    time.sleep(0.02)
    assert closing.is_alive() and not any(f.done() for f in futures)
    steps.results[0].finish()
    closing.join(10)
    assert not closing.is_alive()
    assert [f.result(timeout=10)[0] for f in futures] == [10, 11]
    with pytest.raises(RuntimeError, match="closed"):
        batcher.submit(_row())


def _pause_holds_the_launch(batcher, steps, registry):
    """``pause()`` holds the next launch, not the delivery of the tick in
    flight."""
    first = [batcher.submit(_row(10 + i)) for i in range(2)]
    assert steps.logged(("launch", 0))
    batcher.pause()
    held = [batcher.submit(_row(20 + i)) for i in range(2)]
    steps.results[0].finish()
    assert [f.result(timeout=10)[0] for f in first] == [10, 11]
    time.sleep(0.02)
    assert ("launch", 1) not in steps.log and batcher.backlog() == 2
    batcher.resume()
    assert steps.logged(("launch", 1))
    steps.results[1].finish()
    assert [f.result(timeout=10)[0] for f in held] == [20, 21]


def _shares_sum_to_the_histogram(batcher, steps, registry):
    """Each row's ``device_ms`` is its share of its tick's OWN time: a tick
    queued behind another is not billed that one's step, and the shares
    sum to the ``serve.tick`` histogram."""
    from spark_bam_tpu.obs import account

    costs = [account.RequestCost("count") for _ in range(2)]
    batcher.pause()
    for cost in costs:  # a request a tick
        token = account.bind(cost)
        try:
            for i in range(2):
                batcher.submit(_row(10 + i))
        finally:
            account.reset(token)
    batcher.resume()
    assert steps.logged(("read", 0))
    time.sleep(0.05)     # tick 0's step, and tick 1 queued behind it
    steps.results[0].finish()
    time.sleep(0.01)     # tick 1's own
    steps.results[1].finish()
    batcher.close()
    hist = [h for h in registry.snapshot()["hists"]
            if h["name"] == "serve.tick"]
    assert sum(h["count"] for h in hist) == 2
    assert sum(c.device_ms for c in costs) == pytest.approx(
        sum(h["sum"] for h in hist), abs=1e-6)
    assert costs[0].device_ms >= 50.0
    assert 10.0 <= costs[1].device_ms < 45.0   # 60 or more from its put
    # The rows' events carry the same length, and start where it starts.
    ticks = [e for e in registry.events() if e["name"] == "serve.tick"]
    assert [e["ms"] for e in ticks] == [
        pytest.approx(c.device_ms, abs=1e-3) for c in costs]
    assert ticks[1]["t"] == pytest.approx(
        ticks[0]["t"] + ticks[0]["ms"] / 1e3, abs=2e-3)


def test_every_row_is_answered_once_under_many_submitters(registry):
    """More submitting threads than cores, the interpreter's switch
    interval cut short: every row gets its own answer, every tick is
    delivered, and the ticks after the first few ride behind another."""
    import sys

    from spark_bam_tpu.serve.batcher import Batcher

    steps = _GatedSteps(finish_after=0.001)
    batcher = Batcher(steps, width=64, batch_rows=4, tick_ms=0.2)
    got, threads = {}, []

    def client(k):
        futures = [(n, batcher.submit(_row(n)))
                   for n in range(k * 100, k * 100 + 40)]
        got[k] = [(n, f.result(timeout=20)[0]) for n, f in futures]

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        sys.setswitchinterval(interval)
        batcher.close()
    assert not any(t.is_alive() for t in threads)
    assert sorted(got) == list(range(24))
    assert all(n == answer for rows in got.values() for n, answer in rows)
    ticks = len(steps.results)
    assert _counter(registry, "serve.batches") == ticks
    assert sum(k * v for k, v in batcher.batch_sizes.items()) == 24 * 40
    assert _counter(registry, "serve.ticks_overlapped") > ticks // 2


@pytest.mark.parametrize("case", [
    _tick_ahead, _lone_ticks, _a_failed_result, _a_failed_launch,
    _close_delivers, _pause_holds_the_launch, _shares_sum_to_the_histogram,
], ids=lambda case: case.__name__.lstrip("_"))
def test_the_batcher_keeps_a_tick_ahead(registry, case):
    from spark_bam_tpu.serve.batcher import Batcher

    steps = _GatedSteps()
    batcher = Batcher(steps, width=64, batch_rows=2, tick_ms=500.0)
    try:
        case(batcher, steps, registry)
    finally:
        for out in steps.results:
            out.computed.set()
        batcher.close()
    assert {e["name"] for e in registry.events()} <= NAMES


# ------------------------------------------------------- the --profile hook

def test_the_profile_hook_takes_a_window_that_does_not_compile(
        tmp_path, monkeypatch):
    """The first window of a shape compiles; the capture is of the first
    window whose shape has run before, once."""
    from spark_bam_tpu.tpu import inflate

    monkeypatch.setenv(inflate.PROFILE_ENV, str(tmp_path))
    monkeypatch.setattr(inflate, "_profiled", False)
    monkeypatch.setattr(inflate, "_profile_seen", set())
    for shape in ((512, 386), (512, 260)):  # two shapes, first runs
        with inflate.maybe_profile_window("count_window", shape) as dump:
            assert dump is None
    with inflate.maybe_profile_window("count_window", (512, 386)) as dump:
        assert dump is not None
        jnp.arange(64).sum().block_until_ready()
    assert list(tmp_path.rglob("*.xplane.pb"))
    with inflate.maybe_profile_window("count_window", (512, 386)) as dump:
        assert dump is None  # one shot
