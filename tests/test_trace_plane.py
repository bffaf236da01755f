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


def test_the_batchers_spans_around_a_tick(registry):
    from spark_bam_tpu.serve.batcher import Batcher, RowTask

    batcher = Batcher(_Steps(), width=64, batch_rows=4, tick_ms=500.0)
    try:
        for tick in range(2):
            futures = [
                batcher.submit(RowTask(np.zeros(64, np.uint8), 10 + i, True,
                                       0, 10, np.zeros(4, np.int32), 1))
                for i in range(4)]
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
    # One wait a tick, and the one that met the close.
    assert len(named("serve.batch_wait")) == 3
    for child in ("serve.h2d", "serve.step", "serve.d2h"):
        for e, tick in zip(named(child), ticks):
            assert e["parent"] == "serve.tick"
            assert tick["t"] <= e["t"] + 1e-4
            assert e["t"] + e["ms"] / 1e3 <= tick["t"] + tick["ms"] / 1e3 + 1e-3
    for phase in ("serve.batch_wait", "serve.batch_pack", "serve.tick",
                  "serve.scatter"):
        assert {e["parent"] for e in named(phase)} == {"serve.cycle"}
    assert len(named("serve.cycle")) == 3
    # In a cycle: wait, pack, tick, scatter.
    order = [e["name"] for e in events if e["name"] in (
        "serve.batch_wait", "serve.batch_pack", "serve.tick",
        "serve.scatter")]
    assert order == ["serve.batch_wait", "serve.batch_pack", "serve.tick",
                     "serve.scatter"] * 2 + ["serve.batch_wait"]
    snap = registry.snapshot()
    queue = [h for h in snap["hists"] if h["name"] == "serve.queue_ms"]
    assert sum(h["count"] for h in queue) == 8  # one a row, as before
    assert {e["name"] for e in events} <= NAMES


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
