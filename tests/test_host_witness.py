"""The registry's witness of the host (``obs/witness.py``): what a wake that
came late, a collection and a pass's own account put into the registry, and
when the thread and the collector's hook exist at all.

The clock is ``tests/test_pass_tracing.py``'s fake, for the registry and the
witness alike, and the witness's wait is the test's own (it advances that
clock by the wait and by how late the test says the wake came), so the
cases below drive ``Witness.tick`` by hand and none of them sleeps. The
cases about the thread itself start the real one and wait for nothing but
its end.
"""

import gc
import importlib
import io
import json
import threading
import time

import pytest
from test_pass_tracing import clock, one_pass  # noqa: F401  (a fixture)

from spark_bam_tpu import obs
from spark_bam_tpu.obs import witness as obs_witness
from spark_bam_tpu.obs.witness import STOP_MS, THREAD_NAME, TICK_S, Witness

obs_registry = importlib.import_module("spark_bam_tpu.obs.registry")


def witness_threads() -> list:
    return [t for t in threading.enumerate() if t.name == THREAD_NAME]


def hooks() -> list:
    return [cb for cb in gc.callbacks
            if getattr(cb, "__self__", None).__class__ is Witness]


def hist(name: str):
    found = [h for h in obs.registry().snapshot()["hists"]
             if h["name"] == name]
    return found[0] if found else None


def counter(name: str) -> int:
    return sum(c["value"] for c in obs.registry().snapshot()["counters"]
               if c["name"] == name)


def stops() -> list:
    return [e for e in obs.registry().events() if e["name"] == "host.stop"]


@pytest.fixture
def witness(clock, monkeypatch):  # noqa: F811
    """The live registry's witness, on the fake clock and never started:
    ``wake(late_ms)`` is one wait that ends ``late_ms`` late; of that,
    Python's collector ran ``gc_ms``, from ``gc_early_ms`` before the
    wait's end on."""
    monkeypatch.setattr(obs_witness, "time", clock)
    reg = obs.registry()
    plan = []

    def wait(seconds: float) -> None:
        late_ms, gc_ms, gc_early_ms, gc_open = plan.pop()
        clock.sleep_ms(seconds * 1e3 - gc_early_ms)
        if gc_ms:
            w._on_gc("start", {"generation": 2})
            clock.sleep_ms(gc_early_ms + gc_ms)
            if not gc_open:
                w._on_gc("stop", {"generation": 2})
        clock.sleep_ms(late_ms - gc_ms + (0 if gc_ms else gc_early_ms))

    w = Witness(reg, wait=wait)
    reg._witness = w  # the first pass finds one and starts no thread

    def wake(late_ms: float = 0.0, gc_ms: float = 0.0,
             gc_early_ms: float = 0.0, gc_open: bool = False) -> None:
        """``gc_open``: the wake comes before the hook's second call has
        run, as it does on the interpreter (the lock is handed over as that
        call begins); the call follows the wake."""
        plan.append((late_ms, gc_ms, gc_early_ms, gc_open))
        w.tick()
        if gc_open:
            w._on_gc("stop", {"generation": 2})

    w.wake = wake
    return w


# --------------------------------------------------------------- a late wake


@pytest.mark.parametrize("late_ms, stopped", [
    (0.0, 0), (39.0, 0), (STOP_MS + 1, 1), (120.0, 1), (2566.0, 1),
])
def test_a_wake_is_a_stop_from_forty_milliseconds_late(
        witness, late_ms, stopped):
    witness.wake(late_ms)
    late = hist("host.overshoot_ms")
    assert late["count"] == 1 and late["values"] == [pytest.approx(late_ms)]
    assert hist("host.pace_us")["count"] == 1  # the unit of work, every wake
    assert counter("host.stops") == stopped
    assert len(stops()) == stopped
    if stopped:
        (event,) = stops()
        assert event["ms"] == pytest.approx(late_ms)
        # It starts where the wait should have ended; no pass is open, so
        # it is in no trace.
        assert event["t"] == pytest.approx(1000.0 + TICK_S)
        assert "trace" not in event and "pspan" not in event
        assert hist("host.stop")["values"] == [pytest.approx(late_ms)]
    else:
        assert hist("host.stop") is None


@pytest.mark.parametrize("gc_open", [False, True])
@pytest.mark.parametrize("late_ms, gc_ms, gc_early_ms, stop_ms", [
    (45.0, 44.0, 0.0, None),    # a full collection held the wake back
    (45.0, 44.0, 10.0, None),   # one that began before the wait's end
    (130.0, 44.0, 0.0, 86.0),   # the machine stood still besides
])
def test_the_collector_is_not_a_stop_of_the_machine(
        witness, late_ms, gc_ms, gc_early_ms, stop_ms, gc_open):
    """The wake needs the interpreter lock and a collection holds it: what
    of a collection ran past the wait's end comes out of the lateness,
    whether the hook has closed the collection by then or not."""
    witness.wake(late_ms, gc_ms, gc_early_ms, gc_open)
    gc_total = gc_ms + gc_early_ms + (late_ms - gc_ms if gc_open else 0)
    assert hist("host.gc")["values"] == [pytest.approx(gc_total)]
    assert hist("host.overshoot_ms")["values"] == [
        pytest.approx(0.0 if gc_open else late_ms - gc_ms, abs=1e-6)]
    stopped = stop_ms is not None and not gc_open
    assert counter("host.stops") == int(stopped)
    assert [e["ms"] for e in stops()] == (
        [pytest.approx(stop_ms)] if stopped else [])
    witness.wake(0.0)  # the next wake owes the collection nothing
    assert hist("host.overshoot_ms")["values"][1] == pytest.approx(
        0.0, abs=1e-6)


def plain_pass(clock):  # noqa: F811
    """A pass of 31 ms whose head is the member walk, as the cells' are."""
    with obs.pass_span("load.count") as root:
        with obs.span("bgzf.read"):
            clock.sleep_ms(8)
        with obs.span("check.window"):
            clock.sleep_ms(20)
            obs.dispatched()
        with obs.span("load.drain"):
            clock.sleep_ms(3)
    return root


def test_a_stop_inside_a_pass_is_in_its_trace_its_account_and_its_line(
        witness, clock):  # noqa: F811
    """PR 41's 3.084 s pass: the machine stood still for 2,566 ms from
    0.15 s into it, under the member walk. The record named ``bgzf.read``;
    now ``host.stop`` carries the same excess beside it."""
    from bench.readers import slowest_pass

    for _ in range(5):
        plain_pass(clock)
    with obs.pass_span("load.count") as root:
        with obs.span("bgzf.read"):
            clock.sleep_ms(150)
            witness.wake(2566.0)
        with obs.span("check.window"):
            clock.sleep_ms(20)
            obs.dispatched()
    assert counter("host.stops") == 1
    (event,) = stops()
    assert event["trace"] == root.trace_id
    assert event["pspan"] == root.span_id and event["span"]
    assert event["ms"] == pytest.approx(2566.0)
    assert hist("load.stop_ms")["values"] == [0.0] * 5 + [
        pytest.approx(2566.0)]
    snapshot = obs.registry().snapshot()
    (kept,) = snapshot["slowest_passes"]
    assert kept["trace"] == root.trace_id
    assert kept["stop_ms"] == pytest.approx(2566.0) and kept["gc_ms"] == 0.0
    assert kept["spans"]["host.stop"] == [
        1, pytest.approx(2566.0), pytest.approx(2566.0)]
    # The benchmark's reader as it stands: a row for every name of the
    # record, the rows sorted by their excess over a median pass.
    line = slowest_pass.table(snapshot, ["load.count"])
    names = [row[0] for row in line["rows"]]
    assert set(names[:2]) == {"bgzf.read", "host.stop"}
    row = line["rows"][names.index("host.stop")]
    # One stop among six passes: a median pass holds a sixth of the median
    # stop, so all but that of the row is excess.
    assert row[1:] == [1, pytest.approx(2566.0), pytest.approx(2566.0 / 6)]
    assert line["ms"] / line["median_ms"] == pytest.approx(2756.0 / 31.0)


def test_two_open_passes_both_get_the_stop_and_the_histogram_one(
        witness, clock):  # noqa: F811
    opened, release = threading.Barrier(3), threading.Event()
    roots = []

    def a_pass():
        with obs.pass_span("load.count") as root:
            roots.append(root)
            opened.wait(timeout=10)
            assert release.wait(timeout=10)

    threads = [threading.Thread(target=a_pass) for _ in range(2)]
    for t in threads:
        t.start()
    opened.wait(timeout=10)
    witness.wake(130.0)
    release.set()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    events = stops()
    assert {e["trace"] for e in events} == {r.trace_id for r in roots}
    assert len(events) == 2
    assert hist("host.stop")["count"] == 1 and counter("host.stops") == 1
    assert hist("load.stop_ms")["values"] == [pytest.approx(130.0)] * 2
    witness.wake(55.0)  # both have ended: in no trace
    assert "trace" not in stops()[-1]
    assert hist("load.stop_ms")["count"] == 2


def test_a_pass_accounts_for_its_cpu_time(witness, clock):  # noqa: F811
    """A pass that waited against one that computed on two cores."""
    with obs.pass_span("load.count"):
        clock.sleep_ms(40)
    with obs.pass_span("load.count"):
        clock.sleep_ms(40, cpu=2.0)
    assert hist("load.cpu_ms")["values"] == [0.0, pytest.approx(80.0)]
    assert hist("load.gc_ms")["values"] == [0.0, 0.0]
    kept = obs.registry().snapshot()["slow_passes"]
    assert [p["cpu_ms"] for p in kept] == [0.0, pytest.approx(80.0)]


# ------------------------------------------------------------ the collector


def test_a_full_collection_inside_a_pass_is_a_host_gc_event(clock):  # noqa: F811
    """The real hook on the real clock (the registry's stays the fake):
    a forced full collection is recorded whatever it took."""
    with obs.pass_span("load.count") as root:
        assert len(hooks()) == 1
        gc.collect()
    mine = [e for e in obs.registry().events()
            if e["name"] == "host.gc" and e.get("trace") == root.trace_id
            and e["attrs"]["generation"] == 2]
    assert mine and all(e["pspan"] == root.span_id for e in mine)
    total = sum(e["ms"] for e in mine)
    assert total > 0
    assert hist("load.gc_ms")["values"][0] >= total - 0.01
    kept = obs.registry().snapshot()["slowest_passes"][0]
    assert kept["gc_ms"] > 0 and kept["spans"]["host.gc"][0] >= len(mine)
    gc.collect()  # no pass open: recorded, in no trace
    assert "trace" not in [e for e in obs.registry().events()
                           if e["name"] == "host.gc"][-1]


def test_a_short_young_collection_is_not_recorded(witness, clock):  # noqa: F811
    for generation, ms, recorded in [(0, 0.2, 0), (0, 1.5, 1), (2, 0.1, 2)]:
        witness._on_gc("start", {"generation": generation})
        clock.sleep_ms(ms)
        witness._on_gc("stop", {"generation": generation})
        found = hist("host.gc")
        assert (found["count"] if found else 0) == recorded


# ------------------------------------------------------- the eight slowest


def test_the_eight_slowest_passes_are_kept_in_order_and_the_slowest_alone(
        witness, clock, tmp_path):  # noqa: F811
    from spark_bam_tpu.obs.exporters import merge_snapshots
    from spark_bam_tpu.obs.report import load_trace, render_report

    traces = [one_pass(clock, [10 * k]) for k in (3, 1, 4, 10, 5, 9, 2, 6, 8, 7)]
    one_pass(clock, [15], root="load.check_bam")
    by_ms = {10 * k + 5: t for k, t in zip((3, 1, 4, 10, 5, 9, 2, 6, 8, 7),
                                           traces)}
    snapshot = obs.registry().snapshot()
    slow = [p for p in snapshot["slow_passes"] if p["root"] == "load.count"]
    assert [p["ms"] for p in slow] == [pytest.approx(10 * k + 5)
                                       for k in range(10, 2, -1)]
    assert [p["trace"] for p in slow] == [by_ms[10 * k + 5]
                                          for k in range(10, 2, -1)]
    assert all(p["spans"]["check.window"][0] == 1 for p in slow)
    assert {"stop_ms", "gc_ms", "cpu_ms", "at_s"} <= set(slow[0])
    # One record a root name stays what ``slowest_passes`` is.
    assert sorted((p["root"], p["ms"]) for p in snapshot["slowest_passes"]) == [
        ("load.check_bam", pytest.approx(20)), ("load.count", pytest.approx(105))]
    # The JSONL carries both, a reader gets both back, a fleet keeps eight.
    path = tmp_path / "m.jsonl"
    obs.export_jsonl(path)
    kinds = [json.loads(line)["e"] for line in path.read_text().splitlines()]
    assert kinds.count("slowest_pass") == 2 and kinds.count("slow_pass") == 9
    loaded = load_trace(path)["snapshot"]
    assert loaded["slow_passes"] == [
        {"e": "slow_pass", **p} for p in snapshot["slow_passes"]]
    other = {"slow_passes": [{"root": "load.count", "ms": 99.0, "trace": "w2",
                              "spans": {}}]}
    merged = merge_snapshots([loaded, other, {}])["slow_passes"]
    counts = [p for p in merged if p["root"] == "load.count"]
    assert [p["ms"] for p in counts] == [
        pytest.approx(ms) for ms in (105, 99, 95, 85, 75, 65, 55, 45)]
    # The report: the kept passes' trees, slowest first, each with its
    # account on its first line; the two that were not kept come after.
    report = render_report(path, max_traces=10)
    trees = [b for b in report.split("\n\n") if b.startswith("trace ")]
    assert [t.split()[1] for t in trees[:8]] == [p["trace"] for p in slow]
    assert all("spans): stop_ms=0.000 gc_ms=0.000 cpu_ms=0.000\n" in t
               for t in trees[:9])
    assert len(trees) == 10 and "stop_ms" not in trees[9]
    assert "... 1 more traces omitted" in report


# ------------------------------------------- when the witness exists at all


def test_configure_alone_starts_nothing():
    obs.shutdown()
    before = list(gc.callbacks)
    reg = obs.configure()
    try:
        obs.count("bgzf.blocks_read")
        with obs.span("inflate.window"):
            pass
        assert not witness_threads() and gc.callbacks == before
        snap = reg.snapshot()
        names = {m["name"] for kind in ("counters", "gauges", "hists")
                 for m in snap[kind]}
        assert names == {"bgzf.blocks_read", "inflate.window"}
        assert snap["slowest_passes"] == snap["slow_passes"] == []
    finally:
        obs.shutdown()


@pytest.mark.parametrize("root", ["a pass", "serve.request"])
def test_the_first_root_starts_it_and_shutdown_ends_it(root):
    obs.shutdown()
    before = list(gc.callbacks)
    obs.configure()
    try:
        opened = (obs.pass_span("load.count") if root == "a pass"
                  else obs.span(root, op="count"))
        with opened:
            (thread,) = witness_threads()
            assert thread.daemon and len(hooks()) == 1
        with obs.pass_span("load.count"):  # a second root: the same one
            assert witness_threads() == [thread]
    finally:
        obs.shutdown()
    thread.join(timeout=10)
    assert not thread.is_alive()
    assert not witness_threads() and gc.callbacks == before


def test_with_the_registry_off_there_is_no_witness():
    obs.shutdown()
    before = list(gc.callbacks)
    assert obs.pass_span("load.count", path="x") is obs.NOOP
    with obs.pass_span("load.count"), obs.span("serve.request"):
        obs.dispatched()
    assert not witness_threads() and gc.callbacks == before


def test_the_real_thread_fills_the_histograms_and_ends_at_once():
    """The one case on the real clock: a wake or two, then ``shutdown``
    must not wait a tick out."""
    obs.shutdown()
    reg = obs.configure()
    try:
        with obs.pass_span("load.count"):
            deadline = time.monotonic() + 10
            while (not any(h["name"] == "host.pace_us"
                           for h in reg.snapshot()["hists"])
                   and time.monotonic() < deadline):
                time.sleep(TICK_S / 4)
        names = {h["name"] for h in reg.snapshot()["hists"]}
        assert {"host.overshoot_ms", "host.pace_us", "load.stop_ms",
                "load.gc_ms", "load.cpu_ms"} <= names
    finally:
        t0 = time.monotonic()
        obs.shutdown()
    assert not witness_threads()
    assert time.monotonic() - t0 < 5.0


# --------------------------------------------------------------- operators


def test_top_prints_the_host_line_against_a_live_daemon(tmp_path):
    from spark_bam_tpu.benchmarks.synth import synthetic_fixture
    from spark_bam_tpu.cli import top
    from spark_bam_tpu.cli.output import Printer
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.serve import ServeClient, ServerThread, SplitService

    bam = str(synthetic_fixture(tmp_path))
    obs.shutdown()
    obs.configure()
    svc = SplitService(Config(serve="window=64KB,halo=8KB,batch=8,tick=5"))
    try:
        with ServerThread(svc) as srv:
            out = io.StringIO()
            top.run(srv.address, Printer(out=out))
            assert "host:" not in out.getvalue()  # no request yet: no witness
            with ServeClient(srv.address) as c:
                assert c.request("count", path=bam)["count"] > 0
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                out = io.StringIO()
                top.run(srv.address, Printer(out=out))
                if "host:" in out.getvalue():
                    break
                time.sleep(TICK_S)
    finally:
        svc.close()
        obs.shutdown()
    (line,) = [ln for ln in out.getvalue().splitlines() if "host:" in ln]
    words = line.split()
    assert words[:2] == ["host:", "stops"] and words[3] == "worst"
    assert words[5:7] == ["pace", "p50/p99"] and words[8] == "gc"
    assert int(words[2]) >= 0 and words[4].endswith("ms")
    assert not witness_threads()
