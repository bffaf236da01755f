"""The pass as a unit of tracing (``obs.pass_span``): one identifier a pass
on every thread the pass starts, the pass's own account at the root's exit,
and the slowest pass kept with its spans summed by name.

The registry's clock is a fake here, so every millisecond below is worked
out by hand. The three real paths (the one-chip stream, the mesh count,
check-bam) are held to the same in ``tests/test_host_fed_count.py`` and
``tests/test_check_bam_tpu.py``, on their own fixtures.
"""

import importlib
import json
import threading
from concurrent.futures import ThreadPoolExecutor

import pytest

from spark_bam_tpu import obs
from spark_bam_tpu.obs.names import NAMES

# ``obs.registry`` the attribute is the function; this is the module.
obs_registry = importlib.import_module("spark_bam_tpu.obs.registry")


class FakeClock:
    """``time`` for the registry: its clocks advance only when told to.
    The process's CPU time advances with the ``cpu`` share of a sleep (0:
    the pass waited; 1: it computed on one core)."""

    def __init__(self):
        self.now = 1000.0
        self.cpu_s = 50.0

    def perf_counter(self) -> float:
        return self.now

    def time(self) -> float:
        return self.now

    def process_time(self) -> float:
        return self.cpu_s

    def sleep_ms(self, ms: float, cpu: float = 0.0) -> None:
        self.now += ms / 1e3
        self.cpu_s += cpu * ms / 1e3


@pytest.fixture
def clock(monkeypatch):
    fake = FakeClock()
    monkeypatch.setattr(obs_registry, "time", fake)
    obs.shutdown()
    obs.configure()
    yield fake
    obs.shutdown()


def one_pass(clock, window_ms, root="load.count"):
    """A pass of three phases: 2 ms of opening, one window a ``window_ms``
    (its dispatch returns 1 ms before the window's end), 3 ms of drain."""
    with obs.pass_span(root, path="f.bam") as span:
        with obs.span("load.open"):
            clock.sleep_ms(2)
        for ms in window_ms:
            with obs.span("check.window"):
                with obs.span("inflate.stall_ms"):
                    clock.sleep_ms(ms - 1)
                obs.dispatched()
                clock.sleep_ms(1)
        with obs.span("load.drain"):
            clock.sleep_ms(3)
    return span.trace_id


def slowest(root="load.count"):
    records = obs.registry().snapshot()["slowest_passes"]
    return next((p for p in records if p["root"] == root), None)


def of_the_program(names):
    """``names`` (a record's ``spans``, a set of names, a list of events)
    without what the witness of the host put there: it runs on the real
    clock beside these passes (``tests/test_host_witness.py`` drives it by
    hand), and a busy sandbox or a full collection would else be a row."""
    def mine(item) -> bool:
        name = item["name"] if isinstance(item, dict) else item
        return not name.startswith("host.")

    if isinstance(names, dict):
        return {k: v for k, v in names.items() if mine(k)}
    return type(names)(item for item in names if mine(item))


def test_the_slowest_pass_is_kept_with_its_excess_under_one_span(clock):
    first = one_pass(clock, [10, 10])
    assert slowest()["trace"] == first and slowest()["ms"] == pytest.approx(25)
    slow = one_pass(clock, [10, 500])  # the middle pass, slow in ONE span
    one_pass(clock, [10, 10])          # a later, faster pass
    kept = slowest()
    assert kept["trace"] == slow and kept["ms"] == pytest.approx(515)
    assert kept["t"] == pytest.approx(1000.025)
    # [count, summed ms, max ms] a name; the root's own event is not a row.
    assert of_the_program(kept["spans"]) == {
        "load.open": [1, pytest.approx(2), pytest.approx(2)],
        "check.window": [2, pytest.approx(510), pytest.approx(500)],
        "inflate.stall_ms": [2, pytest.approx(508), pytest.approx(499)],
        "load.drain": [1, pytest.approx(3), pytest.approx(3)],
    }
    # What a median over the passes hides and the record names: the excess
    # over a plain pass's 20 ms of windows lies under check.window.
    assert kept["spans"]["check.window"][1] - 20 == pytest.approx(490)


def test_a_root_name_keeps_a_record_of_its_own(clock):
    one_pass(clock, [40])
    one_pass(clock, [10], root="load.check_bam")
    assert slowest()["ms"] == pytest.approx(45)
    assert slowest("load.check_bam")["ms"] == pytest.approx(15)


def test_head_and_drain_are_observed_once_a_pass(clock):
    one_pass(clock, [10, 30])
    one_pass(clock, [20])
    hists = {h["name"]: h for h in obs.registry().snapshot()["hists"]}
    # Root start to the return of the FIRST dispatch: 2 ms + (ms - 1).
    assert hists["load.head_ms"]["values"] == [
        pytest.approx(11), pytest.approx(21)]
    # The return of the LAST dispatch to the root's end: 1 ms + the drain.
    assert hists["load.drain_ms"]["values"] == [
        pytest.approx(4), pytest.approx(4)]
    assert hists["load.count"]["count"] == 2


def test_a_pass_without_a_dispatch_has_no_account(clock):
    with obs.pass_span("load.count"):
        clock.sleep_ms(5)
    names = {h["name"] for h in obs.registry().snapshot()["hists"]}
    assert "load.count" in names
    assert not {"load.head_ms", "load.drain_ms"} & names
    assert of_the_program(slowest()["spans"]) == {}


def test_nothing_is_kept_when_the_registry_is_off():
    obs.shutdown()
    assert obs.pass_span("load.count", path="x") is obs.NOOP
    with obs.pass_span("load.count"):
        obs.dispatched()  # outside a live registry: nothing
    obs.configure()
    try:
        obs.dispatched()  # live, but outside a pass: nothing either
        snap = obs.registry().snapshot()
        assert snap["slowest_passes"] == [] and snap["hists"] == []
    finally:
        obs.shutdown()


def test_two_passes_carry_two_traces_on_the_threads_they_start(clock):
    """A pool's threads and a started thread begin with an empty context:
    ``obs.trace.carried`` hands them the submitter's."""

    def work(name):
        with obs.span(name):
            pass

    def a_pass():
        with obs.pass_span("load.count") as root:
            with ThreadPoolExecutor(2) as pool:
                pool.submit(obs.trace.carried(work), "inflate.window").result()
                pool.submit(work, "inflate.block").result()  # not carried
            with obs.span("mesh.stall"):
                t = threading.Thread(
                    target=obs.trace.carried(work), args=("mesh.assemble",))
                t.start()
                t.join(timeout=10)
                assert not t.is_alive()
        return root

    roots = [a_pass(), a_pass()]
    assert roots[0].trace_id != roots[1].trace_id
    events = obs.registry().events()
    for root in roots:
        mine = {e["name"]: e for e in events if e.get("trace") == root.trace_id}
        assert of_the_program(set(mine)) == {
            "load.count", "inflate.window", "mesh.stall", "mesh.assemble"}
        # Each under the span that was open where it was handed over.
        assert mine["inflate.window"]["pspan"] == root.span_id
        assert mine["mesh.assemble"]["pspan"] == mine["mesh.stall"]["span"]
        assert "pspan" not in mine["load.count"]
    loose = [e for e in events if e["name"] == "inflate.block"]
    assert len(loose) == 2 and not any("trace" in e for e in loose)
    assert obs.trace.current() is None  # nothing left bound here


def test_the_record_survives_a_compaction_of_the_event_buffer(clock):
    """Tail sampling drops other traces' events while a pass runs: the
    pass's mark into the buffer is void then, and the whole buffer is
    read."""
    reg = obs.registry()
    for i in range(reg._DROP_COMPACT):
        reg.emit_span_event("serve.request", 1.0, trace_id=f"req{i}")
    with obs.pass_span("load.count"):
        with obs.span("load.open"):
            clock.sleep_ms(2)
        for i in range(reg._DROP_COMPACT):
            reg.drop_trace(f"req{i}")  # the last one compacts
        with obs.span("check.window"):
            clock.sleep_ms(7)
    assert len(of_the_program(reg.events())) == 3
    assert of_the_program(slowest()["spans"]) == {
        "load.open": [1, pytest.approx(2), pytest.approx(2)],
        "check.window": [1, pytest.approx(7), pytest.approx(7)],
    }


def test_the_jsonl_carries_the_record_and_the_report_reads_a_tree(
        clock, tmp_path):
    from spark_bam_tpu.obs.exporters import merge_snapshots
    from spark_bam_tpu.obs.report import load_trace, render_report

    one_pass(clock, [10])
    slow = one_pass(clock, [300])
    path = tmp_path / "m.jsonl"
    obs.export_jsonl(path)
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    records = [ev for ev in lines if ev["e"] == "slowest_pass"]
    assert len(records) == 1 and records[0]["trace"] == slow
    assert records[0]["spans"]["check.window"] == [1, 300.0, 300.0]
    snapshot = load_trace(path)["snapshot"]
    assert snapshot["slowest_passes"][0]["ms"] == pytest.approx(305)
    # A fleet's slowest is the slowest of its workers'.
    other = {"slowest_passes": [{"root": "load.count", "ms": 9000.0,
                                 "t": 1.0, "trace": "w2", "spans": {}}]}
    merged = merge_snapshots([snapshot, other, {}])["slowest_passes"]
    assert [p["trace"] for p in merged] == ["w2"]
    report = render_report(path)
    assert "slowest load.count pass: 305.000ms" in report
    assert "  check.window: 1 x, 300.000ms, max 300.000ms" in report
    # One tree a pass, the slowest first.
    trees = [b for b in report.split("\n\n") if b.startswith("trace ")]
    assert len(trees) == 2 and trees[0].startswith(f"trace {slow} (5 spans)")
    assert "load.count 305.000ms" in trees[0]
    assert "\n    inflate.stall_ms 299.000ms" in trees[0]


@pytest.mark.parametrize("name", [
    "load.open", "load.drain", "load.head_ms", "load.drain_ms", "mesh.plan",
    "bgzf.read", "load.count", "load.check_bam",
    "load.stop_ms", "load.gc_ms", "load.cpu_ms", "host.stop", "host.gc",
    "host.sleep", "host.overshoot_ms", "host.pace_us", "host.stops",
])
def test_every_name_of_a_pass_is_in_the_catalogue(name):
    assert name in NAMES


def test_the_host_is_a_layer_of_the_catalogue():
    from spark_bam_tpu.obs.names import LAYERS

    assert "host" in LAYERS


def test_the_lint_holds_pass_span_to_the_catalogue():
    from spark_bam_tpu.analysis.rules.obs_contract import NAME_FNS

    assert "pass_span" in NAME_FNS
