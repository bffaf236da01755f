"""The head and the tail of a pass on the device's clock, on synthetic
planes worked out by hand (beside ``test_gap_by_span.py``)."""

import json

import pytest

from bench.readers import gap_by_span, pass_edges, xplane
from bench.readers.xplane import Event

MS = 1e6  # nanoseconds
KNOWN = frozenset({"load.count", "load.open", "bgzf.read", "check.window",
                   "inflate.stall_ms", "inflate.device_kernel", "check.flush",
                   "load.drain", "inflate.window", "mesh.dispatch"})
DISPATCH = ["inflate.device_kernel", "mesh.dispatch"]


def ev(name, start, end):
    return Event(name, start * MS, (end - start) * MS, {})


# A pass 10-110 ms on the feeding thread. Its head: load.open 10-12,
# nothing 12-13, bgzf.read 13-23, check.window 23-41 holding
# inflate.stall_ms 24-38 and the dispatch 39-40. The chip's first operation
# starts at 44 (so 41-44 of the head lies under the root alone, with 12-13),
# its last ends at 100; a second window's span 60-99; check.flush 100-104,
# nothing 104-105, load.drain 105-109, nothing 109-110.
HOST = ("/host:CPU", [
    ("python3", [ev("inflate.window", 11, 37)]),  # a worker: not the thread
    ("python3", [
        ev("$scan.py:1 window", 0, 200),           # the python tracer's
        ev("load.count", 10, 110),
        ev("load.open", 10, 12), ev("bgzf.read", 13, 23),
        ev("check.window", 23, 41), ev("inflate.stall_ms", 24, 38),
        ev("inflate.device_kernel", 39, 40),
        ev("check.window", 60, 99),
        ev("check.flush", 100, 104), ev("load.drain", 105, 109),
    ]),
])
ONE_CHIP = [
    ("/device:TPU:0", [
        ("XLA Modules", [ev("jit_count_window(1)", 44, 100)]),
        ("XLA Ops", [ev("%a", 44, 58), ev("%b", 58, 59), ev("%c", 70, 100),
                     ev("%before", 2, 5), ev("%after", 150, 160)]),
    ]),
    HOST,
]


def test_head_and_tail_split_over_nested_spans():
    out = pass_edges.reduce_planes(ONE_CHIP, ["load.check_bam", "load.count"],
                                   DISPATCH, KNOWN)
    assert out["phase"] == "pass_edges" and out["root"] == "load.count"
    assert out["head_ms"] == pytest.approx(34)   # 10 -> 44
    assert out["host_ms"] == pytest.approx(30)   # 10 -> 40
    assert out["launch_ms"] == pytest.approx(4)  # 40 -> 44
    assert out["tail_ms"] == pytest.approx(10)   # 100 -> 110
    assert out["planes"] == 1 and out["clock"] is True
    # Innermost span first; the window's own 23-24, 38-39 and 40-41.
    assert dict(out["head_rows"]) == {
        "inflate.stall_ms": pytest.approx(14), "bgzf.read": pytest.approx(10),
        "check.window": pytest.approx(3), "load.open": pytest.approx(2),
        "inflate.device_kernel": pytest.approx(1),
    }
    assert [r[0] for r in out["head_rows"]][:2] == [
        "inflate.stall_ms", "bgzf.read"]  # most first
    assert dict(out["tail_rows"]) == {
        "check.flush": pytest.approx(4), "load.drain": pytest.approx(4)}
    # Under the root alone: 12-13, 41-44, 104-105, 109-110.
    assert out["unattributed_ms"] == pytest.approx(6)
    # Operations before and after the root, and the worker's span, took
    # no part.
    assert "inflate.window" not in dict(out["head_rows"])


def test_the_first_chip_to_start_and_the_last_to_end_bound_the_pass():
    second = ("/device:TPU:1", [
        ("XLA Ops", [ev("%a", 42, 60), ev("%c", 70, 103)])])
    out = pass_edges.reduce_planes(
        [ONE_CHIP[0], second, HOST], ["load.count"], DISPATCH, KNOWN)
    assert out["planes"] == 2
    assert out["head_ms"] == pytest.approx(32)   # chip 1 starts at 42
    assert out["tail_ms"] == pytest.approx(7)    # and ends at 103
    assert dict(out["tail_rows"]) == {
        "check.flush": pytest.approx(1), "load.drain": pytest.approx(4)}


def test_three_metrics_one_table_one_line(monkeypatch, capsys):
    loads = []
    monkeypatch.setattr(xplane, "load",
                        lambda path: loads.append(path) or ONE_CHIP)
    monkeypatch.setattr(gap_by_span, "span_names", lambda: KNOWN)
    sources = {"profile": {"file": "somewhere"}}
    args = {"roots": ["load.count", "load.check_bam"],
            "dispatch_spans": DISPATCH}
    got = {v: pass_edges.read({**args, "value": v}, sources)
           for v in ("head_ms", "tail_ms", "attributed_share")}
    assert got == {"head_ms": pytest.approx(34), "tail_ms": pytest.approx(10),
                   "attributed_share": pytest.approx(100 * 38 / 44)}
    assert loads == ["somewhere"]
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 1 and json.loads(lines[0])["phase"] == "pass_edges"


def without(planes, *names):
    return [(plane, [(line, [e for e in events if e.name not in names])
                     for line, events in lines]) for plane, lines in planes]


def test_nothing_to_read_is_none_and_prints_nothing(monkeypatch, capsys):
    args = {"roots": ["load.count"], "dispatch_spans": DISPATCH,
            "value": "head_ms"}
    assert pass_edges.read(args, {"profile": None}) is None
    monkeypatch.setattr(gap_by_span, "span_names", lambda: KNOWN)
    # No root span in the capture: the served cell, a program without spans.
    monkeypatch.setattr(xplane, "load",
                        lambda path: without(ONE_CHIP, "load.count"))
    assert pass_edges.read(args, {"profile": {"file": "x"}}) is None
    # A root with no operation inside it.
    monkeypatch.setattr(xplane, "load",
                        lambda path: without(ONE_CHIP, "%a", "%b", "%c"))
    assert pass_edges.read(args, {"profile": {"file": "x"}}) is None
    assert capsys.readouterr().out == ""


def test_a_program_without_the_dispatch_span_still_has_its_edges():
    out = pass_edges.reduce_planes(
        without(ONE_CHIP, "inflate.device_kernel"), ["load.count"], DISPATCH,
        KNOWN)
    assert out["host_ms"] is None and out["launch_ms"] is None
    assert out["head_ms"] == pytest.approx(34) and out["clock"] is True


@pytest.mark.parametrize("shift_ms,what", [
    (-6, "the first operation starts before its dispatch does"),
    (+11, "the last operation ends after the pass has"),
])
def test_clocks_apart_read_nothing_and_say_so(shift_ms, what, monkeypatch,
                                              capsys):
    apart = [(plane, [(line, [Event(e.name, e.start_ns + shift_ms * MS,
                                    e.duration_ns, e.stats) for e in events]
                       if plane.startswith("/device") else events)
                      for line, events in lines]) for plane, lines in ONE_CHIP]
    monkeypatch.setattr(xplane, "load", lambda path: apart)
    monkeypatch.setattr(gap_by_span, "span_names", lambda: KNOWN)
    sources = {"profile": {"file": "x"}}
    for value in ("head_ms", "tail_ms", "attributed_share"):
        assert pass_edges.read(
            {"roots": ["load.count"], "dispatch_spans": DISPATCH,
             "value": value}, sources) is None, what
    line = json.loads(capsys.readouterr().out)
    assert line["clock"] is False
    assert (line["launch_ms"] < 0) or (line["tail_ms"] < 0)


def test_a_launch_inside_the_returning_call_is_no_clock_fault():
    """A chip may start while the dispatching call is still returning."""
    early = [(plane, [(line, [Event(e.name, e.start_ns - 4.5 * MS
                                    if e.name == "%a" else e.start_ns,
                                    e.duration_ns, e.stats) for e in events])
                      for line, events in lines]) for plane, lines in ONE_CHIP]
    out = pass_edges.reduce_planes(early, ["load.count"], DISPATCH, KNOWN)
    assert out["launch_ms"] == pytest.approx(-0.5) and out["clock"] is True
