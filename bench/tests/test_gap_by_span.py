"""Idle time put down to host spans, on a synthetic trace worked out by
hand."""

import json

import pytest

from bench.readers import gap_by_span, xplane
from bench.readers.xplane import Event

MS = 1e6  # nanoseconds
KNOWN = frozenset({"serve.tick", "serve.step", "serve.batch_wait",
                   "serve.request"})


def ev(name, start, end):
    return Event(name, start * MS, (end - start) * MS, {})


# Device busy 0-10, 30-40, 70-80 ms: idle 10-30 and 40-70.
# The batcher's thread: serve.tick 0-42 holding serve.step 5-20, then
# serve.batch_wait 45-60. So 10-20 is the step's, 20-30 and 40-42 the
# tick's, 42-45 and 60-70 nobody's, 45-60 the wait's.
PLANES = [
    ("/device:TPU:0", [
        ("XLA Modules", [ev("jit_serve_step(1)", 0, 80)]),
        ("XLA Ops", [ev("%a", 0, 10), ev("%b", 30, 38), ev("%c", 36, 40),
                     ev("%d", 70, 80)]),
    ]),
    ("/host:CPU", [
        ("python3", [ev("serve.request", 0, 100)]),  # a client's thread
        ("python3", [
            ev("$batcher.py:1 _loop", 0, 100),        # the python tracer's
            ev("serve.tick", 0, 42), ev("serve.step", 5, 20),
            ev("PjitFunction(serve_step)", 6, 7),    # the runtime's
            ev("serve.batch_wait", 45, 60),
        ]),
    ]),
]


def test_a_gap_goes_to_the_innermost_span_and_is_split_where_it_crosses():
    out = gap_by_span.reduce_planes(PLANES, "serve.tick", "serve.tick", KNOWN)
    assert out["phase"] == "idle_by_span"
    assert out["idle_s"] == pytest.approx(0.050)
    rows = dict(out["rows"])
    assert rows == {
        "serve.batch_wait": pytest.approx(0.015),
        "serve.tick": pytest.approx(0.012),
        "serve.step": pytest.approx(0.010),
    }
    assert [r[0] for r in out["rows"]] == [
        "serve.batch_wait", "serve.tick", "serve.step"]  # most first
    assert out["unattributed_s"] == pytest.approx(0.013)
    # The other thread's span (0-100 ms) took nothing.
    assert "serve.request" not in rows


def test_the_clock_check():
    clock = gap_by_span.reduce_planes(
        PLANES, "serve.tick", "serve.tick", KNOWN)["clock"]
    # One tick, 0-42 ms: busy 0-10 and 30-40 inside it, all of it.
    assert clock["spans"] == 1
    assert clock["busy_inside_share"] == pytest.approx(1.0)
    assert clock["lead_ms"] == pytest.approx(0.0)
    assert clock["tail_ms"] == pytest.approx(2.0)
    # Clocks 15 ms apart: half of that busy time falls outside the span.
    late = [(name, [(ln, [Event(e.name, e.start_ns + 15 * MS, e.duration_ns,
                                e.stats) for e in events] if
                     name.startswith("/device") else list(events))
                    for ln, events in lines]) for name, lines in PLANES]
    # Two ticks now, so that there is busy time between them to lose.
    dict(dict(late)["/host:CPU"][1:])["python3"].append(
        ev("serve.tick", 60, 100))
    off = gap_by_span.reduce_planes(late, "serve.tick", "serve.tick",
                                    KNOWN)["clock"]
    assert off["busy_inside_share"] < 0.9


def test_segments_of_nested_spans():
    segs = gap_by_span.segments([("a", 0, 10), ("b", 2, 4), ("c", 3, 4),
                                 ("d", 20, 30)])
    assert segs == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 10, "a"),
                    (20, 30, "d")]


def test_read_prints_the_table_before_the_value(monkeypatch, capsys):
    monkeypatch.setattr(xplane, "load", lambda path: PLANES)
    monkeypatch.setattr(gap_by_span, "span_names", lambda: KNOWN)
    value = gap_by_span.read(
        {"thread_span": "serve.tick", "clock_span": "serve.tick"},
        {"profile": {"file": "somewhere"}})
    assert value == pytest.approx(100.0 * 37 / 50)
    line = json.loads(capsys.readouterr().out)
    assert line["phase"] == "idle_by_span" and line["rows"][0][0] == (
        "serve.batch_wait")


def test_no_thread_with_the_span_reads_nothing(monkeypatch, capsys):
    monkeypatch.setattr(xplane, "load", lambda path: PLANES)
    args = {"thread_span": "check.window", "clock_span": "load.count"}
    assert gap_by_span.read(args, {"profile": {"file": "x"}}) is None
    assert gap_by_span.read(args, {"profile": None}) is None
    assert capsys.readouterr().out == ""


def test_the_programs_catalogue_names_the_spans():
    names = gap_by_span.span_names()
    assert {"serve.tick", "serve.batch_wait", "check.window", "check.pace",
            "load.count"} <= names
