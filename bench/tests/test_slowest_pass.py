"""The slowest pass over the median one, on snapshots worked out by hand."""

import json

import pytest

from bench.readers import slowest_pass


def hist(name, values):
    return {"name": name, "labels": {"unit": "ms"}, "count": len(values),
            "sum": float(sum(values)), "min": min(values), "max": max(values),
            "values": list(values)}


# Five passes of 150 ms but the fourth, 1,650: three windows a pass, 40 ms
# each, and in the slow pass one wait for the host inflate of 1,510 ms
# inside its window.
SNAPSHOT = {
    "counters": [], "gauges": [],
    "hists": [
        hist("load.count", [150, 150, 150, 1650, 150]),
        hist("check.window", [40] * 14 + [1540]),
        hist("inflate.stall_ms", [10] * 14 + [1510]),
        hist("bgzf.read", [9, 10, 10, 10, 11]),
    ],
    "slowest_passes": [{
        "root": "load.count", "ms": 1650.0, "t": 1790000000.5, "at_s": 12.25,
        "trace": "abc", "spans": {
            "bgzf.read": [1, 10.0, 10.0],
            "check.window": [3, 1620.0, 1540.0],
            "inflate.stall_ms": [3, 1530.0, 1510.0],
            "load.drain": [1, 0.5, 0.5],
        }}],
}
ROOTS = ["load.count", "load.check_bam"]


def test_the_ratio_and_the_line_that_names_the_phase(capsys):
    value = slowest_pass.read({"roots": ROOTS}, {"snapshot": SNAPSHOT})
    assert value == pytest.approx(11.0)  # 1,650 over a median of 150
    line = json.loads(capsys.readouterr().out)
    assert line["phase"] == "slowest_pass" and line["root"] == "load.count"
    assert line["ms"] == 1650.0 and line["median_ms"] == 150.0
    assert line["at_s"] == 12.25
    # [span, count, summed ms, its summed ms in a median pass]: the median
    # of its histogram times its observations a pass; most excess first.
    assert line["rows"] == [
        ["check.window", 3, 1620.0, pytest.approx(120.0)],
        ["inflate.stall_ms", 3, 1530.0, pytest.approx(30.0)],
        ["load.drain", 1, 0.5, 0.0],  # no histogram of it: nothing usual
        ["bgzf.read", 1, 10.0, pytest.approx(10.0)],
    ]


def test_an_even_window_reads_one(capsys):
    even = dict(SNAPSHOT, hists=[hist("load.check_bam", [1430, 1440, 1485])],
                slowest_passes=[{"root": "load.check_bam", "ms": 1485.0,
                                 "t": 1.0, "at_s": 0.1, "trace": "x",
                                 "spans": {}}])
    assert slowest_pass.read({"roots": ROOTS}, {"snapshot": even}) == (
        pytest.approx(1485 / 1440))
    assert json.loads(capsys.readouterr().out)["rows"] == []


@pytest.mark.parametrize("snapshot", [
    {k: v for k, v in SNAPSHOT.items() if k != "slowest_passes"},  # parent
    dict(SNAPSHOT, slowest_passes=[]),
    dict(SNAPSHOT, slowest_passes=[dict(SNAPSHOT["slowest_passes"][0],
                                        root="serve.request")]),
    dict(SNAPSHOT, hists=[]),  # a record and no histogram of its root
])
def test_no_record_reads_nothing_and_prints_nothing(snapshot, capsys):
    assert slowest_pass.read({"roots": ROOTS}, {"snapshot": snapshot}) is None
    assert capsys.readouterr().out == ""
