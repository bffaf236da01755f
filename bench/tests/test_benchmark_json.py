"""``BENCHMARK.json`` against its contract and against the files it names."""

import importlib
import json
import re

import pytest

from bench.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_keys_names_and_units(benchmark_json):
    bm = benchmark_json
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"] and 1 <= bm["run_seconds"] <= 51
    names = []
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        names.append(c["name"])
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in bm["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bm["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert m["source"] in SOURCES and "\n" not in m["layer"]
    metrics = bm["end_to_end"] + bm["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in (names, [w["name"] for w in bm["workloads"]],
                  [m["name"] for m in metrics]):
        assert len(group) == len(set(group))
    pairs = [(w["config"], w["traffic"]) for w in bm["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in bm["end_to_end"])
    assert len(json.dumps(bm)) < 64 << 10


def test_every_moves_is_reported_by_each_of_its_cells(benchmark_json):
    bm = benchmark_json
    cells = [w["name"] for w in bm["workloads"]]
    reported = {
        m["name"]: set(m.get("workloads", cells)) for m in bm["end_to_end"]
    }
    for m in bm["per_layer"]:
        assert m["moves"] in reported, m
        assert m["moves"] != "setup_s"
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in reported[m["moves"]], (m["name"], cell)
    for cell in cells:  # setup_s, one more end-to-end, one per-layer
        assert sum(cell in v for v in reported.values()) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bm["per_layer"])


def test_files_behind_the_names(benchmark_json):
    bm = benchmark_json
    bench = ROOT / "bench"
    for c in bm["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        for key in ("source", "generator", "params", "scale", "shapes",
                    "reduced", "assumed", "guarantees", "rehearsal"):
            assert key in cfg, (c["name"], key)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert (bench / "generators" / f"{cfg['generator']}.py").exists()
    for w in bm["workloads"]:
        traffic = json.loads(
            (bench / "traffic" / f"{w['traffic']}.json").read_text())
        assert "who" in traffic
        assert (bench / "drivers" / f"{traffic['driver']}.py").exists()
    layers = {}
    for m in bm["per_layer"]:
        spec = json.loads(
            (bench / "layer_metrics" / f"{m['name']}.json").read_text())
        for key in ("layer", "unit", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert spec["cells"] == m["workloads"]
        assert (bench / "readers" / f"{spec['reader']}.py").exists()
        layers.setdefault(m["layer"], []).append(m["name"])
    perf = (ROOT / "PERF.md").read_text()
    for layer in layers:
        assert layer in perf, f"PERF.md's layers lack {layer!r}"


def test_an_unknown_device_kind_is_an_error():
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "TPU v5 lite" in peaks and "cpu" not in peaks
    for row in peaks.values():
        assert row["source"] and row["hbm_bytes_per_s"] > 0


def test_least_bytes_of_the_fused_window():
    from bench.readers import roofline

    # The 32 MiB window written once (the put) and read once (the check),
    # whatever implements the work: no term for an operand of today's.
    assert roofline.least_bytes(32 << 20) == 67_108_864


def test_a_longread_twin_reads_what_its_original_reads(benchmark_json):
    """The long-read cells report ``scan_rate.longread`` under a bound of
    their own (PR 41), so a per-layer metric they share with the short-read
    cells is two entries, one a rate: same reader, same arguments."""
    specs = ROOT / "bench" / "layer_metrics"
    by_name = {m["name"]: m for m in benchmark_json["per_layer"]}
    twins = [n for n in by_name if n.endswith(".longread")]
    assert len(twins) == 17
    for name in twins:
        stem = name[:-len(".longread")]
        original = stem if stem in by_name else f"{stem}.scan"
        a = json.loads((specs / f"{original}.json").read_text())
        b = json.loads((specs / f"{name}.json").read_text())
        for key in ("layer", "unit", "reader", "args"):
            assert a[key] == b[key], (name, key)
        assert (a["moves"], b["moves"]) == ("scan_rate", "scan_rate.longread")
        assert not set(a["cells"]) & set(b["cells"])
        assert set(b["cells"]) <= {"longread-hifi.count",
                                   "longread-ultra.count"}


@pytest.mark.parametrize("metric", ("count_window_roofline",
                                    "count_step_roofline"))
def test_a_count_roofline_reads_its_histograms_median_alone(metric):
    """Worked by hand: 2 x 32 MiB / 819 GB/s = 0.08194 ms against a median
    of 20 ms is 0.4097%. No counter stands guard: a histogram nothing
    observed into, or a device kind without peaks, reads nothing."""
    from bench.readers import roofline

    args = json.loads((ROOT / "bench" / "layer_metrics"
                       / f"{metric}.json").read_text())["args"]
    assert set(args) == {"time_histogram", "stat", "bound"}
    hist = {"name": args["time_histogram"], "count": 3, "sum": 60.0,
            "max": 30.0, "values": [10.0, 30.0, 20.0]}
    sources = {"snapshot": {"hists": [hist], "counters": []},
               "peaks": {"hbm_bytes_per_s": 819e9},
               "config": {"shapes": {"kernel_window_bytes": 32 << 20}}}
    assert roofline.read(args, sources) == pytest.approx(0.40970, rel=1e-4)
    assert roofline.read(args, {**sources, "peaks": None}) is None
    assert roofline.read(
        args, {**sources, "snapshot": {"hists": [], "counters": []}}) is None


def test_every_per_layer_metric_has_a_reader_and_a_program_that_exists(
        benchmark_json):
    """A metric that names a program the program under test no longer has
    reads nothing for ever (twelve did, PRs 29-40): the name is held to the
    program's own catalogue."""
    from spark_bam_tpu.obs.names import PROGRAMS

    bench = ROOT / "bench"
    listed = {m["name"] for m in benchmark_json["per_layer"]}
    assert listed == {p.name[:-len(".json")]
                      for p in (bench / "layer_metrics").glob("*.json")}
    for name in listed:
        spec = json.loads(
            (bench / "layer_metrics" / f"{name}.json").read_text())
        assert spec["name"] == name
        reader = importlib.import_module(f"bench.readers.{spec['reader']}")
        assert callable(reader.read), name
        program = spec.get("args", {}).get("program")
        assert program is None or program in PROGRAMS, (name, program)
