"""``BENCHMARK.json`` against its contract and against the files it names."""

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


@pytest.mark.parametrize("kernel_window,tokens", [(32 << 20, 100_663_296)])
def test_least_bytes_of_the_fused_window(kernel_window, tokens):
    from bench.readers import roofline

    # 512 rows x 65,536 symbols x 3 bytes of tokens, read once; the 32 MiB
    # window written once and read once.
    assert roofline.least_bytes(tokens, kernel_window) == 167_772_160
