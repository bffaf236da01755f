"""A run end to end at rehearsal size: the last line, the ranged oracle
against serve's answer, a broken timed path, and a cell added by files
alone."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.tests.conftest import ROOT

CELLS = ("wgs-short.count", "longread-hifi.count", "wgs-short.serve-count-2m")


def run_py(args: list, cwd=ROOT, root=ROOT) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT),
           "BENCH_RUN": "ignored"}
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), *args], cwd=cwd,
        env=env, capture_output=True, text=True, timeout=600)


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("cell", CELLS)
def test_rehearse_prints_the_contracts_last_line(cell, trace,
                                                 benchmark_json):
    proc = run_py(["--workload", cell, "--seed", str(2 ** 31 + 7),
                   "--seconds", "1", "--trace", str(trace), "--rehearse"])
    line = last_line(proc)
    assert set(line) - {"breakdown"} == {"correct", "attempted", "failed",
                                         "metrics", "device", "compared"}
    assert list(line)[-1] == "compared"
    assert line["correct"] is False  # a rehearsal never measures
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    group = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in benchmark_json[group]
               if cell in m.get("workloads", [cell])}
    assert line["metrics"], "a line reports at least one metric"
    for name, row in line["metrics"].items():
        assert row["unit"] == allowed[name] and row["value"] > 0
    if not trace:
        assert set(line["metrics"]) == set(allowed)
    # Every comparison is printed beside its limit, and all held.
    checks = [json.loads(s) for s in proc.stdout.splitlines()
              if s.startswith('{"check"')]
    assert checks and all(c["ok"] and "limit" in c for c in checks)
    # ... and once a kind in the line's last key and on stderr's last lines.
    compared = line["compared"]
    assert sum(row["n"] for row in compared.values()) == len(checks)
    assert all(row["ok"] and row["got"] == row["limit"]
               for row in compared.values())
    said = [json.loads(s) for s in proc.stderr.strip().splitlines()
            [-len(compared):]]
    assert [s["compared"] for s in said] == list(compared)


def test_without_a_chip_it_refuses():
    proc = run_py(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert proc.returncode == 3 and "not measuring" in proc.stderr
    assert not any(s.startswith('{"correct"')
                   for s in proc.stdout.splitlines())


def test_in_a_bare_directory_it_fails(tmp_path):
    """Only BENCHMARK.json and bench/: no program, no result, not exit 0."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env={**env, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout


# --- in process: the harness's look for a chip skipped, nothing else -------

@pytest.fixture
def run_cell():
    from bench import run

    def call(cell: str, seconds: float = 0.5, trace: bool = False) -> dict:
        return run.run_cell(cell, 2 ** 31 + 99, seconds, trace,
                            rehearse=True)

    return call


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(cell, run_cell):
    out = run_cell(cell)
    assert out["correct"] is True and out["failed"] == 0


def test_a_pass_that_miscounts_is_not_correct(run_cell, monkeypatch):
    """An answer altered where it is produced: the timed pass returns one
    record more from its second call on (the warm-up's answer is sound)."""
    from bench.drivers import scan

    real, calls = scan.count_pass, []

    def off_by_one(path):
        calls.append(path)
        return real(path) + (len(calls) > 1)

    monkeypatch.setattr(scan, "count_pass", off_by_one)
    out = run_cell("wgs-short.count")
    assert out["correct"] is False and out["failed"] == out["attempted"] > 0
    # The line's last key keeps the first pass that failed beside its limit.
    row = out["compared"]["pass.count"]
    assert row["ok"] is False and row["got"] == row["limit"] + 1
    assert row["n"] == out["attempted"]
    assert out["compared"]["warm_up.count"]["ok"] is True


def test_a_served_row_dropped_is_not_correct(run_cell, monkeypatch):
    """A part of the batch left out: the service scans one row fewer for
    every ranged request after the warm-up's two."""
    from spark_bam_tpu.serve import service

    real, calls = service.SplitService._scan_rows, []

    def short(self, fs, lo, hi, deadline_ts):
        calls.append(lo)
        tasks = real(self, fs, lo, hi, deadline_ts)
        return tasks[:-1] if len(calls) > 2 else tasks

    monkeypatch.setattr(service.SplitService, "_scan_rows", short)
    out = run_cell("wgs-short.serve-count-2m", seconds=1.5)
    assert out["correct"] is False and out["failed"] > 0


def test_a_demotion_is_not_correct(run_cell, monkeypatch):
    """A path that left the device is a different result: the counter alone
    fails the run, though every count is right."""
    from bench.drivers import scan
    from spark_bam_tpu import obs

    real = scan.count_pass

    def demoted(path):
        obs.count("check.fused_demotions")
        return real(path)

    monkeypatch.setattr(scan, "count_pass", demoted)
    out = run_cell("longread-hifi.count")
    assert out["correct"] is False and out["failed"] == 0


def test_the_ranged_oracle_is_what_serve_answers(tmp_path):
    """``oracle.ranged_count`` (the index alone) against the service's
    ``count`` with start and end, over ranges that cut members anywhere."""
    import numpy as np

    from bench import oracle
    from bench.tests.conftest import generate
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.parallel.mesh import local_mesh
    from spark_bam_tpu.serve import ServeClient, ServerThread, SplitService

    path = tmp_path / "short.bam"
    index, _ = generate("wgs-short", 2 ** 31 + 5, path)
    size = int(index["compressed_bytes"])
    rng = np.random.default_rng(5)
    ranges = [(0, size), (0, 1), (size - 10, size + 999), (5, 5)]
    for _ in range(12):
        a, b = sorted(rng.integers(0, size, 2).tolist())
        ranges.append((a, b))
    ranges += [(int(index["block_starts"][3]), int(index["block_starts"][7]))]
    svc = SplitService(Config(), mesh=local_mesh())
    try:
        with ServerThread(svc) as srv, ServeClient(srv.address) as client:
            for start, end in ranges:
                got = client.request("count", path=str(path), start=start,
                                     end=end)["count"]
                assert got == oracle.ranged_count(index, start, end), (
                    start, end)
            assert client.request("count", path=str(path))["count"] == (
                oracle.whole_file_count(index))
    finally:
        svc.close()
    assert oracle.ranged_count(index, 0, size) == len(index["record_starts"])


# --- adding a cell is adding files and one entry ---------------------------

def tree_hashes(root) -> dict:
    return {
        str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts
    }


def test_a_cell_is_added_by_files_and_one_entry_each(tmp_path):
    """A throw-away configuration, generator, traffic mix, driver, per-layer
    metric and reader, in a copy: no file that was there is edited."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = tree_hashes(bench)

    config = json.loads((bench / "configs" / "wgs-short.json").read_text())
    config.update(name="throwaway", generator="throwaway_gen",
                  source="a test")
    (bench / "configs" / "throwaway.json").write_text(json.dumps(config))
    (bench / "generators" / "throwaway_gen.py").write_text(
        "from bench.generators.shortread import generate\n")
    (bench / "traffic" / "twice.json").write_text(json.dumps({
        "name": "twice", "driver": "twice", "who": "a test",
        "profiled_pass": 0}))
    (bench / "drivers" / "twice.py").write_text(
        "from bench.drivers import scan\n\n\n"
        "class Driver(scan.Driver):\n"
        "    def window(self, seconds):\n"
        "        out = super().window(seconds)\n"
        "        out['metrics']['passes_per_s'] = (\n"
        "            out['attempted'] / out['detail']['pass_ends_s'][-1])\n"
        "        return out\n")
    (bench / "layer_metrics" / "windows_seen.json").write_text(json.dumps({
        "name": "windows_seen", "layer": "streaming count", "unit": "windows",
        "moves": "passes_per_s", "cells": ["throwaway.twice"],
        "reader": "count_of", "args": {"counter": "check.windows"}}))
    (bench / "readers" / "count_of.py").write_text(
        "from bench.readers import counter_sum\n\n\n"
        "def read(args, sources):\n"
        "    return counter_sum(sources['snapshot'], args['counter']) or None\n")

    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "throwaway", "source": "a test",
                          "file": "bench/configs/throwaway.json",
                          "reduced": ["uncompressed_bytes"], "why": "a test"})
    bm["workloads"].append({"name": "throwaway.twice", "config": "throwaway",
                            "traffic": "twice", "chips": 1, "why": "a test"})
    bm["end_to_end"].append({"name": "passes_per_s", "unit": "1/s",
                             "better": "higher", "bound": 0.05,
                             "source": "host_clock",
                             "workloads": ["throwaway.twice"]})
    bm["per_layer"].append({"name": "windows_seen", "unit": "windows",
                            "better": "higher", "source": "program_counter",
                            "layer": "streaming count",
                            "moves": "passes_per_s",
                            "workloads": ["throwaway.twice"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    for trace, want in ((0, {"passes_per_s", "setup_s"}),
                        (1, {"windows_seen"})):
        line = last_line(run_py(
            ["--workload", "throwaway.twice", "--seed", "11", "--seconds",
             "0.2", "--trace", str(trace), "--rehearse"],
            cwd=tmp_path, root=tmp_path))
        assert set(line["metrics"]) == want, line
    # ... and the cells that were there still run from the copy.
    line = last_line(run_py(
        ["--workload", CELLS[0], "--seed", "11", "--seconds", "0.2",
         "--trace", "0", "--rehearse"], cwd=tmp_path, root=tmp_path))
    assert set(line["metrics"]) == {"scan_rate", "setup_s"}
    after = tree_hashes(bench)
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 6
