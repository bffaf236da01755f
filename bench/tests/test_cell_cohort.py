"""The cell ``wgs-cohort.serve-count-2m-zipf``, as far as the CPU can show
it: the entry is the issue's, the generator writes a set of files that
differ and are ``wgs-short``'s but for what tells samples apart, the cell
rehearses through ``run.py`` with its comparisons passing and leaves no file
behind, and the counter-ratio reader on a case worked by hand."""

import json

import numpy as np
import pytest

from bench.tests.conftest import ROOT, config_of, held_entry
from bench.tests.test_run import last_line, run_py

CELL = "wgs-cohort.serve-count-2m-zipf"
CONFIG = "wgs-cohort"
TRAFFIC = "serve-count-2m-zipf"
WARM_CELL = "wgs-short.serve-count-2m"
TIER = "serve requests and file tier (serve/service.py)"
#: The file tier's metrics. Every ``.cohort`` metric moves
#: ``request_p50_ms``: the cell is not listed under ``request_p80_ms``, which
#: spread over its bound on the builder's six seeds (PERF.md, section 2).
TIER_METRICS = (
    "segment_miss_share", "segment_inflate_ms", "miss_inflate_ms_per_request",
    "segment_evictions_per_request", "flat_resident_mib",
    "worker_wait_ms",
)
#: The warm cell's ten, and the name of each one's twin here.
TWINS = {
    "tick_ms": "tick_ms", "queue_ms": "queue_ms",
    "batch_wait_ms": "batch_wait_ms", "batch_pack_ms": "batch_pack_ms",
    "tick_rows": "tick_rows", "lanes_per_tick": "lanes_per_tick",
    "serve_step_device_ms": "serve_step_device_ms",
    "check_device_ms.serve": "check_device_ms",
    "device_idle_share.serve": "device_idle_share",
    "idle_attributed_share.serve": "idle_attributed_share",
}


def spec_of(metric: str) -> dict:
    return json.loads((ROOT / "bench" / "layer_metrics"
                       / f"{metric}.json").read_text())


def test_the_entry_is_the_issues(benchmark_json):
    bm = benchmark_json
    mine = held_entry(bm, CELL, CONFIG, TRAFFIC, 1, rate="request_p50_ms")
    assert mine >= {f"{stem}.cohort" for stem in TIER_METRICS}
    assert mine >= {f"{stem}.cohort" for stem in TWINS.values()}
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for stem in TIER_METRICS:
        m = by_name[f"{stem}.cohort"]
        assert (m["workloads"], m["layer"], m["moves"]) == (
            [CELL], TIER, "request_p50_ms"), stem
    for original, stem in TWINS.items():
        a, b = by_name[original], by_name[f"{stem}.cohort"]
        assert a["workloads"] == [WARM_CELL] and b["workloads"] == [CELL]
        for key in ("unit", "better", "source", "layer"):
            assert a[key] == b[key], (stem, key)
        assert b["moves"] == "request_p50_ms"
        x, y = spec_of(original), spec_of(f"{stem}.cohort")
        assert (x["reader"], x.get("args")) == (y["reader"], y.get("args"))
    listed = {m["name"]: m.get("workloads") for m in bm["end_to_end"]}
    assert listed["request_p50_ms"][:2] == [WARM_CELL, CELL]
    assert CELL not in listed["request_p80_ms"]
    assert [w["name"] for w in bm["workloads"]
            if w["config"] == CONFIG] == [CELL]  # no second cell
    entry = next(c for c in bm["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["uncompressed_bytes", "files"]
    for word in ("docs/benchmarks.md", "cohorts of BAMs", "2 MB splits",
                 "configs[3]"):
        assert word in entry["source"]
    assert len({c["source"] for c in bm["configs"]}) == len(bm["configs"])
    assert len(json.dumps(bm)) < 64 << 10


def test_the_traffic_is_the_issues():
    traffic = json.loads((ROOT / "bench" / "traffic"
                          / f"{TRAFFIC}.json").read_text())
    assert traffic["driver"] == "serve_cohort"
    assert (traffic["clients"], traffic["range_bytes"],
            traffic["zipf_exponent"]) == (16, 2_097_152, 1.0)
    assert (traffic["timeout_s"], traffic["profile_lead_s"],
            traffic["profile_slice_s"]) == (120, 5, 10)
    warm = json.loads((ROOT / "bench" / "traffic"
                       / "serve-count-2m.json").read_text())
    assert warm["range_bytes"] == traffic["range_bytes"]
    assert traffic["who"]
    # The issue's parameters and no other: the mix has no knob of its own.
    assert set(traffic) == set(warm) | {"zipf_exponent"}


def test_the_configuration_is_a_cohort_of_wgs_short():
    from bench.generators import cohort
    from spark_bam_tpu.serve import ServeConfig
    from spark_bam_tpu.serve.service import SEGMENT_TICKS

    config, short = config_of(CONFIG), config_of("wgs-short")
    assert config["generator"] == "cohort"
    params, files = config["params"], config["params"]["files"]
    assert files == 8 == config["scale"]["files"]
    assert set(params["per_file"]) == {"contig", "run", "flowcell"}
    for key, values in params["per_file"].items():
        assert len(set(values)) == len(values) == files, key
        assert values[0] == short["params"][key]  # file 0 is wgs-short's
    for k in range(files):
        mine = cohort.file_params(params, k)
        assert set(mine) == set(short["params"])
        assert {key for key in mine if mine[key] != short["params"][key]} <= (
            set(params["per_file"]))
    scale = config["scale"]
    assert scale["file_uncompressed_bytes"] == (
        short["scale"]["uncompressed_bytes"]) == 218_103_808
    assert scale["uncompressed_bytes"] == 8 * 218_103_808 == 1_744_830_464
    assert config["reduced"] == ["uncompressed_bytes", "files"]
    assert config["reduced_why"] and len(config["source"]) <= 200
    assert config["assumed"][:len(short["assumed"])] == short["assumed"]
    # The daemon's documented defaults, and the guarantee worked from them.
    shapes, serve = config["shapes"], ServeConfig()
    assert (shapes["flat_cache_bytes"], shapes["workers"],
            shapes["rows_per_tick"], shapes["row_window_bytes"],
            shapes["row_halo_bytes"]) == (
        serve.flat_cache, serve.workers, serve.batch_rows, serve.window,
        serve.halo)
    assert shapes["row_owned_bytes"] == serve.window - serve.halo
    assert shapes["segment_rows"] == SEGMENT_TICKS * serve.batch_rows
    assert shapes["segment_bytes_at_most"] == (
        shapes["segment_rows"] * shapes["row_owned_bytes"]
        + shapes["row_halo_bytes"] + 2 * shapes["bgzf_payload_bytes"])
    assert scale["uncompressed_bytes"] > 6 * shapes["flat_cache_bytes"]
    assert scale["file_uncompressed_bytes"] < shapes["flat_cache_bytes"]
    guarantees = config["guarantees"]
    assert set(guarantees) == set(short["guarantees"]) | {
        "resident_flat_bytes_at_most"}
    assert {k: guarantees[k] for k in short["guarantees"]} == (
        short["guarantees"])
    # A 2 MiB split at ratio 4.97 is 12 or 13 rows: at most three segments
    # of eight.
    assert guarantees["resident_flat_bytes_at_most"] == (
        shapes["flat_cache_bytes"]
        + shapes["workers"] * 3 * shapes["segment_bytes_at_most"])
    assert config["rehearsal"]["uncompressed_bytes"] == files * (
        short["rehearsal"]["uncompressed_bytes"])


@pytest.fixture(scope="module")
def two_sets(tmp_path_factory):
    """The rehearsal's set twice from one seed, and once from another."""
    from bench.generators import cohort

    config = config_of(CONFIG)
    size = config["rehearsal"]["uncompressed_bytes"]
    root = tmp_path_factory.mktemp("cohort")
    return [
        (cohort.generate(config["params"], seed, size, root / name), config)
        for seed, name in ((2 ** 31 + 5, "a.bam"), (2 ** 31 + 5, "b.bam"),
                           (77, "c.bam"))]


def test_the_generator_is_deterministic_and_its_files_differ(two_sets):
    from pathlib import Path

    from bench.generators import cohort

    (a, config), (b, _), (c, _) = two_sets
    files = config["params"]["files"]
    assert len(a["files"]) == files
    blobs = [Path(f["path"]).read_bytes() for f in a["files"]]
    assert blobs == [Path(f["path"]).read_bytes() for f in b["files"]]
    assert len(set(blobs)) == files  # every sample its own
    assert blobs[0] != Path(c["files"][0]["path"]).read_bytes()
    assert [Path(f["path"]).name for f in a["files"]] == (
        ["a.bam"] + [f"a.{k}.bam" for k in range(1, files)])
    assert a["files"][3]["path"] == str(cohort.sibling(a["path"], 3))
    # The line a run prints: the set's sums and means.
    each = config["rehearsal"]["uncompressed_bytes"] // files
    assert a["uncompressed_bytes"] == sum(
        f["uncompressed_bytes"] for f in a["files"]) >= files * each
    assert a["compressed_bytes"] == sum(
        len(blob) for blob in blobs)
    assert a["ratio"] == a["uncompressed_bytes"] / a["compressed_bytes"]
    low = min(f["record_bytes_mean"] for f in a["files"])
    high = max(f["record_bytes_mean"] for f in a["files"])
    assert low <= a["record_bytes_mean"] <= high
    # File k is on contig k, under its own flowcell.
    for k, (f, blob) in enumerate(zip(a["files"], blobs)):
        assert each <= f["uncompressed_bytes"] - f["header_end"] < each + 600
        assert np.all(np.diff(f["record_starts"]) > 0)
        from spark_bam_tpu.bgzf.flat import flatten_file

        flat = flatten_file(f["path"]).data
        first = int(f["record_starts"][0])
        assert int(flat[first + 4: first + 8].view("<i4")[0]) == k
        assert config["params"]["per_file"]["flowcell"][k].encode() in (
            flat[first: first + 400].tobytes())


@pytest.mark.parametrize("trace", (0, 1))
def test_the_cell_rehearses(trace, benchmark_json):
    proc = run_py(["--workload", CELL, "--seed", str(2 ** 31 + 43),
                   "--seconds", "2", "--trace", str(trace), "--rehearse"])
    line = last_line(proc)
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] >= 16 and line["device"]["platform"] == "cpu"
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in benchmark_json[group]
                if CELL in m.get("workloads", [CELL])}
    for name, row in line["metrics"].items():
        assert row["unit"] == declared[name]
    if trace:  # what the host's clock and the registry give without a chip
        # (the rehearsal's set lies under flat_cache and the warm-up left
        # it resident: nothing is inflated or evicted in the window, so the
        # readers of the miss path read nothing and leave their names out)
        cold = {f"{stem}.cohort" for stem in (
            "segment_inflate_ms", "miss_inflate_ms_per_request",
            "flat_resident_mib", "segment_evictions_per_request")}
        assert {f"{stem}.cohort" for stem in (
            *TIER_METRICS, "tick_ms", "queue_ms", "batch_wait_ms",
            "batch_pack_ms", "tick_rows", "lanes_per_tick")} - cold <= set(
                line["metrics"])
        assert not cold & set(line["metrics"])
        assert line["metrics"]["segment_miss_share.cohort"]["value"] == 0.0
    else:
        assert set(line["metrics"]) == {"request_p50_ms", "setup_s"}
    checks = [json.loads(s) for s in proc.stdout.splitlines()
              if s.startswith('{"check"')]
    assert checks and all(c["ok"] for c in checks)
    names = {c["check"] for c in checks}
    assert {"warm_up.count", "warm_up.ranged_count.file_0",
            "warm_up.ranged_count.file_7", "requests.wrong_or_failed",
            "window.resident_flat_bytes_over", "compiles_in_window",
            "warm_up.check.fused_demotions",
            "warm_up.check.count_escape_retries"} <= names
    over = next(c for c in checks
                if c["check"] == "window.resident_flat_bytes_over")
    assert 0 < over["peak"] <= over["at_most"] == 316_797_952
    window = next(json.loads(s) for s in proc.stdout.splitlines()
                  if s.startswith('{"phase": "window"'))
    assert sorted(window["detail"]["files_by_rank"]) == list(range(8))
    assert window["detail"]["files_open"] == 8
    assert not list((ROOT / ".smoke_data" / "bench").glob("wgs-cohort-*"))


def test_a_service_that_keeps_no_account_is_not_correct(monkeypatch):
    """The parent's daemon has no account of its resident bytes: the
    comparison fails there, it is not skipped."""
    from bench import run
    from spark_bam_tpu.serve import service

    real = service.SplitService.stats

    def no_account(self):
        out = real(self)
        del out["flat_resident_peak_bytes"]
        return out

    monkeypatch.setattr(service.SplitService, "stats", no_account)
    out = run.run_cell(CELL, 2 ** 31 + 99, 1.0, False, rehearse=True)
    assert out["correct"] is False and out["failed"] == 0
    row = out["compared"]["window.resident_flat_bytes_over"]
    assert row == {"n": 1, "ok": False, "got": None, "limit": 0}
    assert all(r["ok"] for what, r in out["compared"].items()
               if what != "window.resident_flat_bytes_over")


def _snapshot(**counters) -> dict:
    return {"hists": [], "counters": [
        {"name": name.replace("__", "."), "value": value}
        for name, value in counters.items()]}


def test_the_counter_ratio_worked_by_hand():
    from bench.readers import counter_ratio

    share = spec_of("segment_miss_share.cohort")
    assert share["reader"] == "counter_ratio"
    assert share["args"] == {
        "counter": "serve.segment_misses",
        "over": ["serve.segment_hits", "serve.segment_misses"],
        "percent": True}
    snapshot = _snapshot(serve__segment_hits=30, serve__segment_misses=90,
                         serve__segment_waits=5, serve__requests=60,
                         serve__segment_evictions=84)
    # 90 of 120 lookups that found or made their segment: 75%.
    assert counter_ratio.read(share["args"], {"snapshot": snapshot}) == 75.0
    evictions = spec_of("segment_evictions_per_request.cohort")
    assert counter_ratio.read(
        evictions["args"], {"snapshot": snapshot}) == pytest.approx(1.4)
    # Nothing counted (the parent, or an empty window): nothing to read.
    for spec in (share, evictions):
        for seen in (_snapshot(), _snapshot(serve__requests=9)):
            assert counter_ratio.read(spec["args"], {"snapshot": seen}) is None
    # All hits is a share of 0, not nothing.
    assert counter_ratio.read(share["args"], {"snapshot": _snapshot(
        serve__segment_hits=4)}) == 0.0


def test_the_new_spans_and_counters_are_registered():
    from spark_bam_tpu.obs.names import NAMES

    for stem in (*TIER_METRICS,):
        args = spec_of(f"{stem}.cohort")["args"]
        for name in (args.get("histogram"), args.get("counter"),
                     args.get("per_counter"), *args.get("over", ())):
            assert name is None or name in NAMES, (stem, name)
