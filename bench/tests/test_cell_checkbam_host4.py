"""The four-chip check-bam cell ``wgs-short-checkbam-host4.check-bam``, as
far as the CPU can show it: the entry is the issue's, its configuration is
``wgs-short-checkbam``'s deployment on four chips (the same records, the same
guarantees), its byte count makes exactly 24 rows = six whole steps of one
row a chip, the cell rehearses through ``run.py`` with the declared metrics
(on the CPU's one device: the mesh is whatever the process sees), and the
chip's-share roofline on a snapshot worked out by hand."""

import json

import numpy as np
import pytest

from bench.tests.conftest import ROOT, config_of, generate, held_entry
from bench.tests.test_run import last_line, run_py

CELL = "wgs-short-checkbam-host4.check-bam"
CONFIG = "wgs-short-checkbam-host4"
ONE_CHIP_CELL = "wgs-short-checkbam.check-bam"
STEPS = ("check-bam steps (parallel/stream_mesh.check_bam_sharded, "
         "parallel/mesh.confusion_step)")
MESH = ("mesh steps (parallel/stream_mesh.count_reads_sharded, "
        "parallel/mesh.count_step)")
#: The ``.checkbam4`` metrics and the layer of each one's twin.
CHECKBAM4 = {
    "confusion_step_device_ms": STEPS, "check_device_ms": STEPS,
    "scatter_device_ms": STEPS, "collect_device_ms": STEPS,
    "checkbam_assemble_host_ms": STEPS, "row_inflate_ms": STEPS,
    "truth_fill_ms": STEPS, "checkbam_h2d_ms": STEPS, "mesh_stall_ms": STEPS,
    "truth_load_ms": STEPS, "lanes_per_step": STEPS,
    "confusion_step_roofline": STEPS, "chip_balance": MESH,
    "head_scan_ms": "device", "first_dispatch_ms": "device",
    "drain_ms": "device", "slowest_pass_ratio": "device",
    "device_idle_share": "device", "hbm_peak_gib": "device",
}


def spec_of(metric: str) -> dict:
    return json.loads((ROOT / "bench" / "layer_metrics"
                       / f"{metric}.json").read_text())


def test_the_entry_is_the_issues(benchmark_json):
    bm = benchmark_json
    mine = held_entry(bm, CELL, CONFIG, "check-bam", 4)
    assert mine == {f"{stem}.checkbam4" for stem in CHECKBAM4}
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for stem, layer in CHECKBAM4.items():
        m = by_name[f"{stem}.checkbam4"]
        assert m["workloads"] == [CELL] and m["moves"] == "scan_rate"
        assert m["layer"] == layer, stem
    # The second four-chip cell of seven, and the only one of its pair.
    assert sum(w["chips"] == 4 for w in bm["workloads"]) == 2
    assert [w["name"] for w in bm["workloads"]
            if w["config"] == CONFIG] == [CELL]
    entry = next(c for c in bm["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["uncompressed_bytes"]
    for word in ("docs/benchmarks.md", "check-bam", "configs[1]"):
        assert word in entry["source"]
    assert len(entry["source"]) <= 200
    assert len({c["source"] for c in bm["configs"]}) == len(bm["configs"])


@pytest.mark.parametrize("stem", ["row_inflate_ms", "truth_fill_ms"])
def test_the_one_chip_cell_reads_the_same_assembly(stem, benchmark_json):
    """The one-chip check-bam cell runs the same assembly, so the row's
    inflate and its truth's fill are read there too, by the same reader."""
    by_name = {m["name"]: m for m in benchmark_json["per_layer"]}
    one, four = by_name[f"{stem}.checkbam"], by_name[f"{stem}.checkbam4"]
    assert one["workloads"] == [ONE_CHIP_CELL]
    assert (one["moves"], one["layer"]) == ("scan_rate", STEPS)
    a, b = spec_of(one["name"]), spec_of(four["name"])
    assert (a["reader"], a["args"]) == (b["reader"], b["args"])
    assert a["args"]["histogram"] == {
        "row_inflate_ms": "mesh.row_inflate",
        "truth_fill_ms": "mesh.truth_fill"}[stem]


def test_the_configuration_is_the_one_chip_cells_on_four_chips():
    config = config_of(CONFIG)
    short, one = config_of("wgs-short"), config_of("wgs-short-checkbam")
    assert config["params"] == short["params"]  # the source's shapes
    assert config["generator"] == short["generator"] == "shortread"
    assert config["reduced"] == ["uncompressed_bytes"]
    assert config["reduced_why"]
    assert config["guarantees"] == one["guarantees"]
    assert set(config["guarantees"]) == {
        "exact", "every_position_checked", "no_demotion_off_device",
        "no_record_exceeds_halo"}
    assert config["scale"] == {"uncompressed_bytes": 600_000_000,
                               "source_uncompressed_bytes": 60_000_000_000}
    assert config["scale"] == config_of("wgs-short-host4")["scale"]
    shapes = config["shapes"]
    # The oracle's seam drops read the row's owned bytes with ``int()``.
    assert type(shapes["row_owned_bytes"]) is int
    assert shapes["row_owned_bytes"] == one["shapes"]["row_owned_bytes"]
    assert (shapes["rows_per_pass"], shapes["rows_per_chip_per_step"],
            shapes["steps_per_pass"]) == (24, 1, 6)
    assert (shapes["kernel_window_bytes"],
            shapes["truth_bytes_per_position"]) == (32 << 20, 1)
    for key, value in one["shapes"].items():
        if key not in ("rows_per_pass", "rows_per_chip_per_step",
                       "steps_per_pass", "row_owned_members", "sidecar"):
            assert shapes[key] == value, key
    assert config["rehearsal"] == one["rehearsal"]
    assert len(config["assumed"]) == len(one["assumed"]) + 1


@pytest.mark.parametrize("trace", (0, 1))
def test_the_cell_rehearses(trace, benchmark_json):
    proc = run_py(["--workload", CELL, "--seed", str(2 ** 31 + 42),
                   "--seconds", "1", "--trace", str(trace), "--rehearse"])
    line = last_line(proc)
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] >= 1 and line["device"]["platform"] == "cpu"
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in benchmark_json[group]
                if CELL in m.get("workloads", [CELL])}
    for name, row in line["metrics"].items():
        assert row["unit"] == declared[name]
    if trace:  # what the host's clock and the registry give without a chip
        assert {f"{stem}.checkbam4" for stem in (
            "confusion_step_device_ms", "checkbam_assemble_host_ms",
            "row_inflate_ms", "truth_fill_ms", "checkbam_h2d_ms",
            "mesh_stall_ms", "truth_load_ms", "head_scan_ms",
            "first_dispatch_ms", "drain_ms", "slowest_pass_ratio",
            "lanes_per_step")} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"scan_rate", "setup_s"}
    checks = [json.loads(s) for s in proc.stdout.splitlines()
              if s.startswith('{"check"')]
    assert checks and all(c["ok"] for c in checks)
    names = {c["check"] for c in checks}
    assert {"warm_up.false_positive_positions", "pass_1.positions",
            "pass_1.false_negative_positions", "warm_up.mesh.dirty_steps",
            "warm_up.checkbam.list_overflows",
            "warm_up.check.fused_demotions",
            "warm_up.check.count_escape_retries"} <= names
    assert not list((ROOT / ".smoke_data" / "bench").glob("*.records"))


@pytest.mark.parametrize("seed", (3, 2 ** 31 + 27, 987654401))
def test_the_byte_count_gives_six_whole_steps_of_four_rows(seed, tmp_path):
    """The generator cuts the file at the first record past the target, so
    a file is ``header + target + (0 .. one record)`` bytes. Over that whole
    range the engine's own planner must give 24 rows on four chips: six
    steps of one row a chip, no padding row, every row within the 32 MiB
    kernel window, seams at whole multiples of the row's owned bytes (where
    the oracle drops its records)."""
    from spark_bam_tpu.bgzf.block import Metadata
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.parallel.stream_mesh import (
        _halo_block_range, _plan_rows,
    )
    from spark_bam_tpu.tpu.checker import PAD

    index, config = generate(CONFIG, seed, tmp_path / "small.bam")
    shapes = config["shapes"]
    target = config["scale"]["uncompressed_bytes"]
    payload = shapes["bgzf_payload_bytes"]
    longest = int(np.diff(index["record_starts"]).max())
    cfg = Config()
    # One row a chip: what ``_plan``'s 192 MiB of operands a step gives on
    # four devices of 32 MiB + PAD a row.
    assert (192 << 20) // ((shapes["kernel_window_bytes"] + PAD) * 4) == (
        shapes["rows_per_chip_per_step"])
    for total in (target + index["header_end"],
                  target + index["header_end"] + 2 * longest):
        sizes = [payload] * (total // payload) + [total % payload, 0]
        metas = [Metadata(30_000 * i, 30_000, n) for i, n in enumerate(sizes)]
        groups, owned, flat, first_block, per_proc = _plan_rows(
            metas, cfg.window_size, 4, 1)
        assert len(groups) == per_proc == shapes["rows_per_pass"] == 24
        assert per_proc == 4 * shapes["steps_per_pass"] * shapes[
            "rows_per_chip_per_step"]
        assert int(owned.max()) == shapes["row_owned_bytes"]
        assert flat.tolist() == [k * shapes["row_owned_bytes"]
                                 for k in range(per_proc)]
        for g in range(len(groups)):
            b0, b1 = _halo_block_range(
                metas, groups, first_block, g, g + 1, cfg.halo_size)
            assert sum(sizes[b0:b1]) <= shapes["kernel_window_bytes"]


def _snapshot(rows: int, steps: int, step_ms: list) -> dict:
    return {
        "hists": [{"name": "mesh.step_device_ms", "count": len(step_ms),
                   "sum": sum(step_ms), "max": max(step_ms),
                   "values": step_ms}],
        "counters": [{"name": "mesh.rows", "value": rows},
                     {"name": "mesh.steps", "value": steps}],
    }


def test_the_roofline_is_one_chips_share_of_the_step():
    """Worked by hand: 24 rows in 6 steps on 4 chips is one row a chip a
    step, 2 x 32 MiB = 67,108,864 B ÷ 819 GB/s = 0.08194 ms, against a
    median step of 125 ms: 0.06555%. The same four rows a step on ONE chip
    read four times that; the one-chip cell's reader, which divides by the
    steps alone, would have read the four-chip run at four times its
    share."""
    from bench.readers import confusion_roofline, confusion_roofline_chip

    spec = spec_of("confusion_step_roofline.checkbam4")
    assert spec["reader"] == "confusion_roofline_chip"
    args = spec["args"]
    assert args == spec_of("confusion_step_roofline")["args"]
    assert confusion_roofline_chip.least_bytes(4, 4, 32 << 20) == 67_108_864
    assert confusion_roofline_chip.least_bytes(
        3, 1, 32 << 20) == confusion_roofline.least_bytes(3, 32 << 20)
    sources = {"snapshot": _snapshot(24, 6, [120.0, 125.0, 130.0]),
               "config": config_of(CONFIG), "device": {"count": 4},
               "peaks": {"hbm_bytes_per_s": 819e9}}
    share = confusion_roofline_chip.read(args, sources)
    assert share == pytest.approx(100 * 67_108_864 / 819e9 / 0.125)
    assert share == pytest.approx(0.06555, rel=1e-3)
    one_chip = confusion_roofline_chip.read(
        args, {**sources, "device": {"count": 1}})
    assert one_chip == pytest.approx(4 * share)
    assert confusion_roofline.read(args, sources) == pytest.approx(one_chip)


@pytest.mark.parametrize("lacking", ["peaks", "snapshot", "device"])
def test_the_roofline_with_nothing_to_read(lacking):
    from bench.readers import confusion_roofline_chip

    args = spec_of("confusion_step_roofline.checkbam4")["args"]
    sources = {"snapshot": _snapshot(24, 6, [125.0]),
               "config": config_of(CONFIG), "device": {"count": 4},
               "peaks": {"hbm_bytes_per_s": 819e9}}
    assert confusion_roofline_chip.read(args, sources) is not None
    empty = {"peaks": None, "snapshot": {"hists": [], "counters": []},
             "device": {}}[lacking]
    assert confusion_roofline_chip.read(
        args, {**sources, lacking: empty}) is None


def test_the_collect_scope_is_read_where_the_program_has_it():
    """``collect_device_ms.checkbam4`` reads the scope ``collect`` of
    ``jit_confusion_step``; a program without the scope (the parent's) gives
    nothing, and the metric is left out of its line."""
    from bench.readers import trace_scope
    from bench.readers.xplane import Event
    from spark_bam_tpu.obs.names import PROGRAMS, SCOPES

    args = spec_of("collect_device_ms.checkbam4")["args"]
    assert args == {"program": "confusion_step", "scopes": ["collect"]}
    assert "collect" in SCOPES and args["program"] in PROGRAMS
    ms = 1e6

    def planes(tail_scope):
        path = "jit(confusion_step)/jit(shmap_body)/"
        ops = [
            Event("%fusion.1", 1 * ms, 100 * ms, {"tf_op": path + "check/x:"}),
            Event("%all-reduce.1", 110 * ms, 2 * ms,
                  {"tf_op": path + f"{tail_scope}/psum:"}),
            Event("%all-gather.1", 113 * ms, 1 * ms,
                  {"tf_op": path + f"{tail_scope}/all_gather:"}),
        ]
        return [("/device:TPU:0", [
            ("XLA Modules", [Event("jit_confusion_step(1)", 0.0, 120 * ms,
                                   {})]),
            ("XLA Ops", ops)])]

    assert trace_scope.read_planes(planes("collect"), **args) == (
        pytest.approx(3.0))
    assert trace_scope.read_planes(planes("reduce"), **args) is None
