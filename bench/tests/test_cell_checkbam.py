"""The cell ``wgs-short-checkbam.check-bam``, as far as the CPU can show it:
the entry is the issue's, the cell rehearses through ``run.py`` with the
declared metrics, its byte count makes exactly 18 rows, the oracle's copy
against an index made by hand, the new roofline's least bytes, and the
control: a pass scored against another truth than the oracle's is not
``correct``."""

import json

import numpy as np
import pytest

from bench import oracle_checkbam
from bench.tests.conftest import ROOT, config_of, generate, held_entry
from bench.tests.test_run import last_line, run_py

CELL = "wgs-short-checkbam.check-bam"
CONFIG = "wgs-short-checkbam"
LAYER = ("check-bam steps (parallel/stream_mesh.check_bam_sharded, "
         "parallel/mesh.confusion_step)")
CHECKBAM_METRICS = {
    "confusion_step_device_ms": LAYER, "check_device_ms.checkbam": LAYER,
    "scatter_device_ms": LAYER, "confusion_reduce_device_ms": LAYER,
    "checkbam_assemble_host_ms": LAYER, "checkbam_h2d_ms": LAYER,
    "mesh_stall_ms.checkbam": LAYER, "truth_load_ms": LAYER,
    "confusion_step_roofline": LAYER,
    "device_idle_share.checkbam": "device", "hbm_peak_gib.checkbam": "device",
}


def traffic() -> dict:
    return json.loads((ROOT / "bench" / "traffic" / "check-bam.json")
                      .read_text())


def test_the_entry_is_the_issues(benchmark_json):
    bm = benchmark_json
    mine = held_entry(bm, CELL, CONFIG, "check-bam", 1)
    assert set(CHECKBAM_METRICS) <= mine
    for m in bm["per_layer"]:
        if m["name"] in CHECKBAM_METRICS:
            assert m["layer"] == CHECKBAM_METRICS[m["name"]]
            assert m["workloads"] == [CELL] and m["moves"] == "scan_rate"
    config, short = config_of(CONFIG), config_of("wgs-short")
    assert config["params"] == short["params"]  # the source's shapes
    assert config["generator"] == short["generator"] == "shortread"
    assert config["reduced"] == ["uncompressed_bytes"]
    assert set(config["guarantees"]) == set(short["guarantees"])
    assert len(config["source"]) <= 200
    mix = traffic()
    assert (mix["truth_dropped"], mix["truth_added"], mix["seam_drops"],
            mix["callers"], mix["profiled_pass"]) == (16, 16, 5, 1, 0)


@pytest.mark.parametrize("trace", (0, 1))
def test_the_cell_rehearses(trace, benchmark_json):
    proc = run_py(["--workload", CELL, "--seed", str(2 ** 31 + 33),
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
        assert {"confusion_step_device_ms", "checkbam_assemble_host_ms",
                "checkbam_h2d_ms", "mesh_stall_ms.checkbam",
                "truth_load_ms"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"scan_rate", "setup_s"}
    checks = [json.loads(s) for s in proc.stdout.splitlines()
              if s.startswith('{"check"')]
    assert checks and all(c["ok"] for c in checks)
    # Both lists, element for element, and the two host re-derivations at 0.
    names = {c["check"] for c in checks}
    assert {"warm_up.false_positive_positions", "pass_1.positions",
            "pass_1.false_negative_positions", "warm_up.mesh.dirty_steps",
            "warm_up.checkbam.list_overflows",
            "warm_up.check.fused_demotions"} <= names
    assert not list((ROOT / ".smoke_data" / "bench").glob("*.records"))


@pytest.mark.parametrize("seed", (3, 2 ** 31 + 27, 987654401))
def test_the_byte_count_gives_whole_steps_of_rows(seed, tmp_path):
    """The generator cuts the file at the first record past the target, so
    a file is ``header + target + (0 .. one record)`` bytes. Over that whole
    range the engine's own planner must give 18 rows on one chip: six steps
    of three rows, no padding row, every row within the 32 MiB kernel
    window, and the seams where the oracle drops its records."""
    from types import SimpleNamespace

    from spark_bam_tpu.bgzf.block import Metadata
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.parallel.stream_mesh import (
        _halo_block_range, _plan_rows, _rows_fitting_device,
    )

    index, config = generate(CONFIG, seed, tmp_path / "small.bam")
    shapes = config["shapes"]
    target = config["scale"]["uncompressed_bytes"]
    payload = shapes["bgzf_payload_bytes"]
    longest = int(np.diff(index["record_starts"]).max())
    cfg = Config()
    v5e = SimpleNamespace(
        memory_stats=lambda: {"bytes_limit": int(15.75 * 2 ** 30)})
    assert _rows_fitting_device(
        v5e, shapes["kernel_window_bytes"]) == shapes["rows_per_chip_per_step"]
    for total in (target + index["header_end"],
                  target + index["header_end"] + 2 * longest):
        sizes = [payload] * (total // payload) + [total % payload, 0]
        metas = [Metadata(30_000 * i, 30_000, n) for i, n in enumerate(sizes)]
        groups, owned, flat, first_block, per_proc = _plan_rows(
            metas, cfg.window_size, 1, 1)
        assert len(groups) == per_proc == shapes["rows_per_pass"] == 18
        assert per_proc == (shapes["steps_per_pass"]
                            * shapes["rows_per_chip_per_step"])
        assert int(owned.max()) == shapes["row_owned_bytes"]
        assert flat.tolist() == [k * shapes["row_owned_bytes"]
                                 for k in range(per_proc)]
        for g in range(len(groups)):
            b0, b1 = _halo_block_range(
                metas, groups, first_block, g, g + 1, cfg.halo_size)
            assert sum(sizes[b0:b1]) <= shapes["kernel_window_bytes"]


def hand_made_index() -> dict:
    """Three members of 100 bytes, a 10-byte header, eight records."""
    return {
        "record_starts": np.array([10, 50, 90, 130, 170, 210, 250, 290]),
        "block_starts": np.array([0, 40, 95]),
        "block_flat": np.array([0, 100, 200]),
        "header_end": 10, "uncompressed_bytes": 300,
    }


def test_the_oracles_copy_against_an_index_made_by_hand():
    index = hand_made_index()
    dropped, added = np.array([90, 210]), np.array([11, 299])
    assert oracle_checkbam.expected(index, dropped, added) == {
        "true_positives": 6, "false_positives": 2, "false_negatives": 2,
        "true_negatives": 290, "positions": 300,
        "false_positive_positions": [90, 210],
        "false_negative_positions": [11, 299],
    }
    truth = oracle_checkbam.truth(index, dropped, added)
    assert truth.tolist() == [10, 11, 50, 130, 170, 250, 290, 299]
    assert oracle_checkbam.sidecar_text(index, truth) == (
        "0,10\n0,11\n0,50\n40,30\n40,70\n95,50\n95,90\n95,99\n")


@pytest.mark.parametrize("seed", (3, 2 ** 31 + 27))
def test_the_wrong_truth_is_a_function_of_the_seed(seed, tmp_path):
    """16 dropped, 16 added, every seam's first record among the dropped,
    no added position a record or in the header; the same seed gives the
    same sets, another seed others."""
    index, config = generate(CONFIG, seed, tmp_path / "small.bam")
    mix, row = traffic(), 400_000  # seams inside the rehearsal's 3 MB
    args = (mix["truth_dropped"], mix["truth_added"], mix["seam_drops"], row)
    dropped, added = oracle_checkbam.perturb(index, seed, *args)
    again = oracle_checkbam.perturb(index, seed, *args)
    other = oracle_checkbam.perturb(index, seed + 1, *args)
    assert np.array_equal(dropped, again[0]) and np.array_equal(
        added, again[1])
    assert not np.array_equal(added, other[1])
    records = index["record_starts"]
    assert len(np.unique(dropped)) == 16 and len(np.unique(added)) == 16
    assert np.isin(dropped, records).all()
    assert not np.isin(added, records).any()
    assert added.min() >= index["header_end"]
    assert added.max() < index["uncompressed_bytes"]
    for k in range(1, 6):
        first = records[np.searchsorted(records, k * row)]
        assert first in dropped
    # At the cell's own row size a rehearsal file has no seam: all 16 drawn.
    far, _ = oracle_checkbam.perturb(
        index, seed, 16, 16, 5, config["shapes"]["row_owned_bytes"])
    assert len(np.unique(far)) == 16


@pytest.mark.parametrize("rows,window,least", [
    (3, 32 << 20, 201_326_592), (1, 1 << 20, 2_097_152)])
def test_least_bytes_of_the_confusion_step(rows, window, least):
    """Each row's kernel window read once, and a byte of truth a position
    beside it, read once: three 32 MiB rows are 192 MiB."""
    from bench.readers import confusion_roofline

    assert confusion_roofline.least_bytes(rows, window) == least


def test_the_roofline_reads_rows_a_step_and_the_steps_median():
    from bench.readers import confusion_roofline

    args = json.loads((ROOT / "bench" / "layer_metrics"
                       / "confusion_step_roofline.json").read_text())["args"]
    snapshot = {
        "hists": [{"name": "mesh.step_device_ms", "count": 3, "sum": 18300.0,
                   "max": 6300.0, "values": [6000.0, 6300.0, 6000.0]}],
        "counters": [{"name": "mesh.rows", "value": 9},
                     {"name": "mesh.steps", "value": 3}],
    }
    sources = {"snapshot": snapshot, "config": config_of(CONFIG),
               "peaks": {"hbm_bytes_per_s": 819e9}}
    share = confusion_roofline.read(args, sources)
    assert share == pytest.approx(100 * 201_326_592 / 819e9 / 6.0)
    assert confusion_roofline.read(args, {**sources, "peaks": None}) is None
    empty = {"hists": [], "counters": []}
    assert confusion_roofline.read(
        args, {**sources, "snapshot": empty}) is None


def _scatter_capture():
    """Two executions of ``jit_confusion_step`` as the chip's compiler leaves
    the verdict scatter (``tests/test_chip_compile.py``): the index
    arithmetic keeps its path, the sort, the flat scatter and the row copy
    have none; the deep flags' scatter (s32) has none either and is not the
    verdict's; the walk's last fusion makes int8 lanes and HAS a name."""
    from bench.readers.xplane import Event

    ms = 1e6
    path = "jit(confusion_step)/vmap(jit(check_window))/check/"

    def op(name, t, scale, start, dur, tf_op=None):
        return Event(name, (t + start * scale) * ms, dur * scale * ms,
                     {"tf_op": tf_op} if tf_op else {})

    def step(t, scale):
        return [
            op("%fusion.356 = s8[3,1048576]{1,0:T(4,128)(4,1)} fusion("
               "pred[3,1048576]{1,0} %copy-done.53)", t, scale, 0, 40,
               path + "chain_walk/closed_call/select_n:"),
            op("%fusion.19 = s32[3,1048576,2]{1,0,2:T(4,128)} fusion("
               "s32[3,1048576]{1,0} %p)", t, scale, 50, 1,
               path + "scatter/scatter:"),
            op("%sort.1 = (s32[3145728]{0:T(1024)S(1)}, s8[3145728]"
               "{0:T(1024)(128)(4,1)S(1)}) sort(s32[3145728]{0} %cc.350, "
               "s8[3145728]{0} %reshape.941), dimensions={0}",
               t, scale, 60, 4),
            op("%fusion.110 = s8[100663299]{0:T(1024)(128)(4,1)} fusion("
               "s32[3145728]{0:T(1024)S(1)} %gte.56, s8[3145728]{0} %gte.57)",
               t, scale, 70, 18),
            op("%while.34 = (u32[]{:T(128)}, s8[100663299]{0:T(1024)(128)"
               "(4,1)}, s8[1,3,33554433]{2,1,0:T(4,128)(4,1)}, u32[]) while("
               "(u32[], s8[100663299]) %tuple.294), condition=%wide.cond.5",
               t, scale, 100, 7),
            op("%dynamic-slice.20 = s8[33554433]{0:T(1024)(128)(4,1)S(1)} "
               "dynamic-slice(s8[100663299]{0} %gte.657, u32[] %add.1258)",
               t, scale, 101, 1),
            op("%dynamic-update-slice.17 = s8[1,3,33554433]{2,1,0:T(4,128)"
               "(4,1)} dynamic-update-slice(s8[1,3,33554433]{2,1,0} %gte.650, "
               "s8[1,1,33554433]{2,1,0} %reshape.2147)", t, scale, 103, 3),
            op("%fusion.109 = s32[100663299]{0:T(1024)} fusion(s32[3145728]"
               "{0:T(1024)S(1)} %gte.54, s32[3145728]{0} %gte.55)",
               t, scale, 120, 16),
        ]

    return [
        ("/host:CPU", []),
        ("/device:TPU:0", [
            ("XLA Modules", [
                Event("jit_confusion_step(1)", 0.0, 200 * ms, {}),
                Event("jit_confusion_step(1)", 1000 * ms, 400 * ms, {})]),
            ("XLA Ops", step(0, 1) + step(1000, 2)),
        ]),
    ]


def test_the_scatter_is_read_with_the_operations_that_lost_their_name():
    """``scatter_device_ms``: what is under ``check/scatter`` by name (1 ms)
    and the nameless operations that make an int8 array (sort 4, flat
    scatter 18, the row copy's ``while`` 7 with its body inside it): 30 ms
    an execution, 60 in the slower one, the median of the two. By name alone
    it read 1. The nameless s32 scatter (the deep flags') and the named
    int8 fusion of the walk are not the verdict scatter's."""
    from bench.readers import trace_orphans, trace_scope

    args = json.loads((ROOT / "bench" / "layer_metrics"
                       / "scatter_device_ms.json").read_text())["args"]
    assert args == {"program": "confusion_step", "scopes": ["scatter"],
                    "orphan_results": ["s8"]}
    planes = _scatter_capture()
    got = trace_orphans.read_planes(planes, **args)
    assert got == pytest.approx((30 + 60) / 2)
    assert trace_scope.read_planes(
        planes, "confusion_step", ["scatter"]) == pytest.approx(1.5)
    # A scatter that keeps its name is read all the same, once.
    named = [(plane, [(line, [
        e._replace(stats={"tf_op": "jit(confusion_step)/check/scatter/x:"})
        if e.name.startswith(("%sort.1", "%fusion.110", "%while.34",
                              "%dynamic-")) else e for e in events])
        for line, events in lines]) for plane, lines in planes]
    assert trace_orphans.read_planes(named, **args) == pytest.approx(got)
    assert trace_scope.read_planes(
        named, "confusion_step", ["scatter"]) == pytest.approx(got)


@pytest.mark.parametrize("hlo,types", [
    ("%sort.1 = (s32[8]{0:T(1024)S(1)}, s8[8]{0:T(1024)(128)(4,1)}) "
     "sort(s32[8]{0} %a, s8[8]{0} %b), dimensions={0}", {"s32", "s8"}),
    ("%fusion.109 = s32[9]{0:T(1024)} fusion(s32[3]{0} %a, s8[3]{0} %b)",
     {"s32"}),
    ("%copy.5", set()), ("jit_confusion_step(1)", set())],
    ids=["tuple", "operands-do-not-count", "no-hlo", "a-module"])
def test_an_operations_result_types(hlo, types):
    from bench.readers import trace_orphans

    assert trace_orphans.result_types(hlo) == types


def test_the_orphans_reader_with_nothing_to_read():
    from bench.readers import trace_orphans

    planes = _scatter_capture()
    assert trace_orphans.read_planes(
        planes, "count_step", ["scatter"], ["s8"]) is None
    assert trace_orphans.read_planes(
        planes, "confusion_step", ["assemble"], ["f64"]) is None
    assert trace_orphans.read_planes(
        planes[:1], "confusion_step", ["scatter"], ["s8"]) is None
    assert trace_orphans.read(
        {"program": "confusion_step", "orphan_results": ["s8"]},
        {"profile": None}) is None


def test_a_pass_scored_against_another_truth_is_not_correct(monkeypatch):
    """The control for this cell: the sidecar is written from a truth that
    lacks ONE of the oracle's added positions. Every pass then reports 15
    false negatives where the oracle says 16, and the run is not correct."""
    from bench import run

    sound = run.run_cell(CELL, 2 ** 31 + 99, 0.5, False, rehearse=True)
    assert sound["correct"] is True and sound["failed"] == 0

    real = oracle_checkbam.truth
    monkeypatch.setattr(
        oracle_checkbam, "truth",
        lambda index, dropped, added: real(index, dropped, added[1:]))
    out = run.run_cell(CELL, 2 ** 31 + 99, 0.5, False, rehearse=True)
    assert out["correct"] is False and out["failed"] == out["attempted"] >= 1


def test_a_dead_registry_fails_the_counters_check_where_one_is_expected(
        monkeypatch, capsys):
    """The two host re-derivations are held at 0 from counters. Where the
    harness keeps a registry (every warm-up, a traced window) a registry
    that is not live fails the run: the check does not drop out."""
    from bench import run
    from spark_bam_tpu import obs

    monkeypatch.setattr(obs, "enabled", lambda: False)
    out = run.run_cell(CELL, 2 ** 31 + 98, 0.5, False, rehearse=True)
    assert out["correct"] is False
    failed = [json.loads(line)["check"]
              for line in capsys.readouterr().out.splitlines()
              if '"ok": false' in line]
    assert failed == ["warm_up.registry_live"]
