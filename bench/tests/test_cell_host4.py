"""The four-chip cell ``wgs-short-host4.count``, as far as the CPU can show
it: the cell rehearses through ``run.py`` (on one CPU device, so through the
one-device stream: the mesh engine is selected by TPU chips alone), its
metrics are the declared ones, and its byte count makes exactly 24 rows."""

import json

import numpy as np
import pytest

from bench.tests.conftest import config_of, generate, held_entry
from bench.tests.test_run import last_line, run_py

CELL = "wgs-short-host4.count"
CONFIG = "wgs-short-host4"
MESH_METRICS = {
    "mesh_step_device_ms", "check_device_ms.mesh", "mesh_assemble_host_ms",
    "mesh_h2d_ms", "mesh_stall_ms", "device_idle_share.mesh",
    "idle_attributed_share.mesh", "hbm_peak_gib.mesh", "count_step_roofline",
    "chip_balance",
}


def test_the_entry_is_the_issues(benchmark_json):
    bm = benchmark_json
    mine = held_entry(bm, CELL, CONFIG, "count", 4)
    assert MESH_METRICS <= mine
    for m in bm["per_layer"]:
        if m["name"] in MESH_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "scan_rate"
    config, short = config_of(CONFIG), config_of("wgs-short")
    assert config["params"] == short["params"]  # the source's shapes
    assert config["guarantees"] == short["guarantees"]
    assert config["reduced"] == ["uncompressed_bytes"]


@pytest.mark.parametrize("trace", (0, 1))
def test_the_cell_rehearses(trace, benchmark_json):
    proc = run_py(["--workload", CELL, "--seed", str(2 ** 31 + 27),
                   "--seconds", "1", "--trace", str(trace), "--rehearse"])
    line = last_line(proc)
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] >= 1 and line["device"]["platform"] == "cpu"
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in benchmark_json[group]
                if CELL in m.get("workloads", [CELL])}
    for name, row in line["metrics"].items():
        assert row["unit"] == declared[name]
    if not trace:
        assert set(line["metrics"]) == {"scan_rate", "setup_s"}
    checks = [json.loads(s) for s in proc.stdout.splitlines()
              if s.startswith('{"check"')]
    assert checks and all(c["ok"] for c in checks)


@pytest.mark.parametrize("seed", (3, 2 ** 31 + 27, 987654401))
def test_the_byte_count_gives_whole_steps_of_rows(seed, tmp_path):
    """The generator cuts the file at the first record past the target, so
    a file is ``header + target + (0 .. one record)`` bytes. Over that whole
    range the engine's own planner must give 24 rows: six steps of one row a
    chip, no padding row, every row within the 32 MiB kernel window of the
    one compiled shape."""
    from spark_bam_tpu.bgzf.block import Metadata
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.parallel.stream_mesh import (
        _halo_block_range, _plan_rows,
    )

    index, config = generate(CONFIG, seed, tmp_path / "small.bam")
    target = config["scale"]["uncompressed_bytes"]
    payload = config["shapes"]["bgzf_payload_bytes"]
    longest = int(np.diff(index["record_starts"]).max())
    cfg = Config()
    for total in (target + index["header_end"],
                  target + index["header_end"] + 2 * longest):
        sizes = [payload] * (total // payload) + [total % payload, 0]
        metas = [Metadata(30_000 * i, 30_000, n) for i, n in enumerate(sizes)]
        groups, owned, _flat, first_block, per_proc = _plan_rows(
            metas, cfg.window_size, 4, 1)
        shapes = config["shapes"]
        assert len(groups) == per_proc == shapes["rows_per_pass"] == 24
        assert per_proc == 4 * shapes["steps_per_pass"] * shapes[
            "rows_per_chip_per_step"]
        assert int(owned.max()) == 385 * payload
        for g in range(len(groups)):
            b0, b1 = _halo_block_range(
                metas, groups, first_block, g, g + 1, cfg.halo_size)
            assert sum(sizes[b0:b1]) <= config["shapes"]["kernel_window_bytes"]


def test_chip_balance_on_a_trace_worked_out_by_hand(monkeypatch):
    """Chip 0 busy 60 ms (overlapping operations merge), chip 1 busy 45 ms,
    a host plane ignored: 75%. One chip, or a chip that ran nothing, has no
    balance to report and a 0% balance."""
    from bench.readers import chip_balance
    from bench.readers.xplane import Event

    ms = 1e6

    def plane(n, *ops):
        return (f"/device:TPU:{n}", [
            ("XLA Modules", [Event("jit_prog(1)", 0.0, 100 * ms, {})]),
            ("XLA Ops", [Event(f"%op.{i}", s * ms, d * ms, {})
                         for i, (s, d) in enumerate(ops)]),
        ])

    host = ("/host:CPU", [("python3", [Event("span", 0.0, 500 * ms, {})])])
    both = [host, plane(0, (0, 40), (30, 20), (70, 10)), plane(1, (5, 45))]
    assert chip_balance.busy_by_plane(both) == [60 * ms, 45 * ms]
    sources = {"profile": {"file": "capture"}}
    loaded = {"capture": both}
    monkeypatch.setattr(chip_balance.xplane, "load", loaded.get)
    assert chip_balance.read({}, sources) == pytest.approx(75.0)
    loaded["capture"] = both[:2]
    assert chip_balance.read({}, sources) is None
    loaded["capture"] = [both[1], plane(1)]
    assert chip_balance.read({}, sources) == 0.0
    assert chip_balance.read({}, {"profile": None}) is None
