"""The cell ``longread-ultra.count``, as far as the CPU can show it: the
generator is a function of the seed and its index is what the program's own
codec finds in the file (the ``CG`` record included), the whale exceeds the
halo and stays under ``max_read_size`` on every seed, its start sits where
the stream's geometry puts the first window's owned end, and the cell
rehearses through ``run.py`` with the declared metrics."""

import json
import struct

import numpy as np
import pytest

from bench.tests.conftest import config_of, generate, held_entry
from bench.tests.test_run import last_line, run_py

CELL = "longread-ultra.count"
CONFIG = "longread-ultra"
ULTRA_METRICS = {
    "escape_candidates", "escape_resolve_ms", "escape_retries",
    "window_device_ms.ultra", "inflate_stall_ms.ultra",
    "device_idle_share.ultra", "hbm_peak_gib.ultra",
}
#: The long-read cells' rate, under a bound of its own (PR 41).
RATE = "scan_rate.longread"
SEEDS = (3, 2 ** 31 + 27, 987654401)

#: The configuration's shapes at a size the CPU writes in a blink: reads of
#: kilobytes with a CIGAR operation a base, so that a whale of 70-80 kb
#: (about 0.4 MB) still has more than 65,535 of them and needs ``CG``;
#: drawn before flat offset 400,000.
SMALL = {
    "read_length_n50": 3000, "read_length_sigma": 0.8,
    "read_length_min": 500, "read_length_max": 8000, "op_every": 1,
    "whale_length_min": 70_000, "whale_length_max": 80_000,
    "whale_start_end": 400_000, "whale_start_span": 16_384,
}


def small(seed: int, path, size: int = 1_500_000):
    from bench.generators import ultralong

    params = {**config_of(CONFIG)["params"], **SMALL}
    return ultralong.generate(params, seed, size, path), params


def test_the_entry_is_the_issues(benchmark_json):
    bm = benchmark_json
    mine = held_entry(bm, CELL, CONFIG, "count", 1, rate=RATE)
    assert ULTRA_METRICS <= mine
    for m in bm["per_layer"]:
        if m["name"] in ULTRA_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == RATE
            assert m["layer"] in ("streaming count (tpu/stream_check)",
                                  "device")
    config, hifi = config_of(CONFIG), config_of("longread-hifi")
    assert config["scale"]["uncompressed_bytes"] == (
        hifi["scale"]["uncompressed_bytes"])
    assert config["reduced"] == ["uncompressed_bytes"]
    # Every guarantee of the other count cells but the one that is false.
    assert set(hifi["guarantees"]) - set(config["guarantees"]) == {
        "no_record_exceeds_halo"}
    assert len(config["source"]) < 200


def test_the_whale_sits_where_the_streams_geometry_puts_the_owned_end():
    """``whale_start_end`` is the first window's owned end under
    ``Config()``: the first group of whole 0xFF00-byte members within
    ``window_size``, less the halo. A later change to that geometry fails
    here, before ``escape_candidates`` reads 0 on the chip."""
    from spark_bam_tpu.bgzf.block import Metadata
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.tpu.inflate import window_plan

    config, cfg = config_of(CONFIG), Config()
    payload = config["shapes"]["bgzf_payload_bytes"]
    total = config["scale"]["uncompressed_bytes"]
    sizes = [payload] * (total // payload) + [total % payload, 0]
    metas = [Metadata(40_000 * i, 40_000, n) for i, n in enumerate(sizes)]
    groups = window_plan(metas, cfg.window_size)
    first = sum(m.uncompressed_size for m in groups[0])
    assert first - cfg.halo_size == config["params"]["whale_start_end"] == (
        config["shapes"]["first_window_owned_end"])
    assert len(groups) == config["shapes"]["windows_per_pass"]
    params = config["params"]
    from bench.generators.ultralong import record_bytes

    lo, hi = (record_bytes(params[k], params["op_every"],
                           params["read_group"])
              for k in ("whale_length_min", "whale_length_max"))
    assert cfg.halo_size < lo < hi < cfg.max_read_size
    # Owned by the first window, ending past its buffer, on every draw.
    earliest = params["whale_start_end"] - params["whale_start_span"]
    assert earliest + lo > first
    assert config["shapes"]["halo_bytes"] == cfg.halo_size
    assert config["shapes"]["max_read_size_bytes"] == cfg.max_read_size


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_bytes_and_a_whale_on_every_seed(seed, tmp_path):
    ia, params = small(seed, tmp_path / "a.bam")
    ib, _ = small(seed, tmp_path / "b.bam")
    ic, _ = small(seed + 1, tmp_path / "c.bam")
    assert (tmp_path / "a.bam").read_bytes() == (
        tmp_path / "b.bam").read_bytes()
    assert (tmp_path / "a.bam").read_bytes() != (
        tmp_path / "c.bam").read_bytes()
    assert np.array_equal(ia["record_starts"], ib["record_starts"])
    (whale,) = ia["whale_starts"]
    end = params["whale_start_end"]
    assert end - params["whale_start_span"] <= whale < end
    assert whale in ia["record_starts"]
    # A file that does not reach the whale's start holds none: the
    # rehearsal size of the configuration itself.
    index, config = generate(CONFIG, seed, tmp_path / "r.bam")
    assert index["whale_starts"] == []
    sizes = np.diff(np.append(index["record_starts"],
                              index["uncompressed_bytes"]))
    assert sizes.max() < config["shapes"]["halo_bytes"]


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_the_index_is_what_the_programs_codec_finds(seed, tmp_path):
    """A re-parse by ``bam/``: every record where the index says, names
    UUIDs, positions sorted, CIGARs that consume the read; the whale's
    placeholder CIGAR and its real one in ``CG``."""
    from spark_bam_tpu.bam.record import BamRecord
    from spark_bam_tpu.bgzf.flat import flatten_file

    path = tmp_path / "f.bam"
    index, params = small(seed, path)
    flat = bytes(np.asarray(flatten_file(path).data))
    assert len(flat) == index["uncompressed_bytes"]
    at, found, records = index["header_end"], [], []
    while at < len(flat):
        record, used = BamRecord.decode(flat, at)
        found.append(at)
        records.append(record)
        at += used
    assert at == len(flat)
    assert found == index["record_starts"].tolist()
    positions = [r.pos for r in records]
    assert positions == sorted(positions)
    (whale_at,) = index["whale_starts"]
    for start, r in zip(found, records):
        assert len(r.read_name) == 36 and r.read_name.count("-") == 4
        assert len(r.seq) == len(r.qual) and 1 <= min(r.qual) <= max(
            r.qual) <= 50
        consumed = sum(n for n, op in r.cigar if op in (0, 1, 4))
        assert consumed == len(r.seq)
        if start != whale_at:
            assert b"CGBI" not in r.tags and len(r.cigar) <= 65535
            assert {op for _n, op in r.cigar} <= {0, 1, 2, 4}
            continue
        # The whale: <l_seq>S<span>N, and the real CIGAR in CG:B,I.
        assert params["whale_length_min"] <= len(r.seq) <= params[
            "whale_length_max"]
        assert [op for _n, op in r.cigar] == [4, 3]
        tag = r.tags.index(b"CGBI")
        (count,) = struct.unpack_from("<I", r.tags, tag + 4)
        ops = np.frombuffer(r.tags, "<u4", count, tag + 8)
        assert count > 65535
        assert int((ops[np.isin(ops & 0xF, (0, 1, 4))] >> 4).sum()) == len(
            r.seq)
        assert int((ops[np.isin(ops & 0xF, (0, 2))] >> 4).sum()) == (
            r.cigar[1][0])


def test_the_full_size_file_has_the_issues_shapes(tmp_path):
    """The cell's own 400 MiB, once: the whale's record is 4.6-6.0 MB, above
    the halo and under ``max_read_size``, starts in the last 256 KiB before
    the first window's owned end, and the other records keep under the
    halo; about 1.77 bytes a base."""
    from spark_bam_tpu.core.config import Config

    index, config = generate(
        CONFIG, 2 ** 31 + 27, tmp_path / "full.bam",
        config_of(CONFIG)["scale"]["uncompressed_bytes"])
    cfg, params = Config(), config["params"]
    starts = index["record_starts"]
    sizes = np.diff(np.append(starts, index["uncompressed_bytes"]))
    (whale,) = index["whale_starts"]
    end = params["whale_start_end"]
    assert end - params["whale_start_span"] <= whale < end
    size = int(sizes[np.searchsorted(starts, whale)])
    assert 4_600_000 <= size <= 6_000_000
    assert cfg.halo_size < size < cfg.max_read_size
    assert whale + size > 385 * config["shapes"]["bgzf_payload_bytes"]
    rest = np.delete(sizes, np.searchsorted(starts, whale))
    assert rest.max() < cfg.halo_size // 2
    assert 100_000 < rest.mean() < 160_000
    assert 1.5 < index["ratio"] < 1.7
    # The last read is cut to the size: a rate over the file's bytes does
    # not move with the seed's last draw (a read is up to 1.8 MB).
    target = config["scale"]["uncompressed_bytes"]
    assert 0 <= index["header_end"] + target - index[
        "uncompressed_bytes"] < 8


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
    if trace:
        # A rehearsal file holds no whale: the path, and zeros.
        assert line["metrics"]["escape_retries"]["value"] == 0
        assert line["metrics"]["escape_candidates"]["value"] == 0
    else:
        assert set(line["metrics"]) == {RATE, "setup_s"}
    checks = [json.loads(s) for s in proc.stdout.splitlines()
              if s.startswith('{"check"')]
    assert checks and all(c["ok"] for c in checks)
    # No escape-retry check: the configuration does not state that no
    # record exceeds the halo.
    assert not any("count_escape_retries" in c["check"] for c in checks)


def test_a_small_file_with_a_whale_counts_exactly_through_small_windows(
        tmp_path):
    """The rehearsal holds no whale, so the answers with one are checked
    here: the stream at a 64 KiB halo over the small file, against the
    index, with the candidates resolved and no pass started over."""
    from spark_bam_tpu import obs
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.tpu.stream_check import StreamChecker

    index, _ = small(2 ** 31 + 27, tmp_path / "w.bam")
    obs.shutdown()
    obs.configure()
    try:
        got = StreamChecker(
            tmp_path / "w.bam", Config(), window_uncompressed=4 * 0xFF00,
            halo=64 << 10).count_reads()
        counters = {c["name"]: c["value"]
                    for c in obs.registry().snapshot()["counters"]}
    finally:
        obs.shutdown()
    assert got == len(index["record_starts"])
    assert not counters.get("check.count_escape_retries")
    assert (counters["check.escape_candidates"]
            == counters["check.escape_resolved"] >= 1)


def test_the_per_pass_reader_on_a_snapshot_worked_out_by_hand():
    from bench.readers import obs_per_pass

    def hist(name, values):
        return {"name": name, "count": len(values), "sum": sum(values),
                "max": max(values), "values": values}

    snapshot = {
        "counters": [{"name": "check.escape_candidates", "value": 22},
                     {"name": "check.windows", "value": 6}],
        "hists": [hist("load.count", [1500.0, 1600.0]),
                  hist("check.escape_resolve", [10.0, 14.0, 2.0])],
    }
    src = {"snapshot": snapshot}
    assert obs_per_pass.read(
        {"counter": "check.escape_candidates"}, src) == 11.0
    assert obs_per_pass.read({"span": "check.escape_resolve"}, src) == 13.0
    # A registered name that nothing emitted reads 0: a reading.
    assert obs_per_pass.read(
        {"counter": "check.count_escape_retries"}, src) == 0.0
    # A name the program does not have, or no pass: nothing to read.
    assert obs_per_pass.read({"counter": "check.no_such_name"}, src) is None
    assert obs_per_pass.read(
        {"counter": "check.escape_candidates"},
        {"snapshot": {"counters": [], "hists": []}}) is None
