"""The cell ``wgs-short-load.load-chr20``, as far as the CPU can show it: the
entry is the issue's, the generator writes a coordinate-sorted file over 25
contigs with each contig's share of the records, duplicates marked by the
pair, and an index that says what its bytes say; the oracle on a case worked
by hand; the cell rehearses through ``run.py`` with its comparisons passing
and leaves no file behind; a filter that drops one flag bit fails them."""

import gzip
import json
import zlib

import numpy as np
import pytest

from bench import bamgen, oracle_load
from bench.generators import shortread, shortread_genome
from bench.tests.conftest import ROOT, config_of, generate, held_entry
from bench.tests.test_run import last_line, run_py

CELL = "wgs-short-load.load-chr20"
CONFIG = "wgs-short-load"
TRAFFIC = "load-chr20"
PROGRAM = "fused load program (tpu/checker.load_window)"
STREAM = "streaming load (tpu/stream_check.read_batches)"
#: The issue's per-layer metrics by layer, less the four that the
#: benchmark's limit of 128 per-layer metrics left no room for
#: (``survivors_per_pass.load``, ``drain_ms.load``, ``pass_head_ms.load``,
#: ``hbm_peak_gib.load``: PERF.md, section 7).
METRICS = {
    PROGRAM: {"window_program_device_ms.load", "check_device_ms.load",
              "parse_device_ms.load", "filter_device_ms.load",
              "load_window_roofline", "lanes_per_pass.load",
              "records_parsed_per_pass.load", "rows_out_per_pass.load",
              "d2h_bytes_per_pass.load"},
    STREAM: {"inflate_stall_ms.load", "window_device_ms.load",
             "batch_host_ms.load"},
    "device": {"device_idle_share.load", "first_dispatch_ms.load",
               "slowest_pass_ratio.load"},
}


def spec_of(metric: str) -> dict:
    return json.loads((ROOT / "bench" / "layer_metrics"
                       / f"{metric}.json").read_text())


def test_the_entry_is_the_issues(benchmark_json):
    bm = benchmark_json
    mine = held_entry(bm, CELL, CONFIG, TRAFFIC, 1)
    assert mine >= set().union(*METRICS.values())
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for layer, names in METRICS.items():
        for name in names:
            m = by_name[name]
            assert (m["workloads"], m["layer"], m["moves"]) == (
                [CELL], layer, "scan_rate"), name
    assert len(bm["per_layer"]) <= 128
    rate = next(m for m in bm["end_to_end"] if m["name"] == "scan_rate")
    assert CELL in rate["workloads"] and rate["bound"] == 0.03
    assert [w["name"] for w in bm["workloads"]
            if w["config"] == CONFIG] == [CELL]  # no second cell
    entry = next(c for c in bm["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["uncompressed_bytes"]
    for word in ("CanLoadBam.scala:173-243", ":109-133", "configs[2]",
                 "chr20"):
        assert word in entry["source"]
    assert len(entry["source"]) <= 200
    assert len({c["source"] for c in bm["configs"]}) == len(bm["configs"])
    # The scopes and the program the trace readers look for are the
    # program's own, and so is every span and counter.
    from spark_bam_tpu.obs.names import NAMES, PROGRAMS, SCOPES

    for name in set().union(*METRICS.values()):
        args = spec_of(name).get("args", {})
        assert args.get("program", "load_window") in PROGRAMS
        assert set(args.get("scopes", ())) <= SCOPES
        for key in ("histogram", "time_histogram", "counter",
                    "per_counter"):
            assert args.get(key) is None or args[key] in NAMES, (name, key)
        assert set(args.get("over", ())) | set(args.get("roots", ())) <= NAMES


def test_the_traffic_and_the_configuration_are_the_issues():
    traffic = json.loads((ROOT / "bench" / "traffic"
                          / f"{TRAFFIC}.json").read_text())
    assert (traffic["driver"], traffic["loci"], traffic["flags_required"],
            traffic["flags_forbidden"], traffic["profiled_pass"]) == (
        "load", "chr20", 0, 1796, 0)
    assert traffic["who"] and 1796 == 4 | 256 | 512 | 1024
    config, short = config_of(CONFIG), config_of("wgs-short")
    assert config["generator"] == "shortread_genome"
    params = config["params"]
    # wgs-short's records but for what places them on the genome.
    assert {k: v for k, v in params.items()
            if k not in ("origins", "duplicate_share")} == {
        k: v for k, v in short["params"].items()
        if k not in ("contig", "origin")}
    assert len(params["origins"]) == len(bamgen.GRCH38) == 25
    assert params["duplicate_share"] == 0.06
    assert config["scale"] == short["scale"]
    assert config["scale"]["uncompressed_bytes"] == 218_103_808
    shapes = config["shapes"]
    for key in ("bgzf_payload_bytes", "deflate_level", "window_bytes",
                "halo_bytes", "kernel_window_bytes", "windows_per_pass",
                "record_bytes"):
        assert shapes[key] == short["shapes"][key], key
    assert config["reduced"] == ["uncompressed_bytes"]
    assert config["assumed"][:6] == short["assumed"][:6]
    guarantees = config["guarantees"]
    assert set(guarantees) == set(short["guarantees"]) | {
        "no_cigar_over_scan_cap", "readback_sized_by_rows"}
    for key in ("every_position_checked", "no_demotion_off_device",
                "no_record_exceeds_halo", "no_cigar_over_scan_cap",
                "readback_sized_by_rows"):
        assert guarantees[key] is True
    from spark_bam_tpu.tpu.parser import CIGAR_SCAN_CAP, ROW_WORDS

    assert (shapes["cigar_scan_cap"], shapes["row_words"]) == (
        CIGAR_SCAN_CAP, ROW_WORDS)
    # A head is the rows rounded up to a power of two, 256 at the least, of
    # row_words int32 a column, beside 28 bytes of a window's integers.
    assert shapes["readback_bytes_a_row"] == 2 * 4 * ROW_WORDS
    assert shapes["readback_bytes_a_window"] == 28 + 4 * ROW_WORDS * 256
    # chr20 as the oracle sees it is the traffic's loci.
    assert oracle_load.interval_of(traffic["loci"]) == (19, 0, 64_444_167)


@pytest.fixture(scope="module")
def written(tmp_path_factory):
    """The rehearsal's file twice from one seed and once from another, and
    one at eight times the size (every contig but chrM above the least
    slice), with the bytes ``gzip`` inflates them to."""
    root = tmp_path_factory.mktemp("genome")
    out = {}
    for name, seed, size in (("a", 2 ** 31 + 5, None), ("b", 2 ** 31 + 5, None),
                             ("c", 77, None), ("big", 2 ** 31 + 9, 24 << 20)):
        index, config = generate(CONFIG, seed, root / f"{name}.bam", size)
        out[name] = (index, gzip.decompress((root / f"{name}.bam").read_bytes()))
    return out, config


def test_the_generator_is_deterministic(written):
    files, _ = written
    assert files["a"][1] == files["b"][1] != files["c"][1]
    for key in ("record_starts", "crc", "ref_span", "contig_records"):
        np.testing.assert_array_equal(files["a"][0][key], files["b"][0][key])


@pytest.mark.parametrize("name", ("a", "big"))
def test_the_file_is_a_sorted_genome_with_each_contigs_share(written, name):
    files, config = written
    index, flat = files[name]
    fixed, starts = index["fixed"], index["record_starts"]
    n = len(starts)
    assert n == int(index["contig_records"].sum())
    assert flat[:index["header_end"]] == bamgen.bam_header(
        bamgen.GRCH38, tuple(f"{config['params']['flowcell']}.{k + 1}"
                             for k in range(4)), "ILLUMINA")
    # Coordinate-sorted: contigs in header order, every one of them there,
    # positions ascending within each (a clipped read sorts by its aligned
    # start, which is its ``pos``).
    ref = fixed["ref_id"].astype(np.int64)
    assert np.all(np.diff(ref) >= 0) and set(ref) == set(range(25))
    assert np.all((np.diff(fixed["pos"].astype(np.int64)) >= 0)
                  | (np.diff(ref) > 0))
    np.testing.assert_array_equal(np.bincount(ref), index["contig_records"])
    for k, (_, length) in enumerate(bamgen.GRCH38):
        on = fixed[ref == k]
        assert on["pos"].min() >= config["params"]["origins"][k]
        assert int(on["pos"].max()) + 151 <= length
    # A contig's bytes are its share of the bases, to within a record.
    ends = np.append(starts[1:], len(flat))
    size = np.bincount(ref, weights=(ends - starts))
    target = len(flat) - index["header_end"]
    wanted = shortread_genome.shares(
        (24 << 20) if name == "big" else config["rehearsal"]
        ["uncompressed_bytes"])
    assert np.all(size >= wanted) and np.all(size - wanted < 600)
    assert abs(target - wanted.sum()) < 25 * 600
    # chr20 is one run, 2.087% of the records.
    on20 = np.flatnonzero(ref == 19)
    assert np.all(np.diff(on20) == 1)
    assert abs(len(on20) / n - 64_444_167 / 3_088_286_401) < 0.001


def test_duplicates_are_marked_by_the_pair(written):
    files, config = written
    index, flat = files["big"]
    fixed, starts = index["fixed"], index["record_starts"]
    dup = (fixed["flag"] & shortread_genome.DUPLICATE) != 0
    assert abs(dup.mean() - config["params"]["duplicate_share"]) < 0.01
    names = [flat[s + 36: s + 36 + ln] for s, ln in
             zip(starts.tolist(), fixed["l_read_name"].tolist())]
    by_name: dict = {}
    for name, d in zip(names, dup.tolist()):
        by_name.setdefault(name, []).append(d)
    assert max(len(v) for v in by_name.values()) == 2
    assert sum(len(v) == 2 for v in by_name.values()) > 0.9 * len(by_name)
    assert all(len(set(v)) == 1 for v in by_name.values())  # both or neither
    # Nothing else of the flags moved: every read is paired, none is
    # secondary, QC-failed or supplementary.
    assert np.all(fixed["flag"] & 1) and not np.any(fixed["flag"] & 0xB00)


def test_the_index_says_what_the_bytes_say(written):
    """Every record's fixed fields, span and CRC as the index has them
    against the file's own bytes, walked by ``block_size``."""
    import struct

    files, _ = written
    index, flat = files["a"]
    at = index["header_end"]
    for i, start in enumerate(index["record_starts"].tolist()):
        assert at == start
        (block_size,) = struct.unpack_from("<i", flat, at)
        record = flat[at: at + 4 + block_size]
        assert index["fixed"][i].tobytes() == record[:36]
        assert index["crc"][i] == zlib.crc32(record)
        fixed = np.frombuffer(record[:36], dtype=shortread.FIXED)[0]
        ops = np.frombuffer(
            record, dtype="<u4", count=int(fixed["n_cigar"]),
            offset=36 + int(fixed["l_read_name"]))
        span = sum(int(op >> 4) for op in ops if int(op & 0xF) in (0, 2, 3, 7, 8))
        assert index["ref_span"][i] == span
        at += 4 + block_size
    assert at == len(flat) == index["uncompressed_bytes"]
    assert index["record_bytes_mean"] == pytest.approx(
        (len(flat) - index["header_end"]) / len(index["record_starts"]))


def hand_index(rows: list) -> dict:
    """An index of hand-made records: ``(ref_id, pos, span, flag)`` each."""
    fixed = np.zeros(len(rows), dtype=shortread.FIXED)
    for i, (ref, pos, _span, flag) in enumerate(rows):
        fixed[i]["ref_id"], fixed[i]["pos"], fixed[i]["flag"] = ref, pos, flag
        fixed[i]["block_size"] = 300 + i
    return {
        "fixed": fixed,
        "ref_span": np.array([r[2] for r in rows], dtype=np.int64),
        "record_starts": 1000 + 400 * np.arange(len(rows), dtype=np.int64),
        "crc": 7 + np.arange(len(rows), dtype=np.int64),
    }


def test_the_oracle_on_a_case_worked_by_hand():
    """The interval [1000, 2000) of contig 19, flags 1796 dropped."""
    rows = [
        (19, 850, 150, 99),      # 0 ends at 1000, its last base 999: out
        (19, 851, 150, 99),      # 1 its last base is 1000, the first: in
        (19, 1999, 150, 147),    # 2 starts at the interval's last base: in
        (19, 2000, 150, 147),    # 3 starts at its end: out
        (19, 1500, 0, 73 | 4),   # 4 an unmapped mate placed inside: out
        (19, 1500, 150, 99 | 0x400),   # 5 a duplicate inside: out
        (19, 1500, 150, 99 | 0x100),   # 6 secondary: out
        (19, 1500, 150, 99 | 0x200),   # 7 QC fail: out
        (18, 1500, 150, 99),     # 8 another contig: out
        (19, 999, 0, 99),        # 9 no span: one base, 999: out
        (19, 1000, 0, 99),       # 10 no span: one base, 1000: in
        (19, 1500, 150, 99),     # 11 inside: in
    ]
    index = hand_index(rows)
    keep = oracle_load.passing(index, (19, 1000, 2000), 0, 1796)
    assert np.flatnonzero(keep).tolist() == [1, 2, 10, 11]
    got = oracle_load.expected_rows(index, (19, 1000, 2000), 0, 1796)
    assert got["starts"].tolist() == [1400, 1800, 5000, 5400]
    assert got["crc"].tolist() == [8, 9, 17, 18]
    assert got["pos"].tolist() == [851, 1999, 1000, 1500]
    assert got["block_size"].tolist() == [301, 302, 310, 311]
    assert set(got) == {"starts", "crc", *oracle_load.COLUMNS}
    # Without the mask the duplicate, the secondary and the QC fail pass;
    # the unmapped mate never does. A required bit keeps what carries it.
    assert np.flatnonzero(oracle_load.passing(
        index, (19, 1000, 2000))).tolist() == [1, 2, 5, 6, 7, 10, 11]
    assert np.flatnonzero(oracle_load.passing(
        index, (19, 1000, 2000), 0x400, 0)).tolist() == [5]


@pytest.mark.parametrize("trace", (0, 1))
def test_the_cell_rehearses(trace, benchmark_json):
    proc = run_py(["--workload", CELL, "--seed", str(2 ** 31 + 43),
                   "--seconds", "2", "--trace", str(trace), "--rehearse"])
    line = last_line(proc)
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] >= 2 and line["device"]["platform"] == "cpu"
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in benchmark_json[group]
                if CELL in m.get("workloads", [CELL])}
    for name, row in line["metrics"].items():
        assert row["unit"] == declared[name]
    if trace:  # what the host's clock and the registry give without a chip
        assert set(line["metrics"]) == set().union(*METRICS.values()) - {
            "window_program_device_ms.load", "check_device_ms.load",
            "parse_device_ms.load", "filter_device_ms.load",
            "load_window_roofline", "device_idle_share.load"}
        value = {k: v["value"] for k, v in line["metrics"].items()}
        assert value["rows_out_per_pass.load"] < (
            value["records_parsed_per_pass.load"] / 20)
        assert value["records_parsed_per_pass.load"] <= (
            value["lanes_per_pass.load"])
        assert value["d2h_bytes_per_pass.load"] < 44 * 512 + 28 * 2
    else:
        assert set(line["metrics"]) == {"scan_rate", "setup_s"}
    checks = [json.loads(s) for s in proc.stdout.splitlines()
              if s.startswith('{"check"')]
    assert checks and all(c["ok"] for c in checks)
    names = {c["check"] for c in checks}
    assert {"warm_up.rows", "warm_up.rows_differing.starts",
            "warm_up.rows_differing.flag", "warm_up.rows_differing.crc",
            "warm_up_again.rows", "pass_1.rows", "pass_1.rows_differing.pos",
            "last_pass.rows_differing.crc", "warm_up.load.cigar_host_fixups",
            "warm_up.load.spilled_records", "warm_up.load.passes",
            "warm_up.load.d2h_bytes_over", "compiles_in_window",
            "warm_up.check.fused_demotions",
            "warm_up.check.count_escape_retries"} <= names
    assert "pass_1.rows_differing.crc" not in names  # warm-up and last alone
    assert ("window.load.d2h_bytes_over" in names) == bool(trace)
    rows = next(c for c in checks if c["check"] == "warm_up.rows")
    assert rows["got"] == rows["limit"] > 100
    window = next(json.loads(s) for s in proc.stdout.splitlines()
                  if s.startswith('{"phase": "window"'))
    assert window["detail"]["rows_a_pass"] == rows["limit"]
    assert window["detail"]["crc_ms"] > 0
    assert not list((ROOT / ".smoke_data" / "bench").glob("wgs-short-load-*"))


def test_a_filter_that_drops_a_flag_bit_is_not_correct(monkeypatch):
    """The duplicate bit left out of the mask the program is handed: the
    duplicates on chr20 come back, and the rows are not the oracle's."""
    from bench import run
    from spark_bam_tpu.tpu import parser

    real = parser.RowFilter.of.__func__

    def forgetful(cls, intervals=None, flags_required=0, flags_forbidden=0):
        return real(cls, intervals, flags_required, flags_forbidden & ~0x400)

    monkeypatch.setattr(parser.RowFilter, "of", classmethod(forgetful))
    out = run.run_cell(CELL, 2 ** 31 + 99, 0.5, False, rehearse=True)
    assert out["correct"] is False and out["failed"] == out["attempted"] > 0
    row = out["compared"]["warm_up.rows"]
    assert row["ok"] is False and row["got"] > row["limit"]
    assert out["compared"]["pass.rows_differing.flag"]["ok"] is False
    assert out["compared"]["compiles_in_window"]["ok"] is True


def test_a_head_sized_by_more_than_the_rows_is_not_correct(monkeypatch):
    """Eight times the head read back: the rows are the oracle's, and the
    program's own account is over what ``readback_sized_by_rows`` allows."""
    from bench import run
    from spark_bam_tpu.tpu import checker

    monkeypatch.setattr(checker, "table_head",
                        lambda table, rows: table[:, :8 * rows])
    out = run.run_cell(CELL, 2 ** 31 + 99, 0.5, False, rehearse=True)
    assert out["correct"] is False and out["failed"] == 0
    wrong = {what for what, row in out["compared"].items() if not row["ok"]}
    assert wrong == {"warm_up.load.d2h_bytes_over"}
    assert out["compared"]["warm_up.load.passes"]["got"] == 2


def test_a_program_without_the_loads_account_is_not_measured(monkeypatch,
                                                            capsys):
    """The parent of this cell's PR: ``stream_read_batches`` is there and
    keeps no ``load.passes`` / ``load.d2h_bytes``. The run ends before the
    warm-up, not zero, and leaves no file."""
    from bench import run
    from spark_bam_tpu.obs import names

    monkeypatch.setattr(names, "NAMES", names.NAMES - {
        "load.passes", "load.d2h_bytes"})
    with pytest.raises(SystemExit) as gone:
        run.run_cell(CELL, 2 ** 31 + 98, 0.5, False, rehearse=True)
    assert gone.value.code not in (None, 0)
    assert "readback_sized_by_rows" in str(gone.value.code)
    assert '"phase": "warm_up"' not in capsys.readouterr().out
    assert not list((ROOT / ".smoke_data" / "bench").glob("wgs-short-load-*"))


def test_a_sound_run_is_correct():
    from bench import run

    out = run.run_cell(CELL, 2 ** 31 + 99, 0.5, False, rehearse=True)
    assert out["correct"] is True and out["failed"] == 0
    assert all(row["ok"] for row in out["compared"].values())
