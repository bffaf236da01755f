"""End-to-end CLI golden-output tests (the reference's MainSuite pattern:
full stdout compared against checked-in goldens, timing lines via regex)."""

import re
from pathlib import Path

import pytest

from spark_bam_tpu.cli.main import main

GOLDEN = Path("/root/reference/cli/src/test/resources/output")


def run_cli(args, tmp_path, name="out.txt") -> str:
    out = tmp_path / name
    assert main(args + ["-o", str(out)]) == 0
    return out.read_text()


def test_check_bam_1bam_golden(bam1, tmp_path):
    got = run_cli(["check-bam", str(bam1)], tmp_path)
    assert got == (GOLDEN / "check-bam" / "1.bam").read_text()


def test_full_check_1bam_golden(bam1, tmp_path):
    got = run_cli(["full-check", str(bam1)], tmp_path)
    assert got == (GOLDEN / "full-check" / "1.bam").read_text()


def test_full_check_2bam_golden(bam2, tmp_path):
    got = run_cli(["full-check", str(bam2)], tmp_path)
    assert got == (GOLDEN / "full-check" / "2.bam").read_text()


def test_check_blocks_1bam_upstream(bam1, tmp_path):
    got = run_cli(["check-blocks", "-u", str(bam1)], tmp_path)
    assert got == (
        "First read-position mismatched in 1 of 25 BGZF blocks\n"
        "\n"
        "25871 of 597482 (0.043300049206503294) compressed positions would lead to bad splits\n"
        "\n"
        "Offsets of blocks' first reads (0 blocks didn't contain a read start):\n"
        "N: 25, μ/σ: 2004/8950, med/mad: 191/110\n"
        " elems: 1 25 28 39 42 45 81 112 136 143 … 268 270 271 287 301 304 311 312 316 45846\n"
        "   5:\t8\n"
        "  10:\t27\n"
        "  25:\t63\n"
        "  50:\t191\n"
        "  75:\t294\n"
        "  90:\t314\n"
        "  95:\t32187\n"
        "\n"
        "1 mismatched blocks:\n"
        "\t239479 (prev block size: 25871):\t239479:312\t239479:311\n"
    )


def test_check_blocks_2bam(bam2, tmp_path):
    got = run_cli(["check-blocks", str(bam2)], tmp_path)
    assert got.startswith(
        "First read-position matched in 25 BGZF blocks totaling 519KB (compressed)\n"
        "\n"
        "Offsets of blocks' first reads (0 blocks didn't contain a read start):\n"
        "N: 25, μ/σ: 604/1049, med/mad: 470/152\n"
    )


def test_compute_splits_eager_230k(bam1, tmp_path):
    got = run_cli(["compute-splits", "-s", "-m", "230k", str(bam1)], tmp_path)
    lines = got.splitlines()
    assert re.fullmatch(r"Get spark-bam splits: \d+ms", lines[0])
    assert lines[2:] == [
        "Split-size distribution:",
        "N: 3, μ/σ: 194067/57877.4, med/mad: 224301/20521",
        " elems: 224301 244822 113078",
        "sorted: 113078 224301 244822",
        "",
        "3 splits:",
        "\t0:45846-239479:312",
        "\t239479:312-484396:25",
        "\t484396:25-597482:0",
        "",
    ]


def test_compute_splits_host_plan(bam1, tmp_path, monkeypatch):
    """--plan-hosts renders the per-host sharded-run IO plan (byte ranges
    partitioning the file with a halo seam overlap)."""
    monkeypatch.setenv("SPARK_BAM_WINDOW_SIZE", "256KB")
    monkeypatch.setenv("SPARK_BAM_HALO_SIZE", "64KB")
    got = run_cli(
        ["compute-splits", "-s", "-m", "230k", "--plan-hosts", "2",
         "--devices-per-host", "4", str(bam1)],
        tmp_path,
    )
    assert "2-host plan (4 devices/host):" in got
    lines = [l for l in got.splitlines() if l.startswith("\thost ")]
    assert len(lines) == 2
    assert lines[0].startswith("\thost 0: bytes [0, ")
    assert "owned uncompressed" in lines[0]


def test_compute_splits_seqdoop_230k(bam1, tmp_path):
    got = run_cli(["compute-splits", "-u", "-m", "230k", str(bam1)], tmp_path)
    lines = got.splitlines()
    assert re.fullmatch(r"Get hadoop-bam splits: \d+ms", lines[0])
    assert lines[7:] == [
        "3 splits:",
        "\t0:45846-235520:65535",
        "\t239479:311-471040:65535",
        "\t484396:25-597482:65535",
        "",
    ]


def test_compute_splits_compare_230k(bam1, tmp_path):
    got = run_cli(["compute-splits", "-m", "230k", str(bam1)], tmp_path)
    lines = got.splitlines()
    assert lines[3:] == [
        "2 splits differ (totals: 3, 3):",
        "\t\t239479:311-471040:65535",
        "\t239479:312-484396:25",
        "",
    ]


def test_compute_splits_compare_240k_match(bam1, tmp_path):
    got = run_cli(["compute-splits", "-m", "240k", str(bam1)], tmp_path)
    assert "All splits matched!" in got
    assert "N: 3, μ/σ: 194067/74433.1, med/mad: 244941/3497" in got


def test_count_reads_matched(bam1, tmp_path):
    got = run_cli(["count-reads", "-m", "240k", str(bam1)], tmp_path)
    lines = got.splitlines()
    assert re.fullmatch(r"spark-bam read-count time: \d+", lines[0])
    assert re.fullmatch(r"hadoop-bam read-count time: \d+", lines[1])
    assert lines[2] == ""
    assert lines[3] == "Read counts matched: 4917"


def _cram_from_bam(bam, tmp_path):
    """Round-trip a fixture BAM into a CRAM for CLI tests."""
    from spark_bam_tpu.bam.iterators import RecordStream
    from spark_bam_tpu.bgzf.stream import BlockStream, UncompressedBytes
    from spark_bam_tpu.core.channel import open_channel
    from spark_bam_tpu.cram import CramWriter

    stream = RecordStream(UncompressedBytes(BlockStream(open_channel(bam))))
    header = stream.header
    recs = [rec for _, rec in stream]
    cram = tmp_path / (Path(bam).stem + ".cram")
    with CramWriter(cram, header.contig_lengths, header.text) as w:
        w.write_all(recs)
    return cram


def test_count_reads_cram(bam2, tmp_path):
    cram = _cram_from_bam(bam2, tmp_path)
    got = run_cli(["count-reads", str(cram)], tmp_path)
    lines = got.splitlines()
    assert re.fullmatch(r"spark-bam read-count time: \d+", lines[0])
    assert lines[1] == "Read count: 2500"


def test_count_reads_hadoop_fails(bam1, tmp_path):
    # At 230k the hadoop-bam split start is the 239479:311 false positive;
    # decoding from it must fail SAM validation.
    got = run_cli(["count-reads", "-m", "230k", str(bam1)], tmp_path)
    assert "spark-bam found 4917 reads, hadoop-bam threw exception:" in got
    assert "SAM validation error" in got


def test_time_load(bam1, tmp_path):
    got = run_cli(["time-load", "-m", "240k", str(bam1)], tmp_path)
    assert "All 3 partition-start reads matched" in got
    got = run_cli(["time-load", "-m", "230k", str(bam1)], tmp_path, "out2.txt")
    assert "spark-bam collected 3 partitions' first-reads" in got
    assert "hadoop-bam threw an exception:" in got


def test_compare_splits(bam1, bam2, tmp_path):
    bams = tmp_path / "bams.txt"
    bams.write_text(f"{bam1}\n{bam2}\n")
    got = run_cli(["compare-splits", "-m", "230k", str(bams)], tmp_path)
    lines = got.splitlines()
    assert lines[0] == (
        "1 of 2 BAMs' splits didn't match (totals: 6, 6; 1, 1 unmatched)"
    )
    assert "\t1.bam: 2 splits differ (totals: 3, 3; mismatched: 1, 1):" in lines
    assert "\t\t\t239479:311-471040:65535" in lines
    assert "\t\t239479:312-484396:25" in lines


def test_compare_splits_all_match(bam2, tmp_path):
    bams = tmp_path / "bams.txt"
    bams.write_text(f"{bam2}\n")
    got = run_cli(["compare-splits", "-m", "100k", str(bams)], tmp_path)
    assert got.splitlines()[0] == "All 1 BAMs' splits (totals: 6, 6) matched!"


def test_index_commands(bam2, tmp_path, capsys):
    out_blocks = tmp_path / "b.blocks"
    out_records = tmp_path / "r.records"
    assert main(["index-blocks", "-o", str(out_blocks), str(bam2)]) == 0
    assert main(["index-records", "-o", str(out_records), str(bam2)]) == 0
    assert out_blocks.read_text() == Path(str(bam2) + ".blocks").read_text()
    assert out_records.read_text() == Path(str(bam2) + ".records").read_text()


def test_rewrite_roundtrip(bam2, tmp_path):
    out_bam = tmp_path / "rewritten.bam"
    got = run_cli(
        ["htsjdk-rewrite", "-b", "5000", "-i", str(bam2), str(out_bam)], tmp_path
    )
    assert f"Wrote 2500 reads to {out_bam}" in got
    # The rewritten file loads identically.
    from spark_bam_tpu.load.api import load_bam

    assert load_bam(out_bam, split_size=1_000_000).count() == 2500


def test_cli_knobs(bam2, tmp_path):
    # reads-to-check=1 weakens the chain requirement: more boundary calls
    # than the .records truth (false positives appear), demonstrating the
    # knob reaches the engine.
    got = run_cli(
        ["check-bam", "-s", "--reads-to-check", "1", str(bam2)],
        tmp_path, "knobs.txt",
    )
    assert "false positives" in got or "All calls matched!" in got


def test_full_check_interval_goldens(bam2, tmp_path):
    """The reference's -i golden files (FullCheckTest.scala:34-60)."""
    for name, args in [
        ("2.bam.first", ["-i", "0"]),
        ("2.bam.second", ["-i", "26169"]),
        ("2.bam.200k", ["-i", "0-200k", "-m", "100k"]),
    ]:
        got = run_cli(["full-check", *args, str(bam2)], tmp_path, name + ".txt")
        assert got == (GOLDEN / "full-check" / name).read_text(), name


def test_full_check_noindex_golden(bam1, tmp_path):
    """full-check without .records: no confusion header (golden
    1.noblocks.bam)."""
    import shutil

    bam_copy = tmp_path / "1.noblocks.bam"
    shutil.copyfile(bam1, bam_copy)
    got = run_cli(["full-check", str(bam_copy)], tmp_path)
    assert got == (GOLDEN / "full-check" / "1.noblocks.bam").read_text()


def test_check_blocks_1bam_default_and_spark(bam1, tmp_path):
    # Default (eager vs seqdoop) mismatches exactly like -u; -s (truth vs
    # eager) matches everywhere (CheckBlocksTest.scala:9-53).
    got = run_cli(["check-blocks", str(bam1)], tmp_path, "d.txt")
    assert got.splitlines()[0] == "First read-position mismatched in 1 of 25 BGZF blocks"
    assert "\t239479 (prev block size: 25871):\t239479:312\t239479:311" in got

    got_s = run_cli(["check-blocks", "-s", str(bam1)], tmp_path, "s.txt")
    assert got_s.splitlines()[0] == (
        "First read-position matched in 25 BGZF blocks totaling 583KB (compressed)"
    )


def test_main_help_lists_all_commands(capsys):
    """Reference MainTest analog: the usage text names every subcommand and
    exits cleanly (exit trapped, not raised into the caller)."""
    with pytest.raises(SystemExit) as e:
        main(["--help"])
    assert e.value.code == 0
    out = capsys.readouterr().out
    for cmd in (
        "check-bam", "check-blocks", "full-check", "compute-splits",
        "compare-splits", "count-reads", "time-load", "index-blocks",
        "index-records", "htsjdk-rewrite",
    ):
        assert cmd in out, f"{cmd} missing from usage"


def test_main_unknown_command_fails(capsys):
    with pytest.raises(SystemExit) as e:
        main(["frobnicate"])
    assert e.value.code != 0
    assert "invalid choice" in capsys.readouterr().err


def test_count_reads_sharded(bam2, tmp_path):
    got = run_cli(["count-reads", "--sharded", str(bam2)], tmp_path)
    lines = got.splitlines()
    assert re.fullmatch(r"spark-bam read-count time: \d+", lines[0])
    assert lines[1] == "Read count: 2500"


def test_check_bam_sharded(bam1, tmp_path):
    got = run_cli(["check-bam", "--sharded", str(bam1)], tmp_path)
    golden = (GOLDEN / "check-bam" / "1.bam").read_text()
    # Header block identical to the golden report's first four lines
    # (eager-vs-truth has no miscalls; the golden's FP lines are the
    # seqdoop comparison's).
    assert got.splitlines() == [
        *golden.splitlines()[:4],
        "checked across 8 device(s)",
        "All calls matched!",
    ]


def test_sharded_flag_conflicts_are_usage_errors(bam1, capsys):
    assert main(["check-bam", "--sharded", "-u", str(bam1)]) == 2
    assert "no sharded path" in capsys.readouterr().err
    assert main(["count-reads", "--sharded", "x.cram"]) == 2
    assert "BAM only" in capsys.readouterr().err


def test_full_check_streaming_matches_golden_sections(bam2, tmp_path):
    """full-check --streaming (the WGS-scale O(window) path): every
    mask-derived section — two-check histogram, per-flag totals, total
    error counts — is byte-identical to the reference golden; the
    position list carries the same positions, unannotated."""
    got = run_cli(["full-check", "--streaming", str(bam2)], tmp_path)
    golden = (GOLDEN / "full-check" / "2.bam").read_text()

    assert got.startswith(
        "No positions where only one check failed\n"
        "\n"
        "10 of 2880 positions where exactly two checks failed:\n"
        "\t0:5649\n"
    )
    hist_start = golden.index("\tHistogram:")
    assert golden[hist_start: golden.index("Total error counts:")] in got
    assert golden[golden.index("Total error counts:"):] in got


def test_full_check_streaming_rejects_intervals(bam2, capsys):
    assert main(["full-check", "--streaming", "-i", "0-100k", str(bam2)]) == 2
    assert "not supported on the streaming path" in capsys.readouterr().err


def test_index_bam_command(bam2, tmp_path, capsys):
    import shutil

    bam = tmp_path / "2.bam"
    shutil.copy(bam2, bam)
    assert main(["index-bam", str(bam)]) == 0
    err = capsys.readouterr().err
    assert "84 references" in err
    from spark_bam_tpu.bam.bai import BaiIndex

    assert len(BaiIndex.read(str(bam) + ".bai").references) == 84


def test_compare_splits_corpus(bam2, tmp_path):
    """The many-BAM cohort shape (BASELINE config: compute-splits over a
    corpus; reference CompareSplits runs one task per BAM): ten repacks of
    2.bam at varied block payloads, every one's splits matching."""
    from spark_bam_tpu.cli import rewrite
    from spark_bam_tpu.cli.output import Printer

    paths = []
    for i, payload in enumerate(range(12_000, 62_000, 5_000)):
        out = tmp_path / f"r{i}.bam"
        rewrite.run(str(bam2), str(out), Printer(), block_payload=payload,
                    reindex=False)
        paths.append(out)
    bams = tmp_path / "bams.txt"
    bams.write_text("".join(f"{p}\n" for p in paths))
    got = run_cli(["compare-splits", "-m", "100k", str(bams)], tmp_path)
    assert got.splitlines()[0] == (
        f"All {len(paths)} BAMs' splits (totals: 60, 60) matched!"
    )
