"""check-bam on the device (``load.tpu_load.check_bam_tpu`` →
``parallel.stream_mesh.check_bam_sharded`` → ``jit_confusion_step``) against
the plain reference, ``check/eager.py``: one position at a time, no kernels.

A seeded short-read file of the benchmark's kind (``bench/generators/
shortread.py`` at 600 KB), rows of two BGZF members and a 32 KiB halo so that
the file has five rows, and a ``.records`` sidecar that is WRONG in known
places: records dropped (the first one at or after every row seam among
them), positions added that start no record (one of them in the header). The
reference's verdict at every position, held against the same truth, gives the
four counts and the two position lists that the device path must return,
exactly.
"""

import functools
import json
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from spark_bam_tpu.core.config import Config
from spark_bam_tpu.parallel import stream_mesh
from spark_bam_tpu.parallel.mesh import MISMATCH_LIST, make_mesh
from spark_bam_tpu.parallel.stream_mesh import check_bam_sharded
from tests.test_host_fed_count import (
    _observed, _traced, assert_one_trace_a_pass,
)

ROOT = Path(__file__).resolve().parents[1]
MEMBER = 0xFF00
WINDOW, HALO = 2 * MEMBER, 32 << 10
CFG = dict(window_uncompressed=WINDOW, halo=HALO)
SEED, SIZE = 7, 600_000


@pytest.fixture(scope="module")
def bam(tmp_path_factory):
    """``(path, index, the eager reference's verdict at every position)``."""
    from bench.generators import shortread
    from spark_bam_tpu.check.eager import EagerChecker
    from spark_bam_tpu.core.pos import Pos

    params = json.loads(
        (ROOT / "bench" / "configs" / "wgs-short.json").read_text())["params"]
    path = tmp_path_factory.mktemp("checkbam") / "short.bam"
    index = shortread.generate(params, SEED, SIZE, path)
    checker = EagerChecker.open(path)
    starts, flat = index["block_starts"], index["block_flat"]
    verdict = np.zeros(index["uncompressed_bytes"], dtype=bool)
    for f in range(len(verdict)):
        b = int(np.searchsorted(flat, f, side="right")) - 1
        verdict[f] = checker(Pos(int(starts[b]), int(f - flat[b])))
    return path, index, verdict


def write_sidecar(index: dict, truth: np.ndarray, path) -> None:
    """``truth`` (flat offsets) in upstream's line format."""
    from bench.oracle_checkbam import sidecar_text

    Path(path).write_text(sidecar_text(index, np.asarray(truth)))


def perturbed(index: dict, seed: int = 11):
    """``(truth, dropped, added)``: 9 records dropped, the first at or after
    every row seam among them, and 6 positions added that start no record,
    one of them in the header."""
    rng = np.random.default_rng(seed)
    records = index["record_starts"]
    seams = np.arange(1, 5) * WINDOW
    at_seams = records[np.searchsorted(records, seams)]
    others = rng.choice(np.setdiff1d(records, at_seams), 5, replace=False)
    dropped = np.sort(np.concatenate([at_seams, others]))
    free = np.setdiff1d(
        np.arange(index["header_end"], index["uncompressed_bytes"]), records)
    added = np.sort(np.concatenate([[17], rng.choice(free, 5, replace=False)]))
    truth = np.sort(np.concatenate([np.setdiff1d(records, dropped), added]))
    return truth, dropped, added


def expected(verdict: np.ndarray, truth: np.ndarray) -> dict:
    """The reference's answer: its verdicts held against ``truth``."""
    t = np.zeros(len(verdict), dtype=bool)
    t[truth] = True
    return {
        "true_positives": int((verdict & t).sum()),
        "false_positives": int((verdict & ~t).sum()),
        "false_negatives": int((~verdict & t).sum()),
        "true_negatives": int((~verdict & ~t).sum()),
        "positions": len(verdict),
        "false_positive_positions": np.flatnonzero(verdict & ~t),
        "false_negative_positions": np.flatnonzero(~verdict & t),
    }


def assert_same(got: dict, want: dict, devices: int) -> None:
    assert got.pop("devices") == devices
    assert set(got) == set(want)
    for key, value in want.items():
        if key.endswith("_positions"):
            assert got[key].dtype == np.int64
            assert np.array_equal(got[key], value), key
        else:
            assert got[key] == value, key


def one_row_a_chip(monkeypatch):
    """Steps of one row a device: five steps on one device, two on four."""
    monkeypatch.setattr(stream_mesh, "_rows_fitting_device", lambda *_: 1)


@pytest.mark.parametrize("devices,one_row", [(1, True), (4, True), (4, False)],
                         ids=["1-device", "4-devices", "4-devices-one-step"])
def test_equals_the_eager_reference_at_every_position(
        devices, one_row, bam, tmp_path, monkeypatch):
    path, index, verdict = bam
    truth, dropped, added = perturbed(index)
    sidecar = tmp_path / "wrong.records"
    write_sidecar(index, truth, sidecar)
    if one_row:
        one_row_a_chip(monkeypatch)
    got, counters, hists = _observed(lambda: check_bam_sharded(
        path, Config(), mesh=make_mesh(jax.devices("cpu")[:devices]),
        records_path=sidecar, **CFG))
    want = expected(verdict, truth)
    # The eager checker makes no miscall on this file, so the disagreements
    # are the sidecar's: every dropped record, every added position.
    assert np.array_equal(want["false_positive_positions"], dropped)
    assert np.array_equal(want["false_negative_positions"], added)
    assert_same(got, want, devices)
    assert counters["mesh.steps"] == (
        1 if not one_row else 5 if devices == 1 else 2)
    assert counters["mesh.rows"] == 5
    assert counters["checkbam.mismatches"] == 15
    for name in ("mesh.dirty_steps", "checkbam.list_overflows",
                 "check.fused_demotions"):
        assert not counters.get(name), name
    for span in ("mesh.assemble", "mesh.h2d", "mesh.step_device_ms",
                 "mesh.step_lanes"):
        assert hists[span] == counters["mesh.steps"], span
    assert hists["checkbam.truth_load"] == 1
    # The step's two new columns: each row's stage-0 survivors and the
    # lanes its blocks ran for them, far fewer than the rows' capacity.
    from spark_bam_tpu.tpu.checker import lane_block, lane_capacity
    from spark_bam_tpu.tpu.stream_check import _next_pow2

    kernel_window = _next_pow2(WINDOW + HALO)
    survivors, lanes = counters["funnel.survivors"], counters["funnel.lanes"]
    assert len(index["record_starts"]) <= survivors <= lanes
    assert lanes % lane_block(kernel_window) == 0
    assert lanes < counters["mesh.rows"] * lane_capacity(kernel_window)


def test_check_bam_tpu_runs_the_same_step_on_what_the_process_sees(
        bam, tmp_path):
    """The library entry: the sidecar beside the file, the mesh of every
    local device (eight virtual ones here), one pass counted."""
    from spark_bam_tpu.load.tpu_load import check_bam_tpu

    path, index, verdict = bam
    truth, _, _ = perturbed(index, seed=12)
    local = tmp_path / "short.bam"
    local.symlink_to(path)
    write_sidecar(index, truth, str(local) + ".records")
    config = Config(window_size=WINDOW, halo_size=HALO)
    got, counters, hists = _observed(lambda: check_bam_tpu(local, config))
    assert_same(got, expected(verdict, truth), jax.local_device_count())
    assert counters["checkbam.passes"] == hists["load.check_bam"] == 1


def small_pieces(monkeypatch, piece_bytes: int = 512):
    """The truth's loader handed ``piece_bytes`` of text a piece (the pass
    makes its own ``_Truth``): tens of pieces where the 20 KB sidecar is one."""
    monkeypatch.setattr(stream_mesh, "_Truth", functools.partial(
        stream_mesh._Truth, piece_bytes=piece_bytes))


def no_loader_alive() -> bool:
    return not any(t.name == "checkbam-truth" for t in threading.enumerate())


@pytest.mark.parametrize("small", (False, True),
                         ids=["one-piece", "small-pieces"])
def test_a_check_bam_pass_is_one_trace_with_its_own_account(
        small, bam, tmp_path, monkeypatch):
    """``check_bam_tpu`` twice: two traces, the assembly thread's spans in
    them, the walk, the plan and both ends under spans; the truth's load
    once a pass on its own thread and a wait for it once a live row on the
    row pool's, both in the pass's trace, whatever the pieces."""
    from spark_bam_tpu.load.tpu_load import check_bam_tpu

    if small:
        small_pieces(monkeypatch)
    path, index, verdict = bam
    truth, _, _ = perturbed(index, seed=12)
    local = tmp_path / "short.bam"
    local.symlink_to(path)
    write_sidecar(index, truth, str(local) + ".records")
    config = Config(window_size=WINDOW, halo_size=HALO)
    values, events, hists = _traced(lambda: check_bam_tpu(local, config))
    for got in values:
        assert_same(got, expected(verdict, truth), jax.local_device_count())
    assert_one_trace_a_pass(
        events, hists, "load.check_bam", 2,
        phases={"load.open", "bgzf.read", "mesh.plan",
                "mesh.stall", "mesh.step", "mesh.dispatch", "load.drain"},
        threads={"mesh.assemble", "mesh.h2d", "inflate.window",
                 "checkbam.truth_load", "checkbam.truth_wait"})
    assert hists["bgzf.read"] == hists["mesh.plan"] == 2
    assert hists["checkbam.truth_load"] == 2
    rows = -(-index["uncompressed_bytes"] // WINDOW)
    assert hists["checkbam.truth_wait"] == hists["mesh.truth_fill"] == 2 * rows
    waits = [e for e in events if e["name"] == "checkbam.truth_wait"]
    assert sorted(e["attrs"]["row"] for e in waits) == sorted(
        2 * list(range(rows)))
    assert no_loader_alive()
    assert hists["load.drain"] == 4  # the close, then the result, a pass


def test_a_row_over_its_list_is_rederived_and_still_exact(bam, tmp_path):
    """65 records dropped in one row: one more than the list's slots. The
    row's positions come from the host, its sums stand, the rest is read
    from the lists."""
    path, index, verdict = bam
    records = index["record_starts"]
    first = int(np.searchsorted(records, 2 * WINDOW + 1000))
    dropped = records[first: first + MISMATCH_LIST + 1]
    assert dropped[-1] < 3 * WINDOW  # all of them in the third row
    truth, more_dropped, added = perturbed(index)
    truth = np.setdiff1d(truth, dropped)
    sidecar = tmp_path / "many.records"
    write_sidecar(index, truth, sidecar)
    got, counters, _ = _observed(lambda: check_bam_sharded(
        path, Config(), mesh=make_mesh(jax.devices("cpu")[:4]),
        records_path=sidecar, **CFG))
    assert counters["checkbam.list_overflows"] == 1
    assert counters["mesh.patch_rows"] == 1
    assert not counters.get("check.fused_demotions")
    want = expected(verdict, truth)
    assert len(want["false_positive_positions"]) == len(
        np.union1d(dropped, more_dropped))
    assert_same(got, want, 4)


@pytest.mark.parametrize("whole_file", (False, True),
                         ids=["rows-patched", "whole-file"])
def test_escaped_steps_stay_exact_and_a_demotion_is_counted(
        whole_file, bam, tmp_path, monkeypatch):
    """No halo at all: the last records of every row but the last escape.
    One dirty step is re-derived row by row on the host; when nearly every
    step is dirty the whole file goes through one device, and says so."""
    path, index, verdict = bam
    truth, _, _ = perturbed(index)
    sidecar = tmp_path / "wrong.records"
    write_sidecar(index, truth, sidecar)
    if whole_file:
        one_row_a_chip(monkeypatch)
    got, counters, _ = _observed(lambda: check_bam_sharded(
        path, Config(), mesh=make_mesh(jax.devices("cpu")[:1]),
        records_path=sidecar, window_uncompressed=WINDOW, halo=0))
    assert counters["mesh.dirty_steps"] >= 1
    assert counters.get("check.fused_demotions", 0) == int(whole_file)
    assert_same(got, expected(verdict, truth), 1)


def test_a_stale_sidecar_still_raises(bam, tmp_path):
    path, index, _ = bam
    sidecar = tmp_path / "stale.records"
    sidecar.write_text(f"{index['block_starts'][1] + 1},0\n")
    with pytest.raises(ValueError, match="stale sidecar"):
        check_bam_sharded(path, Config(), records_path=sidecar, **CFG)
    assert no_loader_alive()


@pytest.mark.parametrize("small", (False, True),
                         ids=["one-piece", "small-pieces"])
@pytest.mark.parametrize("last_line,match", [
    ("1,2,3", "not a .records sidecar"), ("{stale},0", "stale sidecar")])
def test_an_error_in_the_sidecars_last_line_reaches_the_caller(
        last_line, match, small, bam, tmp_path, monkeypatch):
    """The loader's error is the caller's ``ValueError`` as it was when the
    truth was loaded before the first step, wherever in the sidecar it lies
    and whichever wait meets it; no loader thread outlives the pass."""
    path, index, _ = bam
    if small:
        small_pieces(monkeypatch)
    sidecar = tmp_path / "bad.records"
    write_sidecar(index, index["record_starts"], sidecar)
    with open(sidecar, "a") as f:
        f.write(last_line.format(stale=index["block_starts"][1] + 1) + "\n")
    with pytest.raises(ValueError, match=match):
        check_bam_sharded(
            path, Config(), mesh=make_mesh(jax.devices("cpu")[:4]),
            records_path=sidecar, **CFG)
    assert no_loader_alive()


# ------------------------------------- the truth, loaded beside the steps

def unordered(text: str, kind: str) -> str:
    """A sidecar's lines out of file order: all of them shuffled, or one
    record of the first row moved behind the last line."""
    lines = text.splitlines()
    if kind == "shuffled":
        np.random.default_rng(5).shuffle(lines)
    elif kind == "one-late-line":
        lines.append(lines.pop(40))
    return "\n".join(lines) + "\n"


@pytest.mark.parametrize("small", (False, True),
                         ids=["one-piece", "small-pieces"])
@pytest.mark.parametrize("kind", ["sorted", "shuffled", "one-late-line"])
def test_a_sidecar_out_of_order_starts_the_pass_over_and_stays_exact(
        kind, small, bam, tmp_path, monkeypatch):
    """Rows are filled from the prefix of the sidecar that lies before
    their end, which is their whole truth only in a sidecar in file order.
    One that is not (today's load sorted it) is seen by the loader, the
    pass starts over with the truth whole and sorted first, and the answer
    is the sorted sidecar's, element for element."""
    from bench.oracle_checkbam import sidecar_text

    path, index, verdict = bam
    truth, _, _ = perturbed(index)
    one_row_a_chip(monkeypatch)  # two steps: rows filled before the end
    if small:
        small_pieces(monkeypatch)
    sidecar = tmp_path / f"{kind}.records"
    sidecar.write_text(unordered(sidecar_text(index, truth), kind))
    got, counters, hists = _observed(lambda: check_bam_sharded(
        path, Config(), mesh=make_mesh(jax.devices("cpu")[:4]),
        records_path=sidecar, **CFG))
    assert_same(got, expected(verdict, truth), 4)
    assert counters.get("checkbam.truth_restarts", 0) == int(kind != "sorted")
    assert hists["checkbam.truth_load"] == 1  # parsed once, restart or none
    assert not counters.get("check.fused_demotions")
    assert no_loader_alive()


def test_no_row_is_filled_before_its_truth_is_covered(
        bam, tmp_path, monkeypatch):
    """A loader far slower than the assembly (it parses nothing before the
    first row waits, then 5 ms a piece of 512 bytes): every row's fill finds
    the truth known up to the row's end, the first row is filled while the
    loader is still at work, and the answer is exact."""
    from spark_bam_tpu.bam import index_records

    path, index, verdict = bam
    truth, _, _ = perturbed(index)
    sidecar = tmp_path / "wrong.records"
    write_sidecar(index, truth, sidecar)
    pieces = index_records.iter_records_arrays
    asked = threading.Event()  # a row waits for its truth
    fills = []  # (the row's end, the truth's reach at its fill)

    def slowly(*args):
        assert asked.wait(60)
        for piece in pieces(*args):
            time.sleep(0.005)
            yield piece

    class Watched(stream_mesh._Truth):
        def wait(self, end):
            asked.set()
            super().wait(end)

        def fill(self, row, base, n):
            fills.append((base + n, self._covered))
            return super().fill(row, base, n)

    monkeypatch.setattr(index_records, "iter_records_arrays", slowly)
    monkeypatch.setattr(stream_mesh, "_Truth", functools.partial(
        Watched, piece_bytes=512))
    one_row_a_chip(monkeypatch)
    got = check_bam_sharded(
        path, Config(), mesh=make_mesh(jax.devices("cpu")[:1]),
        records_path=sidecar, **CFG)
    assert_same(got, expected(verdict, truth), 1)
    assert len(fills) == 5
    assert all(covered >= end for end, covered in fills)
    assert fills[0][1] < index["uncompressed_bytes"]  # beside, not before
    assert no_loader_alive()


def test_rows_on_many_threads_wait_on_one_loader(bam, tmp_path):
    """More waiting threads than cores, the interpreter switching between
    them all the time, pieces of a few lines: every fill, made as soon as
    its wait returns, holds exactly the truth of its span."""
    import sys
    from concurrent.futures import ThreadPoolExecutor

    from spark_bam_tpu.bgzf.index_blocks import blocks_metadata

    path, index, _ = bam
    records, total = index["record_starts"], index["uncompressed_bytes"]
    sidecar = tmp_path / "right.records"
    write_sidecar(index, records, sidecar)
    span = 20_000

    def row(k: int) -> bool:
        base = (k * 104_729) % (total - span)
        got = np.zeros(span, dtype=bool)
        truth.wait(base + span)
        at = truth.fill(got, base, span)
        want = records[(records >= base) & (records < base + span)] - base
        return np.array_equal(np.flatnonzero(got), want) and np.array_equal(
            at, want)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    truth = stream_mesh._Truth(
        path, sidecar, list(blocks_metadata(path)), piece_bytes=256)
    try:
        with ThreadPoolExecutor(64) as pool:
            assert all(pool.map(row, range(512), timeout=120))
        assert np.array_equal(truth.whole(), records)
    finally:
        sys.setswitchinterval(interval)
        truth.close()
    assert no_loader_alive()


# ------------------------------------------------- the sidecar's parse

def test_the_sidecar_round_trips_through_the_vectorised_parse(bam, tmp_path):
    from spark_bam_tpu.bam.index_records import (
        index_records, read_records_arrays, read_records_index,
    )

    path, index, _ = bam
    out, n = index_records(path, tmp_path / "short.records")
    assert n == len(index["record_starts"])
    blocks, offsets = read_records_arrays(out, chunk_bytes=4096)
    assert blocks.dtype == offsets.dtype == np.int64
    assert [(int(b), int(o)) for b, o in zip(blocks, offsets)] == [
        (p.block_pos, p.offset) for p in read_records_index(out)]
    truth = stream_mesh._Truth(path, out, stream_mesh._ShardedStream(
        path, Config(), make_mesh(jax.devices("cpu")[:1]),
        WINDOW, HALO, None).metas)
    assert np.array_equal(truth.whole(), index["record_starts"])
    truth.close()


@pytest.mark.parametrize("chunk", [7, 512, 4096, 64 << 20])
def test_the_pieces_joined_are_the_whole_parse(chunk, bam, tmp_path):
    """``iter_records_arrays``: pieces in file order, cut at line ends,
    none empty; ``read_records_arrays`` is their concatenation."""
    from spark_bam_tpu.bam.index_records import (
        iter_records_arrays, read_records_arrays,
    )

    _, index, _ = bam
    sidecar = tmp_path / "right.records"
    write_sidecar(index, index["record_starts"], sidecar)
    pieces = list(iter_records_arrays(sidecar, chunk))
    assert all(len(b) == len(o) > 0 for b, o in pieces)
    assert (chunk > 4096) == (len(pieces) == 1)
    whole = read_records_arrays(sidecar)
    for joined, column in zip(map(np.concatenate, zip(*pieces)), whole):
        assert joined.dtype == np.int64
        assert np.array_equal(joined, column)
    for column, again in zip(whole, read_records_arrays(sidecar, chunk)):
        assert np.array_equal(column, again)


@pytest.mark.parametrize("text,rows", [
    ("0,45\n0,700\n\n123456789012,65279\n", [(0, 45), (0, 700),
                                            (123456789012, 65279)]),
    ("0,45\n0,700", [(0, 45), (0, 700)]),
    ("\n\n", []),
    ("", []),
], ids=["blank-line", "no-trailing-newline", "blank-alone", "empty"])
def test_blank_lines_and_the_trailing_newline_are_tolerated(
        text, rows, tmp_path):
    from spark_bam_tpu.bam.index_records import (
        read_records_arrays, read_records_index,
    )

    sidecar = tmp_path / "x.records"
    sidecar.write_text(text)
    for chunk in (7, 64 << 20):  # lines cut across chunks, and not
        blocks, offsets = read_records_arrays(sidecar, chunk_bytes=chunk)
        assert list(zip(blocks.tolist(), offsets.tolist())) == rows
    assert [(p.block_pos, p.offset)
            for p in read_records_index(sidecar)] == rows


@pytest.mark.parametrize("text", ["1,2,3\n4\n", "a,b\n", "5\n", ",5\n"])
def test_a_line_that_is_no_position_raises(text, tmp_path):
    from spark_bam_tpu.bam.index_records import read_records_arrays

    sidecar = tmp_path / "x.records"
    sidecar.write_text(text)
    with pytest.raises(ValueError, match="not a .records sidecar"):
        read_records_arrays(sidecar)


def test_no_object_a_record_on_the_check_bam_path(bam, tmp_path, monkeypatch):
    """The check-bam path reads the sidecar through numpy alone: neither the
    ``Pos``-list reader nor its line parser is touched."""
    from spark_bam_tpu.bam import index_records

    path, index, _ = bam
    sidecar = tmp_path / "right.records"
    write_sidecar(index, index["record_starts"], sidecar)

    def refuse(*_a, **_k):
        raise AssertionError("a Pos a record on the check-bam path")

    monkeypatch.setattr(index_records, "read_records_index", refuse)
    monkeypatch.setattr(index_records, "parse_record_line", refuse)
    got = check_bam_sharded(
        path, Config(), mesh=make_mesh(jax.devices("cpu")[:1]),
        records_path=sidecar, **CFG)
    assert got["false_positives"] == got["false_negatives"] == 0
    assert got["true_positives"] == len(index["record_starts"])


# ------------------------------------------------------------- the CLI

@pytest.mark.parametrize("flags,limit", [
    (["--sharded"], None), (["--sharded", "-s"], 3)],
    ids=["sharded", "sharded-s-limit-3"])
def test_the_cli_prints_where_the_calls_disagree(
        flags, limit, bam, tmp_path):
    """``check-bam --sharded`` (with or without ``-s``: the same call) is
    ``check_bam_tpu``, and prints the disagreeing positions as
    ``block:offset`` under the printer's limit, as upstream prints them."""
    from spark_bam_tpu.cli.main import main

    path, index, _ = bam
    truth, dropped, added = perturbed(index)
    local = tmp_path / "short.bam"
    local.symlink_to(path)
    write_sidecar(index, truth, str(local) + ".records")
    out = tmp_path / "out.txt"
    args = ["check-bam", *flags, str(local), "-o", str(out)]
    assert main(args + (["-l", str(limit)] if limit else [])) == 0
    lines = out.read_text().splitlines()
    assert lines[0] == f"{index['uncompressed_bytes']} uncompressed positions"
    assert f"{len(index['record_starts']) - 9 + 6} reads" in lines
    assert f"checked across {jax.local_device_count()} device(s)" in lines
    at = lines.index("9 false positives, 6 false negatives")

    def pos(flat):
        b = int(np.searchsorted(index["block_flat"], flat, side="right")) - 1
        return f"\t{index['block_starts'][b]}:{flat - index['block_flat'][b]}"

    if limit:
        assert lines[at + 1:] == [
            "3 of 9 false positives:", *map(pos, dropped[:3]), "\t…",
            "3 of 6 false negatives:", *map(pos, added[:3]), "\t…"]
    else:
        assert lines[at + 1:] == [
            "9 false positives:", *map(pos, dropped),
            "6 false negatives:", *map(pos, added)]


def test_the_cli_scans_the_blocks_once_and_reports_every_step(
        bam, tmp_path, monkeypatch):
    """The operator's path: ``check_bam_tpu`` is handed the block table the
    CLI has scanned (one scan of the file, not two) and a progress callback
    that is called once a step (the heartbeat: a 60 GB file is hours)."""
    from spark_bam_tpu.bgzf import index_blocks
    from spark_bam_tpu.cli.main import main
    from spark_bam_tpu.parallel import stream_mesh

    path, index, _ = bam
    local = tmp_path / "short.bam"
    local.symlink_to(path)
    write_sidecar(index, index["record_starts"], str(local) + ".records")
    scans, seen = [], {}
    scan, check = index_blocks.blocks_metadata, stream_mesh.check_bam_sharded

    def counted_scan(*a, **k):
        scans.append(a)
        return scan(*a, **k)

    def watched(*a, progress=None, metas=None, **k):
        seen["metas"], seen["calls"] = metas, []

        def heard(*step):
            seen["calls"].append(step)
            progress(*step)

        return check(*a, progress=heard, metas=metas, **k)

    monkeypatch.setattr(index_blocks, "blocks_metadata", counted_scan)
    monkeypatch.setattr(stream_mesh, "check_bam_sharded", watched)
    out = tmp_path / "out.txt"
    assert main(["check-bam", "--sharded", str(local), "-o", str(out)]) == 0
    assert "All calls matched!" in out.read_text()
    assert len(scans) == 1 and len(seen["metas"]) == len(index["block_flat"])
    steps, done, total = zip(*seen["calls"])
    assert steps == tuple(range(1, len(steps) + 1))
    assert done[-1] == total[-1] == index["uncompressed_bytes"]


@pytest.mark.parametrize("backend,mib,device", [
    ("tpu", 48, True), ("tpu", 8, False), ("auto", 48, False)],
    ids=["tpu-at-scale", "tpu-small", "auto-on-the-cpu"])
def test_dash_s_goes_to_the_device_at_scale(backend, mib, device):
    """``-s`` is scored by ``check_bam_tpu`` where the file is over one
    kernel window and the context's eager engine is the device
    (``cli/app.device_engine``: the one decision, which the context asks
    with its view's size), read from the block table alone. (``auto`` asks
    for a TPU, which this process does not see; a small file under a device
    backend is scored on the device through the whole-view path.)"""
    from spark_bam_tpu.bgzf.block import Metadata
    from spark_bam_tpu.cli.app import device_engine
    from spark_bam_tpu.cli.check_bam import _device_scores

    metas = [Metadata(30_000 * i, 30_000, MEMBER)
             for i in range((mib << 20) // MEMBER)]
    assert _device_scores(backend, metas) is device
    assert device_engine(backend, mib << 20) is (backend != "auto")
    assert device_engine("numpy", mib << 20) is False
