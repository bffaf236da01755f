"""The served path's file tier: segments of whole rows, not whole files.

Small files from the benchmark's short-read generator
(``bench/generators/shortread.py``, ``wgs-short``'s ``params``) under rows
of 16 KiB with an 8 KiB halo, so a file of 450 KB is 54 rows in seven
segments of 8 (``SEGMENT_TICKS`` ticks of 8 rows). What is compared: the
rows ``_scan_rows`` cuts against the same tiling cut of ``flatten_file``'s
whole view; served counts against ``check/eager.py`` at every position and
against the generator's index; the tier's own account (misses, waits,
evictions, resident bytes) against the budget.
"""

import dataclasses
import json
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from spark_bam_tpu import obs
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.serve import ServeConfig, SplitService
from spark_bam_tpu.serve import service as service_mod

pytestmark = pytest.mark.serve

ROOT = Path(__file__).resolve().parents[1]
WINDOW, HALO = 16 << 10, 8 << 10
STEP = WINDOW - HALO
SPEC = f"window={WINDOW},halo={HALO},batch=8,tick=2,workers=4"
SIZE = 450_000
ROWS = service_mod.SEGMENT_TICKS * 8
#: A segment's bytes at most: its rows, the halo, whole members at both ends.
SEGMENT_MOST = ROWS * STEP + HALO + 2 * 0xFF00


def segments_of(size: int) -> int:
    """Segments a file of ``size`` flat bytes is cut into."""
    last_row = max(-(-(size - WINDOW) // STEP), 0)
    return last_row // ROWS + 1


def params() -> dict:
    return json.loads(
        (ROOT / "bench" / "configs" / "wgs-short.json").read_text())["params"]


def write(path, seed: int, size: int = SIZE) -> dict:
    from bench.generators import shortread

    return shortread.generate(params(), seed, size, path)


@pytest.fixture(scope="module")
def cohort(tmp_path_factory):
    """Four files: ``(path, index, eager verdict at every position)``."""
    from spark_bam_tpu.check.eager import EagerChecker
    from spark_bam_tpu.core.pos import Pos

    out = []
    root = tmp_path_factory.mktemp("segments")
    for k in range(4):
        path = root / f"sample{k}.bam"
        index = write(path, 100 + k)
        checker = EagerChecker.open(path)
        starts, flat = index["block_starts"], index["block_flat"]
        verdict = np.zeros(index["uncompressed_bytes"], dtype=bool)
        for f in range(len(verdict)):
            b = int(np.searchsorted(flat, f, side="right")) - 1
            verdict[f] = checker(Pos(int(starts[b]), int(f - flat[b])))
        out.append((str(path), index, verdict))
    return out


@pytest.fixture()
def registry():
    reg = obs.configure()
    yield reg
    obs.shutdown()


def counters(reg) -> dict:
    return {c["name"]: c["value"] for c in reg.snapshot()["counters"]
            if not c["labels"]}


def service(cache: str = "256MB") -> SplitService:
    return SplitService(Config(serve=f"{SPEC},cache={cache}"))


# ------------------------------------------------------------ (a) the rows


def reference_rows(flat: np.ndarray, lo: int, hi: int) -> list:
    """The tiling as it was cut of the whole-file view: rows start at whole
    steps from 0, the row whose window reaches the end is the last."""
    n_total, rows = len(flat), []
    if lo >= hi:
        return rows
    for s in range(0, n_total, STEP):
        e = min(s + WINDOW, n_total)
        own_end = e if e == n_total else min(s + STEP, n_total)
        row_lo, row_own = max(lo, s) - s, min(hi, own_end) - s
        if s < hi and row_lo < row_own:
            rows.append((flat[s:e].tobytes(), e - s, e == n_total,
                         row_lo, row_own))
        if e == n_total:
            break
    return rows


def ranges(index: dict) -> dict:
    size, head = int(index["uncompressed_bytes"]), int(index["header_end"])
    seam = ROWS * STEP  # where segment 0's owned rows end
    return {
        "whole": (head, size),
        "from_the_headers_end": (head, head + 3 * STEP + 17),
        "inside_one_row": (5 * STEP + 100, 5 * STEP + 900),
        "across_a_segment_seam": (seam - 2 * STEP - 5, seam + STEP + 5),
        "from_a_seam_on": (seam, seam + 1),
        "to_the_files_end": (size - 2 * STEP - 3, size),
        "the_last_byte": (size - 1, size),
        "empty": (seam, seam),
        "empty_past_the_end": (size, size),
    }


@pytest.mark.parametrize("case", sorted(ranges(
    {"uncompressed_bytes": 10 ** 6, "header_end": 0})))
def test_rows_are_those_cut_of_the_whole_view(case, cohort, monkeypatch):
    from spark_bam_tpu.bgzf.flat import flatten_file

    path, index, _ = cohort[0]
    lo, hi = ranges(index)[case]
    whole = flatten_file(path).data
    assert len(whole) == index["uncompressed_bytes"]
    svc = service()
    try:
        monkeypatch.setattr(svc.batcher, "submit", lambda task: None)
        fs = svc.file_state(path)
        tasks = svc._scan_rows(fs, lo, hi, None)
    finally:
        svc.close()
    got = [(t.window.tobytes(), t.n, t.at_eof, t.lo, t.own) for t in tasks]
    assert got == reference_rows(whole, lo, hi)
    assert (fs.size, fs.header_end) == (len(whole), index["header_end"])
    # Views, not copies: a row's bytes are its segment's.
    assert all(t.window.base is not None for t in tasks)
    if case == "inside_one_row":
        assert len(svc.segments) == 1 and svc.segments.resident <= (
            SEGMENT_MOST)


# ----------------------------------------------- (b) a set over the budget


def test_four_files_under_a_budget_of_less_than_one(cohort, registry):
    from bench import oracle

    budget = 300 << 10  # under one file's 450 KB: two segments at most
    whole = max(segments_of(index["uncompressed_bytes"])
                for _, index, _ in cohort)
    svc = service(cache=f"{budget}")
    wrong, peaks = [], []

    def client(k: int) -> None:
        rng = np.random.default_rng([k, 47])
        for _ in range(12):
            path, index, verdict = cohort[int(rng.integers(4))]
            size = int(index["compressed_bytes"])
            start, end = sorted(rng.integers(0, size, 2).tolist())
            got = svc.submit({"op": "count", "path": path, "start": start,
                              "end": end}).result(timeout=120)
            lo, hi = oracle.flat_range(index, start, end)
            want = oracle.ranged_count(index, start, end)
            if not (got["ok"] and got["count"] == want
                    == int(verdict[lo:hi].sum()) and not got["escaped"]):
                wrong.append((path, start, end, got, want))
            peaks.append(svc.segments.resident)

    try:
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = svc.stats()
    finally:
        svc.close()
    assert not wrong, wrong[:3]
    seen = counters(registry)
    assert seen["serve.segment_evictions"] > 0
    assert seen["serve.segment_misses"] > 4  # more than the files
    # The guarantee: the budget, and what the requests in flight hold (four
    # workers; a request over a whole file holds all its segments).
    most = budget + 4 * whole * SEGMENT_MOST
    assert max(peaks) <= svc.segments.peak <= most
    assert stats["flat_resident_peak_bytes"] == svc.segments.peak
    # With nothing in flight, the budget alone (or the one newest segment).
    assert stats["flat_resident_bytes"] <= max(budget, SEGMENT_MOST)
    assert stats["files_resident"] == 4  # files open: none of them evicted
    assert stats["segments_resident"] == len(svc.segments) <= 2


# ------------------------------------------ (c) one inflate, whoever waits


def test_two_requests_on_one_cold_segment_inflate_it_once(
        cohort, registry, monkeypatch):
    path, index, verdict = cohort[1]
    entered, gate = threading.Event(), threading.Event()
    real = service_mod._FileState.inflate

    def slow(self, i, j):
        entered.set()
        assert gate.wait(60)
        return real(self, i, j)

    monkeypatch.setattr(service_mod._FileState, "inflate", slow)
    svc = service()
    try:
        svc.file_state(path)
        # Member 0, flat to 65,280: rows 0 to 7, segment 0 alone.
        start, end = (int(index["block_starts"][k]) for k in (0, 1))
        req = {"op": "count", "path": path, "start": start, "end": end}
        first = svc.submit(dict(req))
        assert entered.wait(60)
        second = svc.submit(dict(req))
        deadline = time.monotonic() + 60
        while (counters(registry).get("serve.segment_waits", 0) < 1
               and time.monotonic() < deadline):
            time.sleep(0.01)
        gate.set()
        a, b = first.result(timeout=120), second.result(timeout=120)
    finally:
        gate.set()
        svc.close()
    lo, hi = int(index["header_end"]), int(index["block_flat"][1])
    assert a["count"] == b["count"] == int(verdict[lo:hi].sum()) > 0
    seen = counters(registry)
    assert seen["serve.segment_misses"] == 1
    assert seen["serve.segment_waits"] == 1
    assert seen.get("serve.segment_hits", 0) == 0
    hists = {h["name"]: h for h in registry.snapshot()["hists"]}
    assert hists["serve.segment_inflate"]["count"] == 1
    assert hists["serve.file_open"]["count"] == 1
    assert hists["serve.worker_wait_ms"]["count"] == 2
    assert hists["serve.flat_resident_mib"]["count"] == 1


def test_an_inflate_that_fails_fails_its_requests_and_is_tried_again(
        cohort, monkeypatch):
    path, index, _ = cohort[2]
    real, calls = service_mod._FileState.inflate, []

    def broken(self, i, j):
        calls.append((i, j))
        if len(calls) == 1:
            raise OSError("the disk went away")
        return real(self, i, j)

    monkeypatch.setattr(service_mod._FileState, "inflate", broken)
    svc = service()
    try:
        req = {"op": "count", "path": path}
        bad = svc.submit(dict(req)).result(timeout=120)
        assert not bad["ok"] and "disk went away" in bad["message"]
        assert svc.segments.resident == 0 and len(svc.segments) == 0
        good = svc.submit(dict(req)).result(timeout=120)
    finally:
        svc.close()
    assert good["ok"] and good["count"] == len(index["record_starts"])


# ------------------------------------------------- (d) a file that changed


def test_a_file_rewritten_answers_for_its_new_bytes(tmp_path, registry):
    path = tmp_path / "sample.bam"
    old = write(path, 5)
    svc = service()
    try:
        req = {"op": "count", "path": str(path)}
        assert svc.submit(dict(req)).result(timeout=120)["count"] == len(
            old["record_starts"])
        first = svc.file_state(str(path))
        assert len(svc.segments) == segments_of(old["uncompressed_bytes"])
        assert svc.segments.resident >= old["uncompressed_bytes"]
        new = write(path, 6, size=SIZE // 2)
        assert len(new["record_starts"]) != len(old["record_starts"])
        assert svc.submit(dict(req)).result(timeout=120)["count"] == len(
            new["record_starts"])
        assert svc.file_state(str(path)) is not first
        assert svc.stats()["files_resident"] == 1
        # The old file's segments went with its tables.
        assert len(svc.segments) == segments_of(new["uncompressed_bytes"])
        assert svc.segments.resident < old["uncompressed_bytes"]
    finally:
        svc.close()
    hists = {h["name"]: h for h in registry.snapshot()["hists"]}
    assert hists["serve.file_open"]["count"] == 2


# ------------------------------------ (e) the ops that need a file whole


def _answers(svc: SplitService, op: str, path: str, other: str) -> dict:
    req = {
        "batch": {"op": "batch", "path": path, "columns": "flag,pos",
                  "batch_rows": 512},
        "aggregate": {"op": "aggregate", "path": path, "agg": "flagstat"},
        "record_starts": {"op": "record_starts", "path": path, "limit": 7},
        "fleet": {"op": "fleet", "paths": [path, other]},
    }[op]
    out = []
    for _ in range(2):  # cold, then as warm as the budget lets it be
        resp = svc.submit(dict(req)).result(timeout=120)
        assert resp["ok"], resp
        resp.pop("id", None)
        out.append(resp)
    assert out[0] == out[1]
    return out[0]


@pytest.mark.parametrize("op", ("batch", "aggregate", "record_starts",
                                "fleet"))
def test_whole_file_ops_over_a_file_larger_than_the_budget(op, cohort):
    (path, index, _), (other, other_index, _) = cohort[0], cohort[3]
    budget = 200 << 10
    small, large = service(cache=f"{budget}"), service()
    try:
        got = _answers(small, op, path, other)
        assert got == _answers(large, op, path, other)
        # Its bytes count against the same budget: a whole file is the one
        # newest segment, and anything else went for it.
        assert small.segments.resident <= max(
            budget, index["uncompressed_bytes"])
        assert small.segments.peak >= (
            0 if op == "record_starts" else index["uncompressed_bytes"])
    finally:
        small.close()
        large.close()
    records = len(index["record_starts"])
    if op == "fleet":
        assert got["paths"] == {path: records,
                                other: len(other_index["record_starts"])}
    elif op == "record_starts":
        assert got["count"] == records and len(got["vpos"]) == 7
        flat = index["record_starts"][:7]
        b = np.searchsorted(index["block_flat"], flat, side="right") - 1
        assert got["vpos"] == [
            (int(index["block_starts"][i]) << 16) | int(f - index[
                "block_flat"][i]) for i, f in zip(b, flat)]
    else:
        assert got["rows"] == records


# ------------------------------------------- (f) the files kept open


def test_open_files_are_bounded_and_the_oldest_goes_with_its_segments(
        cohort, registry, monkeypatch):
    monkeypatch.setattr(service_mod, "FILES_OPEN", 2)
    svc = service()
    try:
        for path, index, _ in cohort[:3]:
            got = svc.submit({"op": "count", "path": path}).result(timeout=120)
            assert got["count"] == len(index["record_starts"])
        assert svc.stats()["files_resident"] == 2
        # The first file went, least recently asked for, with its segments.
        assert len(svc.segments) == sum(
            segments_of(index["uncompressed_bytes"])
            for _, index, _ in cohort[1:3])
        path, index, _ = cohort[0]
        again = svc.submit({"op": "count", "path": path}).result(timeout=120)
        assert again["count"] == len(index["record_starts"])
        assert svc.stats()["files_resident"] == 2
    finally:
        svc.close()
    hists = {h["name"]: h for h in registry.snapshot()["hists"]}
    assert hists["serve.file_open"]["count"] == 4


def test_a_path_that_is_gone_leaves_nothing_behind(tmp_path):
    path = tmp_path / "sample.bam"
    index = write(path, 9)
    svc = service()
    try:
        req = {"op": "count", "path": str(path)}
        assert svc.submit(dict(req)).result(timeout=120)["count"] == len(
            index["record_starts"])
        assert len(svc.segments) > 0
        path.unlink()
        gone = svc.submit(dict(req)).result(timeout=120)
        assert not gone["ok"] and gone["error"] == "NotFound"
        stats = svc.stats()
        assert (stats["files_resident"], stats["segments_resident"],
                stats["flat_resident_bytes"]) == (0, 0, 0)
    finally:
        svc.close()


# ------------------------------------------- (g) positions by the tables


def test_positions_of_many_flat_offsets_are_those_of_each():
    from spark_bam_tpu.bgzf.flat import pos_of_flat_tables

    starts = np.array([0, 120, 300, 301], dtype=np.int64)
    flat = np.array([0, 1000, 2500, 2500 + 65280], dtype=np.int64)
    asked = np.array([0, 999, 1000, 2499, 2500, 70000], dtype=np.int64)
    blocks, offs = pos_of_flat_tables(starts, flat, asked)
    assert list(zip(blocks.tolist(), offs.tolist())) == [
        pos_of_flat_tables(starts, flat, int(f)) for f in asked]
    assert pos_of_flat_tables(starts, flat, 1000) == (120, 0)
    none = pos_of_flat_tables(starts, flat, asked[:0])
    assert len(none[0]) == len(none[1]) == 0


# --------------------------------------------------- what did not change


def test_no_whole_file_view_and_no_new_knob():
    source = (ROOT / "spark_bam_tpu" / "serve" / "service.py").read_text()
    assert "flatten_file" not in source
    assert "os.environ" not in source and "getenv" not in source
    assert [f.name for f in dataclasses.fields(ServeConfig)] == [
        "batch_rows", "tick_ms", "plan_queue", "scan_queue", "workers",
        "window", "halo", "flat_cache", "shm", "shm_bytes", "shm_wait_ms"]
    assert ServeConfig().flat_cache == 256 << 20
    assert ServeConfig.parse("cache=64MB").flat_cache == 64 << 20
