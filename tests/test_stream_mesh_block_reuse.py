"""The host blocks of the mesh steps are kept, not made anew
(``_ShardedStream._assemble_rows`` takes a device's blocks from
``tpu/inflate.FRAMES`` and ``_steps`` / ``release`` hand them back): what a
block held in its earlier step must not show in the next, a block must not be
written again while the step that reads it is unread, and the kept set must
engage and stay within its bound. On the CPU's virtual devices, where a put
may alias its host block; every answer is held to the same pass in a kept set
of its own, and the count's to the file's index.
"""

import threading
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np
import pytest

from spark_bam_tpu import obs
from spark_bam_tpu.core.channel import open_channel
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.parallel import stream_mesh
from spark_bam_tpu.parallel.mesh import mesh_steps
from spark_bam_tpu.parallel.stream_mesh import (
    _ShardedStream, _Truth, check_bam_sharded, count_reads_sharded,
    full_check_summary_sharded,
)
from spark_bam_tpu.tpu import inflate
from spark_bam_tpu.tpu.checker import PAD
from tests.test_host_fed_count import _generate, _mesh

MEMBER = 0xFF00
WINDOW, HALO = 2 * MEMBER, 32 << 10  # rows of two members
CFG = dict(window_uncompressed=WINDOW, halo=HALO)
#: ``(devices, rows a device a step)``.
WIDTHS = [(1, 1), (1, 3), (2, 1), (2, 2), (4, 1)]


def _wrong_truth(index: dict, seed: int) -> np.ndarray:
    """The index's record starts, four dropped and three positions added."""
    rng = np.random.default_rng(seed)
    records = index["record_starts"]
    free = np.setdiff1d(
        np.arange(index["header_end"], index["uncompressed_bytes"]), records)
    kept = np.setdiff1d(records, rng.choice(records, 4, replace=False))
    return np.sort(np.concatenate([kept, rng.choice(free, 3, replace=False)]))


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    """``{name: (path, index, sidecar)}``: ``x`` of five rows, and ``y`` of
    three, whose last row is short where ``x`` had a full one, whose last
    step is narrower, and whose truth is wrong in other places."""
    from bench.oracle_checkbam import sidecar_text

    out = {}
    for name, seed, size, rows in (("x", 7, 600_000, 5), ("y", 8, 330_000, 3)):
        path = tmp_path_factory.mktemp(name) / "file.bam"
        index = _generate("wgs-short", seed, size, path)
        assert -(-index["uncompressed_bytes"] // WINDOW) == rows
        sidecar = Path(str(path) + ".wrong.records")
        sidecar.write_text(sidecar_text(index, _wrong_truth(index, seed)))
        out[name] = (path, index, sidecar)
    return out


@pytest.fixture()
def kept(monkeypatch):
    """A kept set of the test's own, so that no other test's blocks show."""
    own = inflate._Frames()
    monkeypatch.setattr(inflate, "FRAMES", own)
    return own


def _pass(workload: str, file, devices: int):
    path, _index, sidecar = file
    if workload == "count":
        return count_reads_sharded(path, Config(), mesh=_mesh(devices), **CFG)
    if workload == "check_bam":
        return check_bam_sharded(
            path, Config(), mesh=_mesh(devices), records_path=sidecar, **CFG)
    stats: dict = {}
    out = full_check_summary_sharded(
        path, Config(), mesh=_mesh(devices), stats_out=stats, **CFG)
    assert not stats["fallback"] and not stats["patched_steps"]
    return out


def _plain(answer):
    if isinstance(answer, dict):
        return {k: v.tolist() if hasattr(v, "tolist") else v
                for k, v in answer.items()}
    return answer


def _reuse(snap: dict) -> list:
    return [v for h in snap["hists"] if h["name"] == "mesh.block_reuse"
            for v in h["values"]]


@pytest.mark.parametrize("workload", ["count", "check_bam", "full_check"])
@pytest.mark.parametrize("devices,per_dev", WIDTHS)
def test_nothing_leaks_from_pass_to_pass(
        files, workload, devices, per_dev, monkeypatch):
    """x, then y in x's blocks, then x in y's: each pass answers as it does
    in a kept set of its own, and every step of the later passes was
    assembled in kept blocks."""
    monkeypatch.setattr(
        stream_mesh, "_rows_fitting_device", lambda *_: per_dev)
    want = {}
    for name in "xy":
        monkeypatch.setattr(inflate, "FRAMES", inflate._Frames())
        want[name] = _plain(_pass(workload, files[name], devices))
    if workload == "count":
        assert want == {
            n: len(files[n][1]["record_starts"]) for n in "xy"}
    else:
        assert want["x"]["positions"] == files["x"][1]["uncompressed_bytes"]
    if workload == "check_bam":
        for name in "xy":
            assert want[name]["false_positives"] == 4
            assert want[name]["false_negatives"] == 3
    own = inflate._Frames()
    monkeypatch.setattr(inflate, "FRAMES", own)
    assert _plain(_pass(workload, files["x"], devices)) == want["x"]
    obs.shutdown()
    obs.configure()
    try:
        for name in "yxy":
            assert _plain(_pass(workload, files[name], devices)) == want[name]
            assert 0 < len(own._free) <= 3 * devices
        snap = obs.registry().snapshot()
    finally:
        obs.shutdown()
    steps = {c["name"]: c["value"] for c in snap["counters"]}["mesh.steps"]
    assert _reuse(snap) == [1.0] * steps  # x's first pass left enough


def test_a_padding_slot_that_was_live_is_zeros(files, kept):
    """Two devices, two rows a device a step: x's last step is its fifth row
    alone, in a block whose slots both held rows; the other slot is handed to
    the program as zeros that own nothing, row and truth."""
    path, index, sidecar = files["x"]
    truth_flats = _wrong_truth(index, 7)  # what the sidecar holds
    probe = _ShardedStream(path, Config(), _mesh(2), WINDOW, HALO, None)
    width = probe.kernel_window + PAD
    st = _ShardedStream(
        path, Config(), _mesh(2), WINDOW, HALO, None, workload="check_bam",
        chunk_bytes=4 * width)
    assert st.step_rows_local == 4
    loaded = _Truth(path, sidecar, st.metas)
    with open_channel(path) as ch, ThreadPoolExecutor(4) as pool:
        args, blocks = st._assemble_rows(ch, 0, pool, loaded)
        assert all(np.asarray(args[3]).any(axis=1))  # truth in every slot
        assert len(blocks) == 2 and not kept._free
        for arrays, used in blocks:
            assert all(len(u) for u in used)
            kept.give(arrays, keep=6, note=used)
        args, blocks = st._assemble_rows(ch, 4, pool, loaded)
    loaded.close()
    assert st.row_slots(4) == [(4, 0, 0)]
    (arrays, used), = blocks
    assert used[1] is None and len(used[0])
    assert len(kept._free) == 1  # device 1 keeps resident zeros, made anew
    windows, ns, eofs, truth, los, owns = map(np.asarray, args[:6])
    assert ns.tolist()[1:] == [0, 0, 0] and ns[0] > 0
    assert owns.tolist()[1:] == [0, 0, 0] and los.tolist() == [0, 0, 0, 0]
    assert not windows[1:].any() and not truth[1:].any()
    # The live slot: the row, zeros behind it, and its own truth alone.
    base = int(st.flat_starts[4])
    mine = truth_flats[(truth_flats >= base)
                       & (truth_flats < base + ns[0])] - base
    np.testing.assert_array_equal(np.flatnonzero(truth[0]), mine)
    assert windows[0, :ns[0]].any() and not windows[0, ns[0]:].any()


class _Watched(inflate._Frames):
    """A kept set that says which arrays each ``take`` handed out."""

    def __init__(self):
        super().__init__()
        self.taken: list = []

    def take(self, specs, make=None):
        arrays, note = super().take(specs, make)
        self.taken.append(([id(a) for a in arrays], note is not None))
        return arrays, note


def test_no_block_is_rewritten_while_its_step_is_unread(files, monkeypatch):
    """Five steps of one row on one device, the totals read one step late as
    the count reads them, a slow reader besides: every step's count is the
    index's for the span its row owns, and a step is never assembled in the
    blocks of the two steps before it."""
    path, index, _sidecar = files["x"]
    starts = np.asarray(index["record_starts"])
    watched = _Watched()
    monkeypatch.setattr(inflate, "FRAMES", watched)
    config = Config()
    for _ in range(2):
        st = _ShardedStream(
            path, config, _mesh(1), WINDOW, HALO, None, chunk_bytes=1)
        assert st.step_rows_local == 1 and len(st.groups) == 5
        step = mesh_steps(st.mesh, st.axis).count_step(
            reads_to_check=config.reads_to_check,
            funnel=config.funnel_enabled())

        def settle(out, c0):
            count, escapes, _survivors, _lanes = np.asarray(out).tolist()
            lo = int(st.flat_starts[c0])
            if c0 == 0:
                lo = index["header_end"]
            hi = int(st.flat_starts[c0] + st.sizes[c0])
            want = int(np.searchsorted(starts, hi)
                       - np.searchsorted(starts, lo))
            assert (count, escapes) == (want, 0), f"row {c0}"

        unread = None
        batches = st.row_batches()
        try:
            for args, _done, c0 in batches:
                out = step(*args)
                # While this step runs, the assembly thread is writing the
                # next one's blocks.
                threading.Event().wait(0.05)
                if unread is not None:
                    settle(*unread)
                unread = (out, c0)
            settle(*unread)
        finally:
            batches.close()
        st.release()
    ids = [t[0] for t in watched.taken]
    assert len(ids) == 10
    for k in range(10):
        for back in (1, 2):
            if k - back >= 0 and k // 5 == (k - back) // 5:
                assert not set(ids[k]) & set(ids[k - back]), (k, back)
    # Three steps' blocks made, and no more, over both passes.
    assert [kept for _ids, kept in watched.taken] == (
        [False] * 3 + [True] * 7)
    assert len({i for t in ids for i in t}) == 3
    assert len(watched._free) == 3


def test_it_engages_and_stays_within_its_bound(files, kept, monkeypatch):
    """``mesh.block_reuse`` reads 0 on the first steps of a fresh process's
    first pass and 1.0 on every step of its second; the kept set holds three
    steps' blocks a device at most, and after a pass at another width none
    of the width before."""
    path, _index, sidecar = files["x"]
    monkeypatch.setattr(stream_mesh, "_rows_fitting_device", lambda *_: 1)

    def observed_pass():
        obs.shutdown()
        obs.configure()
        try:
            check_bam_sharded(
                path, Config(), mesh=_mesh(1), records_path=sidecar, **CFG)
            return _reuse(obs.registry().snapshot())
        finally:
            obs.shutdown()

    assert observed_pass() == [0.0, 0.0, 0.0, 1.0, 1.0]
    assert len(kept._free) == 3
    narrow = kept._free[0][0]
    assert [s for s, _d in narrow] == [
        (262144 + PAD,), (1, 262144)]
    assert observed_pass() == [1.0] * 5
    assert len(kept._free) == 3
    # Two devices, one row each: blocks of the same shapes, six kept at most.
    for _ in range(2):
        count_reads_sharded(path, Config(), mesh=_mesh(2), **CFG)
        assert 0 < len(kept._free) <= 6
        assert all(len(key) == 1 for key, _a, _n in kept._free)  # no truth
    # Another width: what the passes before it left goes.
    monkeypatch.setattr(stream_mesh, "_rows_fitting_device", lambda *_: 3)
    check_bam_sharded(
        path, Config(), mesh=_mesh(1), records_path=sidecar, **CFG)
    assert 0 < len(kept._free) <= 3
    assert {key[0][0] for key, _a, _n in kept._free} == {
        (3 * (262144 + PAD),)}
