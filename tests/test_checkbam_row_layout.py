"""How the every-position steps are fed (``_ShardedStream._assemble_rows``,
the count's assembly with a truth beside the rows): check-bam's and
full-check's rows inflated side by side, each into its own device's operand,
the truth of a row filled beside it, every device's operands put straight to
that device. Checked on the operands themselves, on the CPU's virtual
devices, over a file of five rows (the last one short) at step widths that
leave a last step with a device that holds no live row; then on the answers:
``check_bam_sharded`` against the eager per-position reference and
``full_check_summary_sharded`` against the one-device streaming summary, at
every width; then on the spans a pass emits.
"""

from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest

from spark_bam_tpu.core.channel import open_channel
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.parallel import stream_mesh
from spark_bam_tpu.parallel.mesh import make_mesh
from spark_bam_tpu.parallel.stream_mesh import (
    _ShardedStream, _Truth, check_bam_sharded,
    full_check_summary_sharded,
)
from spark_bam_tpu.tpu.checker import PAD
from tests.test_check_bam_tpu import (  # noqa: F401  (``bam``: a fixture)
    CFG, HALO, WINDOW, assert_same, bam, expected, perturbed, write_sidecar,
)
from tests.test_host_fed_count import _observed, _traced

ROWS = 5
#: ``(devices, rows a device a step)``: one row a chip as the benchmark's
#: four-chip cell runs, three on one chip as its one-chip cell does, and
#: widths at which a step's rows are dealt round-robin over the devices.
WIDTHS = [(1, 1), (1, 3), (2, 1), (2, 2), (4, 1), (4, 2)]


def _mesh(devices: int):
    return make_mesh(jax.devices("cpu")[:devices])


def _stream(path, devices: int, per_dev: int, workload: str):
    probe = _ShardedStream(path, Config(), _mesh(devices), WINDOW, HALO, None)
    width = probe.kernel_window + PAD
    return _ShardedStream(
        path, Config(), _mesh(devices), WINDOW, HALO, None,
        workload=workload, chunk_bytes=per_dev * devices * width)


@pytest.mark.parametrize("workload", ["check_bam", "full_check"])
@pytest.mark.parametrize("devices,per_dev", WIDTHS)
def test_every_device_receives_its_own_rows_and_their_truth(
        bam, devices, per_dev, workload, tmp_path):
    path, index, _verdict = bam
    truth_flats, _, _ = perturbed(index)
    with_truth = workload == "check_bam"
    st = _stream(path, devices, per_dev, workload)
    kw, width = st.kernel_window, st.kernel_window + PAD
    assert len(st.groups) == ROWS and st.n_local == devices
    assert st.step_rows_local == min(
        devices * per_dev, -(-ROWS // devices) * devices)
    per_dev = st.step_rows_local // devices
    steps = list(range(0, st.per_proc, st.step_rows_local))
    idle = set()  # devices that held no live row in some step
    loaded = None
    if with_truth:
        write_sidecar(index, truth_flats, tmp_path / "wrong.records")
        loaded = _Truth(path, tmp_path / "wrong.records", st.metas)

    seen = []
    with open_channel(path) as ch, ThreadPoolExecutor(4) as pool:
        for c0 in steps:
            slots = st.row_slots(c0)
            args, _blocks = st._assemble_rows(ch, c0, pool, loaded)
            assert len(args) == (8 if with_truth else 7)
            windows, ns, eofs = args[:3]
            los, owns = args[-4:-2]
            assert windows.shape == (devices * per_dev, width)
            assert windows.sharding.is_equivalent_to(st.row_sharding, 2)
            for d, shard in enumerate(windows.addressable_shards):
                assert shard.device == st.local_devices[d]
                assert shard.data.shape == (per_dev, width)
            if with_truth:
                truth = args[3]
                assert truth.shape == (devices * per_dev, kw)
                assert truth.dtype == bool
                for d, shard in enumerate(truth.addressable_shards):
                    assert shard.device == st.local_devices[d]
                truth = np.asarray(truth)
            rows = np.asarray(windows)
            ns, eofs, los, owns = map(np.asarray, (ns, eofs, los, owns))
            filled = set()
            for g, d, s in slots:
                i = d * per_dev + s
                assert st.step_row(c0, i) == g
                # What inflating the row on its own gives, and its truth.
                buf, n, at_eof = st._row(ch, g)
                np.testing.assert_array_equal(rows[i, :n], buf)
                assert not rows[i, n:].any()
                own, lo = st._row_span(g, n, at_eof, False)
                assert lo == 0  # header bytes included
                assert (ns[i], eofs[i], los[i], owns[i]) == (
                    n, at_eof, lo, own)
                assert at_eof == (g == ROWS - 1)
                if with_truth:
                    base = int(st.flat_starts[g])
                    mine = truth_flats[(truth_flats >= base)
                                       & (truth_flats < base + n)] - base
                    np.testing.assert_array_equal(
                        np.flatnonzero(truth[i]), mine)
                filled.add(i)
                seen.append(g)
            for i in set(range(devices * per_dev)) - filled:
                # A padding slot is zeros, owns nothing, and is no row.
                assert not rows[i].any()
                assert (ns[i], los[i], owns[i]) == (0, 0, 0)
                assert not with_truth or not truth[i].any()
                g = st.step_row(c0, i)
                assert g >= ROWS or g - c0 >= st.per_proc
            idle |= set(range(devices)) - {d for _g, d, _s in slots}
    if loaded is not None:
        loaded.close()
    assert seen == list(range(ROWS))  # every row once, in the file's order
    short = st._row_span(ROWS - 1, 0, False, False)[0]
    assert 0 < short < int(st.sizes[0])  # the last row is the short one
    # A device with no live row keeps resident zero operands, one set.
    assert set(st._zero_rows) == idle
    # Three of the six widths leave their last step a device with no live
    # row: five rows dealt over two devices, or one a step over four.
    assert bool(idle) == ((devices, per_dev) in ((2, 1), (2, 2), (4, 1)))


@pytest.fixture(scope="module")
def answers(bam, tmp_path_factory):
    """The sidecar that is wrong in known places, the reference's answer to
    it, and a place for each width's answer to be held against the others."""
    path, index, verdict = bam
    truth, dropped, added = perturbed(index)
    sidecar = tmp_path_factory.mktemp("layout") / "wrong.records"
    write_sidecar(index, truth, sidecar)
    want = expected(verdict, truth)
    assert np.array_equal(want["false_positive_positions"], dropped)
    assert np.array_equal(want["false_negative_positions"], added)
    return sidecar, want, {}


@pytest.mark.parametrize("devices,per_dev", WIDTHS)
def test_check_bam_equals_the_eager_reference_at_every_width(
        bam, answers, devices, per_dev, monkeypatch):
    path, _index, _verdict = bam
    sidecar, want, got_at = answers
    monkeypatch.setattr(
        stream_mesh, "_rows_fitting_device", lambda *_: per_dev)
    got, counters, hists = _observed(lambda: check_bam_sharded(
        path, Config(), mesh=_mesh(devices), records_path=sidecar, **CFG))
    width = min(devices * per_dev, -(-ROWS // devices) * devices)
    steps = -(-(-(-ROWS // devices) * devices) // width)
    assert counters["mesh.steps"] == steps
    assert counters["mesh.rows"] == ROWS
    # One span a live row on the pool's threads, one a step around them.
    assert hists["mesh.row_inflate"] == hists["mesh.truth_fill"] == ROWS
    assert hists["inflate.window"] == ROWS
    assert hists["mesh.assemble"] == hists["mesh.h2d"] == steps
    for name in ("mesh.dirty_steps", "checkbam.list_overflows",
                 "check.fused_demotions", "mesh.patch_rows"):
        assert not counters.get(name), name
    got_at[devices, per_dev] = {
        k: v.tolist() if hasattr(v, "tolist") else v for k, v in got.items()
        if k != "devices"}
    assert_same(got, want, devices)
    # And each width equals the others, lists included.
    first = next(iter(got_at.values()))
    assert got_at[devices, per_dev] == first


@pytest.fixture(scope="module")
def full_check_files(bam, tmp_path_factory):
    """``{name: (path, window and halo, rows, the one-device streaming
    summary)}``: the short-read file of the other tests, and a file of
    mixed records whose two-check sites lie in every row, so that the order
    of the sites is held too."""
    from bam_factories import random_bam
    from spark_bam_tpu.tpu.stream_check import full_check_summary_streaming

    mixed = tmp_path_factory.mktemp("sited") / "mixed.bam"
    random_bam(mixed, seed=3, n_records=(200, 400), read_len=(10, 6000),
               mapped_rate=0.7)
    small = dict(window_uncompressed=256 << 10, halo=128 << 10)
    return {
        name: (path, cfg, rows,
               full_check_summary_streaming(path, Config(), **cfg))
        for name, path, cfg, rows in (
            ("short", bam[0], CFG, ROWS), ("mixed", str(mixed), small, 7))
    }


@pytest.mark.parametrize("file", ["short", "mixed"])
@pytest.mark.parametrize("devices,per_dev", WIDTHS)
def test_full_check_is_unchanged_at_every_width(
        full_check_files, file, devices, per_dev, monkeypatch):
    path, cfg, rows, b = full_check_files[file]
    monkeypatch.setattr(
        stream_mesh, "_rows_fitting_device", lambda *_: per_dev)
    stats: dict = {}
    a, counters, hists = _observed(lambda: full_check_summary_sharded(
        path, Config(), mesh=_mesh(devices), stats_out=stats, **cfg))
    assert a.pop("devices") == devices and not stats["fallback"]
    assert not stats["defers"] and not stats["patched_steps"]
    assert a["per_flag"] == b["per_flag"]
    assert a["considered"] == b["considered"]
    assert a["positions"] == b["positions"]
    for key in ("critical_positions", "critical_masks",
                "two_check_positions", "two_check_masks"):
        np.testing.assert_array_equal(a[key], b[key])
    # Sites in the file's order, whatever order the step's rows lie in.
    sites = a["two_check_positions"]
    assert np.all(np.diff(sites) > 0)
    if file == "mixed":
        window = cfg["window_uncompressed"]
        assert len(np.unique(sites // window)) == rows
    # The same assembly, and no truth beside the rows.
    assert hists["mesh.row_inflate"] == counters["mesh.rows"] == rows
    assert "mesh.truth_fill" not in hists


def test_a_pass_emits_a_span_a_live_row_inside_its_own_trace(bam, tmp_path):
    """``check_bam_tpu`` twice: each pass's trace holds one
    ``mesh.row_inflate`` and one ``mesh.truth_fill`` a row, from the pool's
    threads, each under the step's ``mesh.assemble`` with the row's
    ``inflate.window`` inside the former."""
    from spark_bam_tpu.load.tpu_load import check_bam_tpu

    path, index, verdict = bam
    truth, _, _ = perturbed(index, seed=12)
    local = tmp_path / "short.bam"
    local.symlink_to(path)
    write_sidecar(index, truth, str(local) + ".records")
    config = Config(window_size=WINDOW, halo_size=HALO)
    values, events, hists = _traced(lambda: check_bam_tpu(local, config))
    for got in values:
        assert_same(got, expected(verdict, truth), jax.local_device_count())
    assert hists["mesh.row_inflate"] == hists["mesh.truth_fill"] == 2 * ROWS
    roots = [e for e in events if e["name"] == "load.check_bam"]
    assert len(roots) == 2
    for root in roots:
        mine = {e["span"]: e for e in events if e["trace"] == root["trace"]}
        for name in ("mesh.row_inflate", "mesh.truth_fill"):
            spans = [e for e in mine.values() if e["name"] == name]
            assert sorted(e["attrs"]["row"] for e in spans) == list(
                range(ROWS)), name
            for e in spans:
                assert mine[e["pspan"]]["name"] == "mesh.assemble"
        inflates = [e for e in mine.values() if e["name"] == "inflate.window"]
        assert len(inflates) == ROWS
        assert {mine[e["pspan"]]["name"] for e in inflates} == {
            "mesh.row_inflate"}
