"""The whole-file count on every backend: windows and rows inflated on the
host, the device only checking them (``jit_count_window``,
``jit_count_step``), exact against the files' own index and the NumPy
engine, and measured under the names the benchmark reads.

Files come from ``bench/generators`` with their own index and from
``tests/bam_factories``; ``Config()`` is what a TPU process passes too, so
the TPU cases only patch what the process observes
(``jax.default_backend``) and must take the same path.
"""

import numpy as np
import pytest

import jax

from spark_bam_tpu import obs
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.obs.names import NAMES, layer_of
from spark_bam_tpu.parallel.mesh import make_mesh, mesh_steps
from spark_bam_tpu.parallel.stream_mesh import (
    _ShardedStream, count_reads_sharded,
)
from spark_bam_tpu.tpu.checker import PAD, lane_block, lane_capacity
from spark_bam_tpu.tpu.stream_check import StreamChecker

MEMBER = 0xFF00  # htslib's payload: what the generators fill every member to

#: Each a path that left the device, or a kernel that misjudged a read.
DEMOTIONS = (
    "check.fused_demotions", "agg.host_fallbacks",
    "check.count_escape_retries",
)


def _generate(name: str, seed: int, size: int, path):
    from bench.tests.conftest import generate  # the benchmark's own helper

    return generate(name, seed, path, size)[0]


@pytest.fixture(scope="module", params=["wgs-short", "longread-hifi"])
def generated(request, tmp_path_factory):
    path = tmp_path_factory.mktemp(request.param) / "file.bam"
    return path, _generate(request.param, 2 ** 31 + 31, 3 << 20, path)


@pytest.fixture(scope="module")
def short48(tmp_path_factory):
    """Short reads in exactly 48 members: 8 rows of 6, 16 rows of 3."""
    path = tmp_path_factory.mktemp("short48") / "file.bam"
    index = _generate("wgs-short", 2 ** 31 + 33, 47 * MEMBER + 20_000, path)
    assert 47 * MEMBER < index["uncompressed_bytes"] <= 48 * MEMBER
    return path, index


def _observed(run):
    """``(run()'s value, counters, histogram counts)`` under a live registry."""
    obs.shutdown()
    obs.configure()
    try:
        value = run()
        snap = obs.registry().snapshot()
    finally:
        obs.shutdown()
    counters = {c["name"]: c["value"] for c in snap["counters"]}
    return value, counters, _hist_counts(snap)


def _hist_counts(snap: dict) -> dict:
    hists: dict = {}
    for h in snap["hists"]:
        hists[h["name"]] = hists.get(h["name"], 0) + h["count"]
    return hists


def _traced(run, passes: int = 2):
    """``run()`` ``passes`` times under one live registry: ``(values, span
    events, histogram counts)``."""
    obs.shutdown()
    obs.configure()
    try:
        values = [run() for _ in range(passes)]
        events = obs.registry().events()
        snap = obs.registry().snapshot()
    finally:
        obs.shutdown()
    return values, events, _hist_counts(snap)


def assert_one_trace_a_pass(events: list, hists: dict, root: str,
                            passes: int, phases: set, threads: set) -> None:
    """What ``obs.pass_span`` promises of ``passes`` passes: every span
    event carries the trace of exactly one root, those of the pool and
    assembly threads (``threads``) too, each under a span of its own pass;
    the phases of the head and the tail are there, once a pass at least;
    the pass's own account is observed once a pass."""
    roots = [e for e in events if e["name"] == root]
    assert len(roots) == passes
    traces = [e["trace"] for e in roots]
    assert len(set(traces)) == passes
    assert all("pspan" not in e for e in roots)
    for trace in traces:
        mine = [e for e in events if e.get("trace") == trace]
        ids = {e["span"] for e in mine}
        names = {e["name"] for e in mine}
        assert phases | threads <= names, (phases | threads) - names
        assert all(e["pspan"] in ids for e in mine if e["name"] != root)
    # Every span of the PROGRAM: what the witness of the host sees between
    # two passes (a stop, a full collection) is an event in no trace.
    assert all(e.get("trace") in traces for e in events
               if layer_of(e["name"]) != "host")
    assert hists["load.head_ms"] == hists["load.drain_ms"] == passes
    assert {e["name"] for e in events} <= NAMES


def _assert_host_fed(counters: dict, hists: dict) -> None:
    for name in DEMOTIONS:
        assert not counters.get(name), name
    # Nothing is emitted under a name the catalogue no longer has.
    assert set(counters) | set(hists) <= NAMES


def _assert_lanes_sized_by_survivors(counters: dict, stages: int,
                                     kernel_window: int) -> None:
    """The funnel's evidence over ``stages`` lane stages (windows or rows):
    whole blocks that hold the survivors, fewer than the ``w // 32`` lanes
    a stage each would run at its full capacity."""
    survivors, lanes = counters["funnel.survivors"], counters["funnel.lanes"]
    assert 0 < survivors <= lanes
    assert lanes % lane_block(kernel_window) == 0
    assert lanes < stages * lane_capacity(kernel_window)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_one_device_count_is_host_fed_and_measured(
        generated, backend, monkeypatch):
    path, index = generated
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    checker = StreamChecker(path, Config())
    got, counters, hists = _observed(checker.count_reads)
    assert got == len(index["record_starts"])
    _assert_host_fed(counters, hists)
    windows = counters["check.windows"]
    assert windows == len(checker.pipeline.groups) == 1
    # One thread span a window (and the one that finds the stream's end),
    # the wait for the host inflate inside it, one put, one dispatch.
    assert hists["check.window"] == windows + 1
    assert hists["inflate.stall_ms"] == windows
    assert hists["inflate.h2d"] == hists["inflate.device_kernel"] == windows
    assert hists["check.flush"] == 1
    assert counters["inflate.h2d_bytes"] == windows * (
        checker.kernel_window + PAD)
    # The observer's, off the feeding thread: every window handed over.
    assert hists["inflate.device_ms"] == hists["inflate.h2d_ms"] == windows
    # The host inflater's own evidence: every byte of the file, once.
    assert counters["inflate.bytes"] == index["uncompressed_bytes"]
    assert counters["inflate.windows"] == windows
    # The funnel screened every byte and ran the lanes its survivors need.
    assert counters["funnel.positions"] == index["uncompressed_bytes"]
    _assert_lanes_sized_by_survivors(counters, windows, checker.kernel_window)


def test_one_device_count_carries_the_halo_and_paces(short48):
    """Eight windows with a carried halo: the pacing read is on the feeding
    thread under its name, the one flush is the stream's end (the window-4
    checkpoint went when every window's escape count came to be read at
    the pace), and the windows' owned spans still add up to the index."""
    path, index = short48
    config = Config(window_size=6 * MEMBER, halo_size=64 << 10)
    checker = StreamChecker(path, config)
    got, counters, hists = _observed(checker.count_reads)
    assert got == len(index["record_starts"])
    _assert_host_fed(counters, hists)
    assert counters["check.windows"] == counters["inflate.windows"] == 8
    assert counters["inflate.bytes"] == index["uncompressed_bytes"]
    assert hists["check.pace"] == 8 - config.ring_depth
    assert hists["check.flush"] == 1
    assert not counters.get("check.escape_candidates")
    assert hists["inflate.device_ms"] == 8
    # The same count with no registry: the same dispatches and waits.
    assert StreamChecker(path, config).count_reads() == got


def _mesh(n: int = 4):
    return make_mesh(jax.devices("cpu")[:n])


def _rows_a_device(st: _ShardedStream) -> list[list[int]]:
    """Per step, the live rows each local device is given."""
    steps = []
    for c0 in range(0, st.per_proc, st.step_rows_local):
        placed = [0] * st.n_local
        for _g, d, slot in st.row_slots(c0):
            assert slot == placed[d]  # a device's slots fill in order
            placed[d] += 1
        steps.append(placed)
    return steps


@pytest.mark.parametrize("rows_a_device,steps", [(1, 2), (2, 1), (3, 1)])
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_mesh_count_of_eight_rows_lands_two_a_device(
        short48, rows_a_device, steps, backend, monkeypatch):
    """8 rows on 4 devices, at every step width the memory rule could give:
    two rows a device over the pass, the count exact, the steps measured."""
    path, index = short48
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    config = Config(window_size=6 * MEMBER, halo_size=64 << 10)
    probe = _ShardedStream(path, config, _mesh(), None, None, None)
    assert len(probe.groups) == 8
    chunk = rows_a_device * 4 * (probe.kernel_window + PAD)
    st = _ShardedStream(
        path, config, _mesh(), None, None, None, chunk_bytes=chunk)
    plan = _rows_a_device(st)
    assert len(plan) == steps
    assert [sum(step[d] for step in plan) for d in range(4)] == [2] * 4
    assert all(max(step) - min(step) == 0 for step in plan)

    stats: dict = {}
    got, counters, hists = _observed(lambda: count_reads_sharded(
        path, config, mesh=_mesh(), stats_out=stats, chunk_bytes=chunk))
    assert got == len(index["record_starts"])
    assert stats["rows"] == 8 and stats["steps"] == steps
    assert not stats["escapes"] and not stats["fallback"]
    _assert_host_fed(counters, hists)
    assert counters["mesh.steps"] == steps
    assert counters["mesh.rows"] == counters["inflate.windows"] == 8
    assert hists["mesh.assemble"] == hists["mesh.h2d"] == steps
    assert hists["mesh.stall"] == steps
    assert hists["mesh.step"] == steps + 1  # the last totals' own read
    assert hists["mesh.step_device_ms"] == steps
    width = st.step_rows_local // 4 * (st.kernel_window + PAD)
    assert counters["mesh.h2d_bytes"] == steps * 4 * width
    # The file's bytes plus the halo each row re-inflates past its span.
    assert counters["inflate.bytes"] > index["uncompressed_bytes"]
    # The same evidence the one-device stream gives, summed over the rows
    # (each whole blocks of an eighth of a 512 KiB row's capacity).
    _assert_lanes_sized_by_survivors(counters, 8, st.kernel_window)


def test_a_stream_pass_is_one_trace_with_its_own_account(short48):
    """``count_reads_tpu`` on one device, twice: the worker pool's
    ``inflate.window`` spans belong to their pass, the member walk and the
    two ends of the pass lie under spans of their own."""
    from spark_bam_tpu.load.tpu_load import count_reads_tpu

    path, index = short48
    config = Config(window_size=6 * MEMBER, halo_size=64 << 10)
    values, events, hists = _traced(lambda: count_reads_tpu(path, config))
    assert values == [len(index["record_starts"])] * 2
    assert_one_trace_a_pass(
        events, hists, "load.count", 2,
        phases={"load.open", "bgzf.read", "check.window", "check.flush",
                "load.drain"},
        threads={"inflate.window"})
    assert hists["inflate.window"] == 16 and hists["bgzf.read"] == 2
    assert hists["load.open"] == 4  # the file, then the program, a pass


def test_the_report_prints_every_kept_pass_with_its_account_of_the_host(
        short48, tmp_path, capsys, monkeypatch):
    """``metrics-report`` on the JSONL of a three-pass count: a tree a
    pass, the slowest first, ``stop_ms`` / ``gc_ms`` / ``cpu_ms`` on each
    tree's first line; the passes computed, so their CPU time is not 0."""
    from spark_bam_tpu.cli.main import main
    from spark_bam_tpu.load.tpu_load import count_reads_tpu

    monkeypatch.delenv("SPARK_BAM_METRICS_OUT", raising=False)
    path, index = short48
    config = Config(window_size=6 * MEMBER, halo_size=64 << 10)
    trace = tmp_path / "m.jsonl"
    obs.shutdown()
    obs.configure()
    try:
        for _ in range(3):
            assert count_reads_tpu(path, config) == len(index["record_starts"])
        kept = obs.registry().snapshot()["slow_passes"]
        obs.export_jsonl(trace)
    finally:
        obs.shutdown()
    assert [p["root"] for p in kept] == ["load.count"] * 3
    assert kept == sorted(kept, key=lambda p: -p["ms"])
    assert all(p["cpu_ms"] > 0 and p["stop_ms"] >= 0 and p["gc_ms"] >= 0
               for p in kept)
    assert main(["metrics-report", str(trace)]) == 0
    out = capsys.readouterr().out
    heads = [line for line in out.splitlines() if line.startswith("trace ")]
    assert [line.split()[1] for line in heads] == [p["trace"] for p in kept]
    for line, p in zip(heads, kept):
        assert line.endswith(
            f"spans): stop_ms={p['stop_ms']:.3f} gc_ms={p['gc_ms']:.3f}"
            f" cpu_ms={p['cpu_ms']:.3f}")


def test_a_mesh_pass_is_one_trace_with_its_own_account(short48, monkeypatch):
    """The same entry on a host of four chips: the assembly thread's
    ``mesh.assemble`` / ``mesh.h2d`` and its row pool's ``inflate.window``
    belong to their pass; the member walk has the stream's name."""
    from spark_bam_tpu.load import tpu_load
    from spark_bam_tpu.parallel import mesh as mesh_module

    path, index = short48
    monkeypatch.setattr(tpu_load, "counts_across_chips", lambda: True)
    monkeypatch.setattr(mesh_module, "local_mesh", _mesh)
    config = Config(window_size=6 * MEMBER, halo_size=64 << 10)
    values, events, hists = _traced(
        lambda: tpu_load.count_reads_tpu(path, config))
    assert values == [len(index["record_starts"])] * 2
    assert_one_trace_a_pass(
        events, hists, "load.count", 2,
        phases={"load.open", "bgzf.read", "mesh.plan", "mesh.stall",
                "mesh.step", "mesh.dispatch", "load.drain"},
        threads={"mesh.assemble", "mesh.h2d", "inflate.window"})
    assert hists["inflate.window"] == 16 and hists["bgzf.read"] == 2
    assert hists["mesh.plan"] == 2


def test_a_short_last_step_is_dealt_over_the_devices(short48):
    """16 rows in steps 12 wide: the last step's 4 rows land one a device
    (not 3/1/0/0), through the vmapped three-rows-a-device program with its
    padding slots, and the count is exact."""
    path, index = short48
    config = Config(window_size=3 * MEMBER, halo_size=64 << 10)
    probe = _ShardedStream(path, config, _mesh(), None, None, None)
    assert len(probe.groups) == 16
    chunk = 3 * 4 * (probe.kernel_window + PAD)
    st = _ShardedStream(
        path, config, _mesh(), None, None, None, chunk_bytes=chunk)
    assert _rows_a_device(st) == [[3] * 4, [1] * 4]
    stats: dict = {}
    got = count_reads_sharded(
        path, config, mesh=_mesh(), stats_out=stats, chunk_bytes=chunk)
    assert got == len(index["record_starts"])
    assert stats["steps"] == 2 and not stats["escapes"]


def test_mesh_count_of_long_reads_is_host_fed(generated):
    """Both configurations through the default mesh count: records that span
    members and row seams, the halo re-inflated a row."""
    path, index = generated
    config = Config(window_size=1 << 20, halo_size=512 << 10)
    stats: dict = {}
    got, counters, hists = _observed(lambda: count_reads_sharded(
        path, config, mesh=_mesh(), stats_out=stats))
    assert got == len(index["record_starts"])
    assert not stats["escapes"] and not stats["fallback"]
    _assert_host_fed(counters, hists)
    assert counters["mesh.rows"] == stats["rows"] >= 3
    assert hists["mesh.step_device_ms"] == stats["steps"]
    _assert_lanes_sized_by_survivors(counters, stats["rows"], 2 << 20)


# ------------------------------------------- the one-device count is exact

CFG = dict(window_uncompressed=128 << 10, halo=32 << 10)


def _numpy_count(path, **cfg) -> int:
    """The NumPy engine through the same windows: the differential oracle."""
    return StreamChecker(path, Config(), use_device=False, **cfg).count_reads()


def _random(tmp_path, seed, **kw):
    from tests.bam_factories import random_bam

    path = tmp_path / f"f{seed}.bam"
    kw.setdefault("contigs", (("chr1", 5_000_000),))
    random_bam(path, seed, dup_rate=kw.pop("dup_rate", 0.05), **kw)
    return path


def _longread(tmp_path):
    from spark_bam_tpu.benchmarks.synth import synth_longread_bam

    path = tmp_path / "lr.bam"
    synth_longread_bam(
        path, target_bytes=2 << 20, seed=0,
        read_lens=(60_000, 140_000), ultra_seq_len=200_000,
    )
    return path


#: name → (file, Config, window/halo): what the count must get right.
ONE_DEVICE = {
    "seed-0": (lambda t: _random(t, 0), Config(), CFG),
    "seed-1": (lambda t: _random(t, 1), Config(), CFG),
    "seed-2": (lambda t: _random(t, 2), Config(), CFG),
    "funnel-off": (lambda t: _random(t, 13), Config(funnel="off"), CFG),
    # Small windows force many carry seams; two contigs exercise the
    # contig-length table.
    "multi-contig-and-carry": (
        lambda t: _random(
            t, 14, contigs=(("chr1", 5_000_000), ("chr2", 3_000_000)),
            dup_rate=0.1),
        Config(), dict(window_uncompressed=64 << 10, halo=16 << 10)),
    # Chains beyond the halo (long reads, a tiny halo) escape in every
    # window and resolve on the host: never a wrong count, never a pass
    # that starts over.
    "escapes-resolve-on-the-host": (
        _longread, Config(),
        dict(window_uncompressed=256 << 10, halo=16 << 10)),
}


@pytest.mark.parametrize("case", sorted(ONE_DEVICE))
def test_one_device_count_matches_the_numpy_engine(case, tmp_path):
    make, config, cfg = ONE_DEVICE[case]
    path = make(tmp_path)
    run = StreamChecker(path, config, **cfg).count_reads
    got, counters, _hists = _observed(run)
    assert got == _numpy_count(path, **cfg) > 0
    assert not counters.get("check.count_escape_retries")
    # Whatever escaped (the small windows of "multi-contig-and-carry" have
    # a few too) resolved on the host.
    assert (counters.get("check.escape_candidates", 0)
            == counters.get("check.escape_resolved", 0))
    if case == "escapes-resolve-on-the-host":
        assert counters["check.escape_candidates"] >= 1


def test_one_device_count_populates_the_funnel_stats(tmp_path):
    checker = StreamChecker(_random(tmp_path, 16), Config(), **CFG)
    checker.count_reads()
    stats = checker.funnel_stats
    assert stats is not None and stats["screened"] > 0
    assert 0 < stats["survivors"] <= stats["lanes"] <= stats["screened"]


# ------------------------------------------------ the mesh count is exact

#: (configuration, bytes, row window, halo): 9 rows of short reads (a last
#: step with three padding rows) and 6-7 rows of long reads whose 15-38 KB
#: records span members and row seams; the halo covers the checker's ten
#: reads of lookahead in both.
MESH_FILES = {
    "wgs-short": (2 << 20, 256 << 10, 64 << 10),
    "longread-hifi": (6 << 20, 1 << 20, 512 << 10),
}


@pytest.fixture(scope="module", params=sorted(MESH_FILES))
def mesh_file(request, tmp_path_factory):
    size, window, halo = MESH_FILES[request.param]
    path = tmp_path_factory.mktemp(request.param) / "file.bam"
    index = _generate(request.param, 2 ** 31 + 27, size, path)
    return path, index, Config(window_size=window, halo_size=halo)


def test_mesh_count_is_the_index_and_the_one_device_count(mesh_file):
    from spark_bam_tpu.load.tpu_load import count_reads_tpu

    path, index, config = mesh_file
    stats: dict = {}
    got = count_reads_sharded(path, config, mesh=_mesh(), stats_out=stats)
    assert got == len(index["record_starts"])
    assert got == count_reads_tpu(path, config)  # one device, carried halo
    assert not stats["escapes"] and not stats["fallback"]
    rows = stats["rows"]
    # Every row in one step at the default step width: up to three a device.
    assert 5 <= rows <= 9 and stats["steps"] == 1


def test_the_shares_add_up(mesh_file):
    """Each row's device count is the index's count of record starts in the
    span the row owns, and the rows' sum is the whole file's: a row counted
    alone (a mesh of one device: its totals are the row's) needs nothing of
    its neighbours but the bytes of its halo."""
    path, index, config = mesh_file
    starts = np.asarray(index["record_starts"])
    st = _ShardedStream(
        path, config, _mesh(1), None, None, None,
        chunk_bytes=1)  # one row a step
    assert st.step_rows_local == 1
    step = mesh_steps(st.mesh, st.axis).count_step(
        reads_to_check=config.reads_to_check,
        funnel=config.funnel_enabled(),
    )
    per_row = []
    batches = st.row_batches()
    try:
        for args, _done, c0 in batches:
            count, escapes, _survivors, _lanes = np.asarray(
                step(*args)).tolist()
            assert escapes == 0
            lo = int(st.flat_starts[c0])
            hi = lo + int(st.sizes[c0])
            want = int(np.searchsorted(starts, hi) - np.searchsorted(starts, lo))
            assert count == want, f"row {c0} owns [{lo}, {hi})"
            per_row.append(count)
    finally:
        batches.close()
    assert len(per_row) == len(st.groups)
    assert sum(per_row) == len(starts)
    # The owned spans tile the file.
    assert int(st.flat_starts[-1] + st.sizes[-1]) == index["uncompressed_bytes"]


def test_a_forced_escape_is_patched_exactly(tmp_path):
    """Long reads behind a halo shorter than the checker's lookahead: owned
    positions near the seams escape, the dirty steps' rows are re-derived on
    the host, the count is exact, and the engine says so under the counter
    the benchmark's ``correct`` reads."""
    path = tmp_path / "long.bam"
    index = _generate("longread-hifi", 2 ** 31 + 28, 3 << 20, path)
    stats: dict = {}
    config = Config(window_size=256 << 10, halo_size=64 << 10)
    got, counters, _hists = _observed(lambda: count_reads_sharded(
        path, config, mesh=_mesh(), stats_out=stats))
    assert got == len(index["record_starts"])
    assert stats["escapes"] > 0
    assert stats["patched_steps"] > 0 and not stats["fallback"]
    assert counters["check.count_escape_retries"] == stats["patched_steps"]
    assert counters["mesh.escapes"] == stats["escapes"]
    assert "check.fused_demotions" not in counters


@pytest.mark.parametrize("size", [300 << 10, 1 << 20])
def test_a_file_smaller_than_the_halo_at_the_defaults(size, tmp_path):
    """``count_reads_tpu`` sends every file of a multi-chip host here, the
    small ones too: the kernel window shrinks with the file while the
    default halo stays 4 MiB, and the step still compiles and is exact (one
    row, three padding rows)."""
    path = tmp_path / "small.bam"
    index = _generate("wgs-short", 2 ** 31 + 29, size, path)
    config = Config()
    assert index["uncompressed_bytes"] < config.halo_size
    stats: dict = {}
    got = count_reads_sharded(path, config, mesh=_mesh(), stats_out=stats)
    assert got == len(index["record_starts"])
    assert stats["rows"] == 1 and stats["steps"] == 1
    assert not stats["escapes"] and not stats["fallback"]


@pytest.mark.parametrize("backend,devices,sharded", [
    ("tpu", 4, True), ("tpu", 1, False), ("cpu", 8, False),
])
def test_count_reads_tpu_counts_across_the_chips_it_sees(
        backend, devices, sharded, monkeypatch):
    """The mesh engine is chosen by what the process observes (a TPU backend
    with more than one local chip), never by an option; the CPU's virtual
    devices do not choose it."""
    from spark_bam_tpu.load import tpu_load
    from spark_bam_tpu.parallel import stream_mesh
    from spark_bam_tpu.tpu import stream_check

    assert not tpu_load.counts_across_chips()  # as the tests run: 8 x cpu
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "local_device_count", lambda: devices)
    called = []

    def mesh_engine(path, config, mesh=None):
        called.append(("mesh", mesh.devices.size))
        return 7

    class OneDevice:
        def __init__(self, path, config):
            called.append(("stream", 1))

        def count_reads(self):
            return 7

    monkeypatch.setattr(stream_mesh, "count_reads_sharded", mesh_engine)
    monkeypatch.setattr(stream_check, "StreamChecker", OneDevice)
    assert tpu_load.count_reads_tpu("any.bam", Config()) == 7
    assert called == [("mesh", len(jax.local_devices()))
                      if sharded else ("stream", 1)]
