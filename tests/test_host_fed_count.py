"""The whole-file count's default path on every backend: windows and rows
inflated on the host, the device only checking them (``jit_count_window``,
``jit_count_step``), measured as the fused token path was.

Files come from ``bench/generators`` with their own index; ``Config()`` is
what a TPU process passes too, so the TPU cases only patch what the process
observes (``jax.default_backend``) and must take the same path.
"""

import pytest

import jax

from spark_bam_tpu import obs
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.parallel.mesh import make_mesh
from spark_bam_tpu.parallel.stream_mesh import (
    _ShardedStream, count_reads_sharded,
)
from spark_bam_tpu.tpu.checker import PAD
from spark_bam_tpu.tpu.stream_check import StreamChecker

MEMBER = 0xFF00  # htslib's payload: what the generators fill every member to

DEMOTIONS = (
    "inflate.tokenize_demotions", "inflate.host_demotions",
    "check.fused_demotions", "agg.host_fallbacks",
    "check.count_escape_retries",
)
#: What only the token path emits: nothing resolves and nothing tokenizes
#: here, so a value under these names would be a false reading.
TOKEN_PATH_ONLY = (
    "inflate.rounds", "mesh.rounds", "inflate.tokenize_host_ms",
    "inflate.tokenize", "inflate.pack",
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
    hists = {}
    for h in snap["hists"]:
        hists[h["name"]] = hists.get(h["name"], 0) + h["count"]
    return value, counters, hists


def _assert_host_fed(counters: dict, hists: dict) -> None:
    for name in DEMOTIONS:
        assert not counters.get(name), name
    for name in TOKEN_PATH_ONLY:
        assert name not in counters and not hists.get(name), name


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_one_device_count_is_host_fed_and_measured(
        generated, backend, monkeypatch):
    path, index = generated
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    checker = StreamChecker(path, Config())
    assert checker.pipeline.device_copy is False
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


def test_one_device_count_carries_the_halo_and_paces(short48):
    """Eight windows with a carried halo: the pacing wait and the window-4
    checkpoint are on the feeding thread under their names, and the windows'
    owned spans still add up to the index."""
    path, index = short48
    config = Config(window_size=6 * MEMBER, halo_size=64 << 10)
    checker = StreamChecker(path, config)
    got, counters, hists = _observed(checker.count_reads)
    assert got == len(index["record_starts"])
    _assert_host_fed(counters, hists)
    assert counters["check.windows"] == counters["inflate.windows"] == 8
    assert counters["inflate.bytes"] == index["uncompressed_bytes"]
    assert hists["check.pace"] == 8 - config.ring_depth
    assert hists["check.flush"] == 2  # window 4's escape checkpoint, EOF
    assert hists["inflate.device_ms"] == 8
    # The same count with no registry: the same dispatches and waits.
    assert StreamChecker(path, config).count_reads() == got


def test_explicit_device_inflate_still_reaches_the_token_path(generated):
    path, index = generated
    checker = StreamChecker(path, Config(device_inflate=True))
    assert checker.pipeline.device_copy is True
    got, counters, hists = _observed(checker.count_reads)
    assert got == len(index["record_starts"])
    assert hists["inflate.rounds"] == counters["check.windows"] == 1
    assert hists["inflate.tokenize_host_ms"] == 1
    assert "inflate.bytes" not in counters  # nothing inflated on the host


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
    assert not probe.fused and not probe.device_inflate
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
    assert not stats["fused"] and not stats["escapes"]
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
    assert not stats["fused"] and not stats["escapes"]
    _assert_host_fed(counters, hists)
    assert counters["mesh.rows"] == stats["rows"] >= 3
    assert hists["mesh.step_device_ms"] == stats["steps"]
