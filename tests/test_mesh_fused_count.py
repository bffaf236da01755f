"""The fused sharded count (``count_reads_sharded`` where the device inflates:
``jit_count_tokens_step``) on 4 of the CPU's virtual devices, against the
plain reference: files from ``bench/generators`` (their own index), host
zlib + ``check/eager.py``, and the one-device stream.

``Config(device_inflate=True)`` selects on the CPU what a TPU selects by
itself: the host tokenizes each row's members, every device resolves,
assembles and checks its own row, and no inflated byte returns to the host.
"""

import gzip
from pathlib import Path

import numpy as np
import pytest

import jax

from spark_bam_tpu import obs
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.native.build import load_native
from spark_bam_tpu.parallel.mesh import make_mesh, mesh_steps
from spark_bam_tpu.parallel.stream_mesh import (
    _ShardedStream, count_reads_sharded,
)

pytestmark = pytest.mark.skipif(
    load_native() is None, reason="the fused step needs the native tokenizer"
)

#: (configuration, bytes, row window, halo): 9 rows of short reads (a last
#: step with three padding rows) and 6-7 rows of long reads whose 15-38 KB
#: records span members and row seams; the halo covers the checker's ten
#: reads of lookahead in both.
FILES = {
    "wgs-short": (2 << 20, 256 << 10, 64 << 10),
    "longread-hifi": (6 << 20, 1 << 20, 512 << 10),
}


def _mesh(n: int = 4):
    return make_mesh(jax.devices("cpu")[:n])


def _generate(name: str, seed: int, size: int, path):
    from bench.tests.conftest import generate  # the benchmark's own helper

    return generate(name, seed, path, size)[0]


@pytest.fixture(scope="module", params=sorted(FILES))
def generated(request, tmp_path_factory):
    size, window, halo = FILES[request.param]
    path = tmp_path_factory.mktemp(request.param) / "file.bam"
    index = _generate(request.param, 2 ** 31 + 27, size, path)
    return path, index, window, halo


def _fused(window: int, halo: int) -> Config:
    return Config(window_size=window, halo_size=halo, device_inflate=True)


def test_mesh_count_is_the_index_and_the_one_device_count(generated):
    from spark_bam_tpu.load.tpu_load import count_reads_tpu

    path, index, window, halo = generated
    config = _fused(window, halo)
    stats: dict = {}
    got = count_reads_sharded(path, config, mesh=_mesh(), stats_out=stats)
    assert got == len(index["record_starts"])
    assert got == count_reads_tpu(path, config)  # one device, carried halo
    assert stats["fused"] and not stats["escapes"] and not stats["fallback"]
    rows = stats["rows"]
    assert 5 <= rows <= 9 and stats["steps"] == -(-rows // 4)


def _eager_starts(path, index) -> np.ndarray:
    """Flat offsets of the record starts by the plain reference: host zlib
    over the whole file, the chain of ``block_size`` fields from the header's
    end, every start confirmed by the sequential eager checker."""
    from spark_bam_tpu.check.eager import EagerChecker
    from spark_bam_tpu.core.pos import Pos

    flat = gzip.decompress(Path(path).read_bytes())
    assert len(flat) == index["uncompressed_bytes"]
    starts, at = [], int(index["header_end"])
    while at < len(flat):
        starts.append(at)
        at += 4 + int.from_bytes(flat[at: at + 4], "little", signed=True)
    assert at == len(flat)
    eager = EagerChecker.open(path)
    try:
        block = np.searchsorted(index["block_flat"], starts, side="right") - 1
        for s, b in zip(starts, block.tolist()):
            pos = Pos(int(index["block_starts"][b]),
                      s - int(index["block_flat"][b]))
            assert eager(pos), f"the eager checker rejects a record at {s}"
    finally:
        eager.close()
    return np.asarray(starts, dtype=np.int64)


def test_the_shares_add_up(generated):
    """Each row's device count is the reference's count of record starts in
    the span the row owns, and the rows' sum is the whole file's: a row
    counted alone (a mesh of one device: its totals are the row's) needs
    nothing of its neighbours but the bytes of its halo."""
    path, index, window, halo = generated
    config = _fused(window, halo)
    starts = _eager_starts(path, index)
    assert np.array_equal(starts, index["record_starts"])

    st = _ShardedStream(path, config, _mesh(1), None, None, None, fused=True)
    assert st.fused and st.step_rows_local == 1
    step = mesh_steps(st.mesh, st.axis).count_tokens_step(
        st.kernel_window, st.halo, reads_to_check=config.reads_to_check,
        flags_impl=config.flags_impl, funnel=config.funnel_enabled(),
    )
    per_row = []
    batches = st.token_batches()
    try:
        for args, _done, c0 in batches:
            totals, _rounds = step(*args)
            count, escapes = np.asarray(totals).tolist()
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
    # The owned spans tile the file (its length checked against zlib's above).
    assert int(st.flat_starts[-1] + st.sizes[-1]) == index["uncompressed_bytes"]


def test_a_forced_escape_is_patched_exactly(tmp_path):
    """Long reads behind a halo shorter than the checker's lookahead: owned
    positions near the seams escape, the dirty steps' rows are re-derived on
    the host, the count is exact, and the engine says so under the counter
    the benchmark's ``correct`` reads."""
    path = tmp_path / "long.bam"
    index = _generate("longread-hifi", 2 ** 31 + 28, 3 << 20, path)
    obs.shutdown()
    obs.configure()
    try:
        stats: dict = {}
        got = count_reads_sharded(
            path, _fused(256 << 10, 64 << 10), mesh=_mesh(), stats_out=stats)
        counters = {c["name"]: c["value"]
                    for c in obs.registry().snapshot()["counters"]}
    finally:
        obs.shutdown()
    assert got == len(index["record_starts"])
    assert stats["fused"] and stats["escapes"] > 0
    assert stats["patched_steps"] > 0 and not stats["fallback"]
    assert counters["check.count_escape_retries"] == stats["patched_steps"]
    assert counters["mesh.escapes"] == stats["escapes"]
    assert "check.fused_demotions" not in counters


def test_a_rejected_row_demotes_the_count_and_says_so(generated, monkeypatch):
    """Input the tokenizer rejects leaves the fused step for the
    host-assembled rows (host zlib), exactly and under
    ``check.fused_demotions``."""
    from spark_bam_tpu.parallel import stream_mesh

    path, index, window, halo = generated

    def rejects(ch, metas):
        raise IOError("tokenized output sizes disagree with block footers")

    monkeypatch.setattr(stream_mesh, "tokenize_group", rejects)
    obs.shutdown()
    obs.configure()
    try:
        stats: dict = {}
        got = count_reads_sharded(
            path, _fused(window, halo), mesh=_mesh(), stats_out=stats)
        counters = {c["name"]: c["value"]
                    for c in obs.registry().snapshot()["counters"]}
    finally:
        obs.shutdown()
    assert got == len(index["record_starts"]) and not stats["fused"]
    assert counters["check.fused_demotions"] == 1


@pytest.mark.parametrize("size", [300 << 10, 1 << 20])
def test_a_file_smaller_than_the_halo_at_the_defaults(size, tmp_path):
    """``count_reads_tpu`` sends every file of a multi-chip host here, the
    small ones too: the kernel window shrinks with the file while the
    default halo stays 4 MiB, and the step still compiles and is exact (one
    row, three padding rows)."""
    path = tmp_path / "small.bam"
    index = _generate("wgs-short", 2 ** 31 + 29, size, path)
    config = Config(device_inflate=True)
    assert index["uncompressed_bytes"] < config.halo_size
    stats: dict = {}
    got = count_reads_sharded(path, config, mesh=_mesh(), stats_out=stats)
    assert got == len(index["record_starts"])
    assert stats["fused"] and stats["rows"] == 1 and stats["steps"] == 1
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
