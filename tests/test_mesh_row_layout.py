"""Where the mesh count puts its rows: ``_ShardedStream.row_slots`` deals a
step's rows round-robin over the devices, and ``_assemble_rows`` lays each
device's rows, inflated on the host, into that device's flat buffer. Checked
on the operands themselves, over 1-9 rows on 1-8 of the CPU's virtual
devices, two rows a device a step: nothing is compiled."""

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import jax

from spark_bam_tpu.core.channel import open_channel
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.parallel.mesh import make_mesh
from spark_bam_tpu.parallel.stream_mesh import _ShardedStream
from spark_bam_tpu.tpu.checker import PAD

MEMBER = 0xFF00  # htslib's payload: what the generators fill every member to
CONFIG = Config(window_size=MEMBER, halo_size=16 << 10)  # a member a row


@pytest.fixture(scope="module", params=[1, 3, 8, 9])
def file_of_rows(request, tmp_path_factory):
    from bench.tests.conftest import generate  # the benchmark's own helper

    rows = request.param
    path = tmp_path_factory.mktemp(f"rows{rows}") / "file.bam"
    index = generate(
        "wgs-short", 2 ** 31 + 40 + rows, path,
        (rows - 1) * MEMBER + 60_000)[0]
    assert (rows - 1) * MEMBER < index["uncompressed_bytes"] <= rows * MEMBER
    return rows, path


@pytest.mark.parametrize("devices", [1, 2, 4, 8])
def test_rows_land_round_robin_in_their_devices_buffers(file_of_rows, devices):
    rows, path = file_of_rows
    mesh = make_mesh(jax.devices("cpu")[:devices])
    probe = _ShardedStream(path, CONFIG, mesh, None, None, None)
    width = probe.kernel_window + PAD
    st = _ShardedStream(
        path, CONFIG, mesh, None, None, None,
        chunk_bytes=2 * devices * width)
    assert len(st.groups) == rows and st.n_local == devices
    padded = -(-rows // devices) * devices
    assert st.per_proc == padded
    assert st.step_rows_local == min(2 * devices, padded)
    per_dev = st.step_rows_local // devices

    seen = []
    with open_channel(path) as ch, ThreadPoolExecutor(4) as pool:
        for c0 in range(0, st.per_proc, st.step_rows_local):
            slots = st.row_slots(c0)
            live = [g for g, _d, _s in slots]
            assert live == list(range(c0, min(c0 + st.step_rows_local, rows)))
            # Dealt like cards: row j of the step to device j mod n, so no
            # device holds two rows more than another (a short last step
            # lands 1/1/1/1, not 2/2/0/0).
            assert [(d, s) for _g, d, s in slots] == [
                (j % devices, j // devices) for j in range(len(live))]
            load = np.bincount([d for _g, d, _s in slots], minlength=devices)
            assert load.max() - load.min() <= 1
            seen += live

            (windows, ns, eofs, los, owns, lengths, nc), _blocks = (
                st._assemble_rows(ch, c0, pool))
            assert windows.shape == (devices * per_dev * width,)
            assert windows.sharding.is_equivalent_to(st.row_sharding, 1)
            flat = np.asarray(windows).reshape(devices * per_dev, width)
            ns, eofs, los, owns = map(np.asarray, (ns, eofs, los, owns))
            filled = set()
            for g, d, s in slots:
                i = d * per_dev + s  # device-major: a device's block
                buf, n, at_eof = st._row(ch, g)
                np.testing.assert_array_equal(flat[i, :n], buf)
                assert not flat[i, n:].any()
                own, lo = st._row_span(g, n, at_eof, True)
                assert (ns[i], eofs[i], los[i], owns[i]) == (
                    n, at_eof, lo, own)
                assert at_eof or g < rows - 1  # the last row ends the file
                filled.add(i)
            for i in set(range(devices * per_dev)) - filled:
                # A padding slot is zeros and owns nothing.
                assert not flat[i].any()
                assert (ns[i], los[i], owns[i]) == (0, 0, 0)
            for d, shard in enumerate(windows.addressable_shards):
                assert shard.device == st.local_devices[d]
                assert shard.data.shape == (per_dev * width,)
    assert seen == list(range(rows))  # every row once, in order
    # The owned spans tile the file, the header's bytes left out.
    assert int(st.flat_starts[-1] + st.sizes[-1]) == st.total
