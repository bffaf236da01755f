"""The reduction from a trace to busy time, self times and gaps, on a small
synthetic trace whose answers are worked out by hand."""

import pytest

from bench import trace_reduce

MS = 1e6  # nanoseconds

# Device 0: fusion.1 0-10 ms; while.2 20-60 ms holding gather.3 25-35 ms and
# gather.3 40-50 ms; copy.4 58-70 ms (overlaps the while's tail).
# Device 1: one op of 35 ms.
PLANES = [
    ("/host:CPU", [("python", [("ignored", 0.0, 500 * MS)])]),
    ("/device:TPU:0", [
        ("XLA Modules", [("jit_step", 0.0, 70 * MS)]),
        ("XLA Ops", [
            ("fusion.1", 0.0, 10 * MS),
            ("while.2", 20 * MS, 40 * MS),
            ("gather.3", 25 * MS, 10 * MS),
            ("gather.3", 40 * MS, 10 * MS),
            ("copy.4", 58 * MS, 12 * MS),
        ]),
    ]),
    ("/device:TPU:1", [("XLA Ops", [("fusion.1", 5 * MS, 35 * MS)])]),
]


def test_busy_self_times_and_gaps():
    out = trace_reduce.reduce_planes(PLANES)
    assert out["devices"] == 2
    # Device 0: 10 + (70 - 20) = 60 ms; device 1: 35 ms; mean 47.5 ms.
    assert out["busy_s"] == pytest.approx(0.0475)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.045)  # 10 + 35
    assert ops["gather.3"] == pytest.approx(0.020)
    # 40 less two children and the 2 ms of copy.4 that lie inside it.
    assert ops["while.2"] == pytest.approx(0.018)
    assert ops["copy.4"] == pytest.approx(0.012)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps == {"after fusion.1 before while.2": pytest.approx(0.010)}
    assert "ignored" not in ops and "jit_step" not in ops


def test_a_plane_without_the_ops_line_uses_what_runs():
    planes = [("/device:TPU:0", [
        ("Steps", [("step", 0.0, 100 * MS)]),
        ("Stream #1", [("op", 10 * MS, 20 * MS)]),
    ])]
    assert trace_reduce.reduce_planes(planes)["busy_s"] == pytest.approx(0.02)


def test_no_device_plane_reads_nothing():
    out = trace_reduce.reduce_planes(PLANES[:1])
    assert out["devices"] == 0 and out["busy_s"] == 0.0


def test_reads_a_capture_of_this_jax(tmp_path):
    """``load_planes`` against a real (CPU) capture: planes, lines, events."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.arange(1024).sum().block_until_ready()
    jax.profiler.stop_trace()
    out = trace_reduce.reduce_dir(tmp_path)
    assert out["file_bytes"] > 0 and out["devices"] == 0
    assert any(lines for lines in out["inventory"].values())  # host lines
