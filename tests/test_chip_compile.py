"""The one file that asks the chip's compiler.

The TPU compiler is installed without a chip: ``jax.experimental.topologies``
describes a ``v5e:2x2`` and ``.lower(...).compile()`` against its devices
raises what the attached chip would raise — Mosaic lowering refusals, HBM
exhaustion — at no chip time. These cases pin the main path's programs at the
default width (the count of host-inflated 32 MiB windows on one chip and on
four; the token path's 4 MiB halo, 512 block rows × 64 KiB payloads, which
an explicit ``device_inflate=True`` still reaches) plus the small serve /
aggregate / Pallas programs.

Only one process may hold libtpu, so the topology is described inside a
module-scoped fixture (never at import or collection) and every compile runs
in this process. A passing compile is not a chip run: it says nothing of
results or times.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from spark_bam_tpu.tpu.checker import PAD

HBM = 16 << 30
WINDOW = 32 << 20       # Config(): next_pow2(24 MiB window + 4 MiB halo)
HALO = 4 << 20
BLOCKS = 512            # b_pad of a 24 MiB group of ~386 BGZF blocks
C_PAD = 65536           # staged payload row: ≈43 KB payloads pad to 64 KiB
CMAX = 1024


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """``shape(dims, dtype)`` placed on one described chip, with the
    persistent compile cache off around the module (such compiles are
    written to it but can never be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    yield shape
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _scalars(chip, *dtypes):
    return [chip((), dt) for dt in dtypes]


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)


def _mesh_shapes(topo, n_devices: int):
    mesh = Mesh(np.array(topo.devices[:n_devices]), ("data",))
    rows, repl = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())

    def shape(dims, dtype, sharding=rows):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    return mesh, shape, repl


# ------------------------------------------------------------ full width
def test_fused_count_program_auto_selects_compiles_at_default_width(chip):
    """The whole fused count program ``device_inflate=True`` ends in on a
    TPU — entropy phase, LZ77 resolve, window assembly, funnel, chain walk —
    built the way ``StreamChecker._count_reads_fused`` builds it."""
    from spark_bam_tpu.core.inflate_config import InflateConfig
    from spark_bam_tpu.tpu import checker
    from spark_bam_tpu.tpu.inflate import STRIDE, _tok_impl

    icfg = InflateConfig()
    tail = [chip((HALO,), jnp.uint8), chip((CMAX,), jnp.int32),
            *_scalars(chip, jnp.int32, jnp.int32, jnp.int32, jnp.bool_,
                      jnp.int32, jnp.int32)]
    if icfg.resolve_tokenize() == "device":
        kernel = checker.make_count_window_raw(
            WINDOW, HALO, 10, flags_impl="xla", funnel=True,
            tok_impl=_tok_impl(icfg.kernel), donate=icfg.donate_enabled,
        )
        args = [chip((BLOCKS, C_PAD), jnp.uint8), chip((BLOCKS,), jnp.int32),
                chip((BLOCKS,), jnp.int32), *tail]
    else:
        kernel = jax.jit(checker.make_count_window_tokens(
            WINDOW, HALO, 10, flags_impl="xla", funnel=True,
        ))
        args = [chip((3 * BLOCKS * STRIDE,), jnp.uint8),
                chip((BLOCKS,), jnp.int32), *tail]
    compiled = kernel.lower(*args).compile()
    assert _device_bytes(compiled) < HBM


def test_count_window_xla_funnel_compiles_at_32mib(chip):
    """``jit_count_window``: the whole device program of the one-chip count
    on every backend (the windows arrive inflated). Its temporaries are the
    check's alone: under 5 GiB with its one 32 MiB operand, where the token
    program reserved 6.0."""
    from spark_bam_tpu.tpu.checker import make_count_window

    kernel = jax.jit(make_count_window(WINDOW, 10, "xla", funnel=True))
    compiled = kernel.lower(
        chip((WINDOW + PAD,), jnp.uint8), chip((CMAX,), jnp.int32),
        *_scalars(chip, jnp.int32, jnp.int32, jnp.bool_, jnp.int32,
                  jnp.int32),
    ).compile()
    assert _device_bytes(compiled) < 5 << 30
    assert "gather" in compiled.as_text()  # the lane walk: a real program


def _count_step_shapes(shape, repl, devices: int, rows: int):
    """The count step's operands for ``rows`` rows a device, flat."""
    k = devices * rows
    return (
        shape((k * (WINDOW + PAD),), jnp.uint8), shape((k,), jnp.int32),
        shape((k,), jnp.bool_), shape((k,), jnp.int32),
        shape((k,), jnp.int32), shape((CMAX,), jnp.int32, repl),
        shape((), jnp.int32, repl),
    )


def test_sharded_count_step_fits_one_chip_at_its_row_cap(topo, chip):
    """The mesh count vmaps a device's rows; five 32 MiB rows need 15.9 GiB
    and are refused, so the stream caps rows by the device's reported
    memory. The capped step must fit."""
    from types import SimpleNamespace

    from spark_bam_tpu.parallel.mesh import make_shard_map_count_step
    from spark_bam_tpu.parallel.stream_mesh import _rows_fitting_device

    v5e = SimpleNamespace(
        memory_stats=lambda: {"bytes_limit": int(15.75 * 2**30)}
    )
    rows = _rows_fitting_device(v5e, WINDOW)
    assert rows == 3
    none = SimpleNamespace(memory_stats=lambda: None)   # the CPU backend
    assert _rows_fitting_device(none, WINDOW) >= 1 << 20

    mesh, shape, repl = _mesh_shapes(topo, 1)
    step = make_shard_map_count_step(mesh, 10, "data", "xla", funnel=True)
    compiled = step.lower(*_count_step_shapes(shape, repl, 1, rows)).compile()
    assert _device_bytes(compiled) < HBM


def test_count_step_compiles_for_four_chips_at_one_row_a_chip(topo, chip):
    """``jit_count_step`` as the whole-file count runs it on a v5e host: one
    host-inflated 32 MiB row a chip, flat, the count pair ``psum``'d. A
    chip holds its own row's bytes once (a ``(1, N)`` u8 block of a
    row-major operand would be tiled four rows high). The compiler sets
    5.9 GiB aside for the one row of a several-chip mesh, against 2.7 GiB
    for the same program on a mesh of one chip (``PERF.md`` §7)."""
    from spark_bam_tpu.parallel.mesh import make_shard_map_count_step

    n = 4
    mesh, shape, repl = _mesh_shapes(topo, n)
    step = make_shard_map_count_step(mesh, 10, "data", "xla", funnel=True)
    compiled = step.lower(*_count_step_shapes(shape, repl, n, 1)).compile()
    ma = compiled.memory_analysis()
    assert WINDOW < ma.argument_size_in_bytes < WINDOW + (1 << 20)
    assert 1 << 30 < _device_bytes(compiled) < HBM // 2
    text = compiled.as_text()
    assert "all-reduce" in text  # the psum, and nothing gathers the rows
    assert "all-gather" not in text and "all-to-all" not in text


@pytest.mark.parametrize("window,blocks,least", [
    (WINDOW, BLOCKS, 4 << 30),
    (1 << 20, 32, 0),  # a 1 MiB file: a window smaller than the 4 MiB halo
])
def test_fused_count_tokens_step_compiles_for_four_chips(
        window, blocks, least, topo, chip):
    """``jit_count_tokens_step``: the fused window program on every chip of
    a v5e host, one row a chip (512 token rows, 32 MiB window, 4 MiB
    halo), the count pair ``psum``'d. Each device must hold its row's
    program: the one-chip program's 6.4 GiB, not four of them. A multi-chip
    host sends its small files through the same step, at the window their
    size gives."""
    from spark_bam_tpu.parallel.mesh import make_shard_map_count_tokens_step
    from spark_bam_tpu.tpu.inflate import STRIDE

    n = 4
    mesh, shape, repl = _mesh_shapes(topo, n)
    step = make_shard_map_count_tokens_step(
        mesh, window, HALO, 10, "data", "xla", funnel=True
    )
    compiled = step.lower(
        shape((n * 3 * blocks * STRIDE,), jnp.uint8),
        shape((n * blocks,), jnp.int32), shape((n,), jnp.int32),
        shape((n,), jnp.bool_), shape((n,), jnp.int32),
        shape((n,), jnp.int32), shape((CMAX,), jnp.int32, repl),
        shape((), jnp.int32, repl),
    ).compile()
    assert least < _device_bytes(compiled) < HBM
    text = compiled.as_text()
    assert "all-reduce" in text  # the psum, and nothing gathers the rows
    assert "all-gather" not in text and "all-to-all" not in text


# ----------------------------------------------------------------- small
def test_serve_step_compiles_at_serve_config_defaults(topo, chip):
    from spark_bam_tpu.parallel.mesh import make_shard_map_serve_step
    from spark_bam_tpu.serve.config import MAX_CONTIGS, ServeConfig

    cfg = ServeConfig()
    b, width = cfg.batch_rows, cfg.window + PAD
    mesh, shape, _ = _mesh_shapes(topo, 1)
    step = make_shard_map_serve_step(mesh, 10, "data", "xla", funnel=True)
    compiled = step.lower(
        shape((b, width), jnp.uint8), shape((b,), jnp.int32),
        shape((b,), jnp.bool_), shape((b,), jnp.int32),
        shape((b,), jnp.int32), shape((b, MAX_CONTIGS), jnp.int32),
        shape((b,), jnp.int32),
    ).compile()
    assert _device_bytes(compiled) < HBM


def test_aggregate_reduction_compiles(chip):
    from spark_bam_tpu.agg.kernels import (
        DEFAULT_CHUNK, PLANES, state_zeros, update_fn,
    )
    from spark_bam_tpu.agg.plan import AggConfig

    plan, nc = AggConfig.parse(""), 2
    state = {k: chip(v.shape, v.dtype) for k, v in state_zeros(plan, nc).items()}
    planes = {
        name: chip((DEFAULT_CHUNK,), jnp.bool_ if name == "valid" else jnp.int32)
        for name in PLANES
    }
    compiled = update_fn(plan, nc).lower(state, planes).compile()
    assert _device_bytes(compiled) < HBM


@pytest.mark.parametrize("kernel", ["tokenize_pallas", "lz77_resolve_pallas"])
def test_refused_pallas_kernels_stay_out_of_auto(chip, kernel, monkeypatch):
    """Mosaic refuses both inflate kernels for the v5e, so ``auto`` must
    never select them on any backend. When one of them is repaired this
    test fails at ``pytest.raises``: that is the moment to let ``auto``
    choose it again. (The ``backend=pallas`` flag kernels are explicit-only
    too and have no case here: ``prefilter_check_flags`` on a four-tile grid
    took 580 s to be refused for 26 MB of scoped VMEM against a 16 MB
    limit.)"""
    from spark_bam_tpu.tpu import inflate
    from spark_bam_tpu.tpu import pallas_kernels as pk

    monkeypatch.delenv("SPARK_BAM_LZ77", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert inflate._tok_impl("auto") == "xla"
    assert inflate._lz77_impl() == "xla"
    rows = 8
    args = {
        "tokenize_pallas": [chip((rows, C_PAD), jnp.uint8),
                            chip((rows,), jnp.int32)],
        "lz77_resolve_pallas": [chip((rows, 65536), jnp.uint8),
                                chip((rows, 65536), jnp.uint16)],
    }[kernel]
    with pytest.raises(Exception, match="block shape"):
        getattr(pk, kernel).lower(*args).compile()
