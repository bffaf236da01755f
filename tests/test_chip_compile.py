"""The one file that asks the chip's compiler.

The TPU compiler is installed without a chip: ``jax.experimental.topologies``
describes a ``v5e:2x2`` and ``.lower(...).compile()`` against its devices
raises what the attached chip would raise — Mosaic lowering refusals, HBM
exhaustion — at no chip time. These cases pin the main path's programs at the
default width (the count of host-inflated 32 MiB windows on one chip and on
four) plus the small serve / aggregate programs.

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


def _position_wide_lookups(text: str, positions: int) -> list:
    """The ``s32`` gathers of a compiled program that return a value for
    every one of ``positions``: what the contig-length lookup of stage 0
    was until PR 32 (two of them, 225 ms each a 32 MiB window on the chip,
    whatever the 1,024-entry table held: a gather costs per index)."""
    import math
    import re

    found = []
    for line in text.splitlines():
        m = re.search(r"= s32\[([\d,]*)\]\S* gather\(", line)
        if m and math.prod(int(d) for d in m.group(1).split(",") if d) >= positions:
            found.append(line.strip()[:120])
    return found


def _word_views(text: str, window: int) -> list:
    """The fusions of a compiled program that make the window's word view
    (``checker._words_at``: the int32 at every byte offset, ``window + PAD``
    of them, followed since PR 49 by the same from word ``WORD_REACH`` on:
    ``2 (window + PAD)`` in whole rows of ``WORD_ROW``; ``window + PAD - 3``
    before): what the lane stage reads its fields from since PR 36. One a
    row's program: the view is materialized once, behind its barrier,
    neither assembled again by stage 0 nor kept a second time as uint32 or
    laid out again in rows (the lanes' ``(rows, WORD_ROW)`` is a bitcast of
    it)."""
    import re

    from spark_bam_tpu.tpu.checker import WORD_ROW

    words = 2 * (window + PAD)
    rows = f"{words // WORD_ROW},{WORD_ROW}"
    # In rows whatever makes it counts: a copy would be the view laid out
    # a second time.
    return [line[:100] for line in text.splitlines()
            if re.search(rf"= [su]32\[{words}\]\S* fusion\(", line)
            and "/check/flags/" in line
            or re.search(rf"= [su]32\[{rows}\]\S* (fusion|copy)\(", line)]


def _word_fetches(text: str, window: int) -> tuple:
    """``(rows fetched, elements gathered)`` from the window's word view by
    a compiled program: the result types of the fusions that fetch whole
    rows of it (``_lane_words``: one a site since PR 49) and of the gathers
    that take single words (eight a lane for the deep flags and three a
    step of the walk until then: a gather costs per index)."""
    import re

    from spark_bam_tpu.tpu.checker import WORD_ROW

    words = 2 * (window + PAD)
    view = rf"s32\[{words // WORD_ROW},{WORD_ROW}\]"
    rows = re.findall(
        rf"^%\S+ \(\S+ {view}, \S+ s32\[\d+\]\) -> (s32\[\d+,{WORD_ROW}\])",
        text, flags=re.M)
    elements = re.findall(
        rf"^%\S+ \(\S+ s32\[{words}\], \S+ s32\[\d+\]\) -> (s32\[\d+\])",
        text, flags=re.M)
    return rows, elements


def _byte_gathers(text: str) -> list:
    """Result types of the gathers of a compiled program that read bytes.
    A gather costs per index, so the lane stage reads words: the one byte
    gather left is the name's last byte, a block of lanes wide (until PR 36
    a 36-byte slab a lane, ``u8[589824]`` a block, and seven bytes a lane a
    step of the walk)."""
    import re

    return re.findall(r"= (u8\[[\d,]*\])\S* gather\(", text)


def _whiles_of_no_constant_trip_count(text: str) -> list:
    """The ``while`` loops of a compiled program whose condition holds the
    counter against no constant: a trip count the device reads from its
    data (the lane stage's blocks), not one the compiler knows (a
    ``lax.map`` over rows, ``searchsorted``'s 16 halvings)."""
    import re

    found = []
    for line in text.splitlines():
        m = re.search(r" while\(.*condition=(%[\w.\-]+)", line)
        if not m:
            continue
        head = f"{m.group(1)} ("
        body = text[text.index("\n" + head):]
        body = body[: body.index("\n}")]
        if " constant(" not in body:
            found.append(line.strip()[:100])
    return found


def _mesh_shapes(topo, n_devices: int):
    mesh = Mesh(np.array(topo.devices[:n_devices]), ("data",))
    rows, repl = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())

    def shape(dims, dtype, sharding=rows):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    return mesh, shape, repl


# ------------------------------------------------------------ full width
def test_count_window_xla_funnel_compiles_at_32mib(chip):
    """``jit_count_window``: the whole device program of the one-chip count
    on every backend (the windows arrive inflated). Its temporaries are the
    check's alone, 0.91 GiB since the survivors are materialized once and
    the lane stage runs in blocks (2.72 GiB before, the bound of PR 30's
    issue): the two ``while`` loops are that stage. Compiled as the stream
    runs it, with the escape list, whose 64 slots ride in the walk's loop
    (chipless: 0.894 GiB with it against 0.908 without at PR 31; 0.9085
    since PR 32 took stage 0's two lookups, the funnel's tables being
    scheduled before the peak, the survivors' word packing; **1.0192 since
    PR 36**: the window's word view, 0.126 GiB, is alive from stage 0 to the
    last block of the walk; 1.0174 at PR 40's block of 2,048 lanes: the one
    byte gather left is a block wide, so its shape says the block; **1.1473
    since PR 49**: the view is followed by itself from its 64th word on,
    0.126 GiB more, so that a site fetches one row of it where it gathered
    eight words or three)."""
    from spark_bam_tpu.tpu.checker import (
        ESCAPE_LIST, LANE_BLOCK, WORD_ROW, make_count_window,
    )

    kernel = jax.jit(make_count_window(
        WINDOW, 10, funnel=True, escapes=ESCAPE_LIST))
    compiled = kernel.lower(
        chip((WINDOW + PAD,), jnp.uint8), chip((CMAX,), jnp.int32),
        *_scalars(chip, jnp.int32, jnp.int32, jnp.bool_, jnp.int32,
                  jnp.int32),
    ).compile()
    # 1.1473 GiB of temporaries + the 32.25 MiB operand = 1.179 GiB read.
    assert 9 << 27 < _device_bytes(compiled) < 5 << 28
    text = compiled.as_text()
    assert "gather" in text  # the lane walk: a real program
    assert text.count(" while(") >= 2  # deep-check blocks, then walk blocks
    assert f"s32[{ESCAPE_LIST}]" in text  # the list, carried by the walk
    assert not _position_wide_lookups(text, WINDOW)
    assert len(_word_views(text, WINDOW)) == 1
    assert _byte_gathers(text) == [f"u8[{LANE_BLOCK}]"]
    # No word of the view is gathered by its own index: the deep flags and
    # the eight steps of the walk whose words are used fetch a row each.
    assert _word_fetches(text, WINDOW) == (
        [f"s32[{LANE_BLOCK},{WORD_ROW}]"] * 9, [])


def test_load_window_compiles_at_32mib(chip):
    """``jit_load_window``: the whole device program of a window of the
    streaming load (``StreamChecker.read_batches``), the count's check with
    the load's fold. What it adds to the count's program: two row fetches a
    lane of the walk's blocks (the fixed block again, a row of CIGAR inside
    a loop of its own, whose trip count the block's longest CIGAR sets), the
    table of rows (11 int32 a lane and a block over: 44.1 MiB, an output and
    once more as the loop's carry) and no gather of a byte or of a single
    word of the view."""
    from spark_bam_tpu.tpu.checker import (
        LANE_BLOCK, LOAD_STATS, WORD_ROW, lane_capacity, make_load_window,
    )
    from spark_bam_tpu.tpu.parser import ROW_WORDS, RowFilter

    rows = RowFilter(
        chip((4, 3), jnp.int32),
        *_scalars(chip, jnp.bool_, jnp.int32, jnp.int32))
    compiled = jax.jit(make_load_window(WINDOW, 10)).lower(
        chip((WINDOW + PAD,), jnp.uint8), chip((CMAX,), jnp.int32),
        *_scalars(chip, jnp.int32, jnp.int32, jnp.bool_, jnp.int32,
                  jnp.int32), rows,
    ).compile()
    table = f"s32[{ROW_WORDS},{lane_capacity(WINDOW) + LANE_BLOCK}]"
    text = compiled.as_text()
    assert table in text and f"s32[{len(LOAD_STATS)}]" in text
    # The count's 1.18 GiB and the table twice or three times over.
    assert 9 << 27 < _device_bytes(compiled) < 6 << 28
    assert len(_word_views(text, WINDOW)) == 1
    assert not _position_wide_lookups(text, WINDOW)
    assert _byte_gathers(text) == [f"u8[{LANE_BLOCK}]"]
    # The count's nine fetches, the record's fixed block and a row of CIGAR.
    assert _word_fetches(text, WINDOW) == (
        [f"s32[{LANE_BLOCK},{WORD_ROW}]"] * 11, [])
    # The blocks of pass 1 and of the walk, and the CIGAR's rows in them.
    assert len(_whiles_of_no_constant_trip_count(text)) == 3


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
    step = make_shard_map_count_step(mesh, 10, "data", funnel=True)
    compiled = step.lower(*_count_step_shapes(shape, repl, 1, rows)).compile()
    assert _device_bytes(compiled) < HBM


@pytest.fixture(scope="module")
def confusion_step(topo, chip):
    """``jit_confusion_step`` as ``check_bam_tpu`` runs it on one v5e chip
    (the cell ``wgs-short-checkbam.check-bam``), compiled once: three 32 MiB
    rows one after another, ``check_window``'s lane stage in blocks sized
    by each row's survivors, the verdicts scattered over every position and
    held against a byte of truth a position."""
    from spark_bam_tpu.parallel.mesh import make_shard_map_confusion_step

    rows = 3
    mesh, shape, repl = _mesh_shapes(topo, 1)
    step = make_shard_map_confusion_step(mesh, 10, "data", funnel=True)
    return rows, step.lower(
        shape((rows, WINDOW + PAD), jnp.uint8), shape((rows,), jnp.int32),
        shape((rows,), jnp.bool_), shape((rows, WINDOW), jnp.bool_),
        shape((rows,), jnp.int32), shape((rows,), jnp.int32),
        shape((CMAX,), jnp.int32, repl), shape((), jnp.int32, repl),
    ).compile()


def test_confusion_step_fits_one_chip_at_three_rows(confusion_step):
    """2.39 GiB of temporaries: ONE row's, since the rows run in turn and
    the lane stage in blocks behind a materialized survivor mask (8.23 GiB
    until PR 34: three rows batched, each a full-capacity stage whose word
    packing re-derived the flags at four times their bytes; 3.47 with the
    blocks and the rows still batched; 2.0142 until PR 36, whose word view
    is 0.126 GiB of the 0.2509 more: the most bytes alive at once are
    1,913,398,011 before and after, at the row's reduce, the rest is how the
    compiler packs its heap around a buffer that lives through both loops;
    2.2628 at PR 40's block of 2,048; 2.3851 since PR 49 doubled the view).
    The mismatch list (``MISMATCH_LIST`` slots a row, two levels of 1,024
    positions) adds nothing to speak of (1.33 GiB when it packed the mask
    into 32-bit words)."""
    from spark_bam_tpu.parallel.mesh import MISMATCH_LIST
    from spark_bam_tpu.tpu.checker import LANE_BLOCK, WORD_ROW

    rows, compiled = confusion_step
    ma = compiled.memory_analysis()
    assert 1 << 30 < ma.temp_size_in_bytes < 5 << 29
    # Two steps' operands are alive at once: one running, one put ahead.
    assert _device_bytes(compiled) + ma.argument_size_in_bytes < 4 << 30
    text = compiled.as_text()
    assert f"s32[{rows},{MISMATCH_LIST}]" in text
    # Both passes of the lane stage run as many blocks as the row needs.
    assert len(_whiles_of_no_constant_trip_count(text)) >= 2
    assert not _position_wide_lookups(text, WINDOW)
    # The word view once a row (the row's program is written once, in the
    # loop over the rows), and no lane gather reads bytes but the name's.
    assert len(_word_views(text, WINDOW)) == 1
    assert _byte_gathers(text) == [f"u8[{LANE_BLOCK}]"]
    assert _word_fetches(text, WINDOW) == (
        [f"s32[{LANE_BLOCK},{WORD_ROW}]"] * 9, [])


def test_the_nameless_int8_operations_are_the_verdict_scatter(confusion_step):
    """What ``scatter_device_ms`` rests on (``bench/readers/trace_orphans``):
    it sums the scope ``check/scatter`` and the operations that carry no
    name and make an int8 array. int8 is the verdict code of the scatter
    and of stage 0's base under it, and nothing else in this program: the
    lane stage's loops carry the walk's code as int32, so no ``while`` (nor
    anything that computes) is taken for the scatter. With the rows in turn
    the scatter is not batched and keeps its name, sort and all (batched,
    the compiler flattened it and dropped the metadata: PR 33); what is
    left without one only MOVES the position-wide verdicts between memory
    spaces (asynchronous copies and slices, the concatenation of the
    slices)."""
    import re

    from bench.readers.trace_orphans import result_types

    _rows, compiled = confusion_step
    text = compiled.as_text()
    moves_nothing = {"parameter", "get-tuple-element", "tuple", "constant",
                     "bitcast"}
    nameless, named = set(), set()
    for line in text.splitlines():
        m = re.search(r"\) ([\w\-]+)\(|\] ([\w\-]+)\(",
                      re.sub(r"\{[^{}]*\}", "", line.partition(" = ")[2]))
        kind = m and (m.group(1) or m.group(2))
        if not kind or "s8" not in result_types(line.strip()):
            continue
        # No loop of the lane stage carries an int8 (the step's loop over
        # its rows holds a row's position-wide base, under its own name).
        assert kind != "while" or "/check/" not in line, line[:200]
        if "metadata=" not in line:
            if kind not in moves_nothing:
                nameless.add(kind)
        elif "check/scatter" in line:
            named.add(kind)
    assert {"scatter", "sort"} <= named
    assert nameless <= {"copy-start", "copy-done", "slice-start",
                        "slice-done", "custom-call", "copy"}


def test_count_step_compiles_for_four_chips_at_one_row_a_chip(topo, chip):
    """``jit_count_step`` as the whole-file count runs it on a v5e host: one
    host-inflated 32 MiB row a chip, flat, the count pair ``psum``'d. A
    chip holds its own row's bytes once (a ``(1, N)`` u8 block of a
    row-major operand would be tiled four rows high). The compiler sets
    1.18 GiB aside for the one row (1.1473 GiB of temporaries and the row;
    1.05 until PR 49 doubled the word view, 0.94 until PR 36 made it), as on
    a mesh of one chip (5.9 against 2.7 GiB before PR 30: ``PERF.md``
    §6)."""
    from spark_bam_tpu.parallel.mesh import make_shard_map_count_step
    from spark_bam_tpu.tpu.checker import LANE_BLOCK, WORD_ROW

    n = 4
    mesh, shape, repl = _mesh_shapes(topo, n)
    step = make_shard_map_count_step(mesh, 10, "data", funnel=True)
    compiled = step.lower(*_count_step_shapes(shape, repl, n, 1)).compile()
    ma = compiled.memory_analysis()
    assert WINDOW < ma.argument_size_in_bytes < WINDOW + (1 << 20)
    assert 9 << 27 < _device_bytes(compiled) < 5 << 28
    text = compiled.as_text()
    assert "all-reduce" in text  # the psum, and nothing gathers the rows
    assert "all-gather" not in text and "all-to-all" not in text
    assert not _position_wide_lookups(text, WINDOW)
    assert len(_word_views(text, WINDOW)) == 1
    assert _byte_gathers(text) == [f"u8[{LANE_BLOCK}]"]
    assert _word_fetches(text, WINDOW) == (
        [f"s32[{LANE_BLOCK},{WORD_ROW}]"] * 9, [])


# ----------------------------------------------------------------- small
def test_serve_step_compiles_at_serve_config_defaults(topo, chip):
    from spark_bam_tpu.parallel.mesh import make_shard_map_serve_step
    from spark_bam_tpu.serve.config import MAX_CONTIGS, ServeConfig
    from spark_bam_tpu.tpu.checker import WORD_ROW, lane_block

    cfg = ServeConfig()
    b, width = cfg.batch_rows, cfg.window + PAD
    mesh, shape, _ = _mesh_shapes(topo, 1)
    step = make_shard_map_serve_step(mesh, 10, "data", funnel=True)
    compiled = step.lower(
        shape((b, width), jnp.uint8), shape((b,), jnp.int32),
        shape((b,), jnp.bool_), shape((b,), jnp.int32),
        shape((b,), jnp.int32), shape((b, MAX_CONTIGS), jnp.int32),
        shape((b,), jnp.int32),
    ).compile()
    assert _device_bytes(compiled) < HBM
    text = compiled.as_text()
    assert not _position_wide_lookups(text, b * cfg.window)
    assert len(_word_views(text, cfg.window)) == 1
    assert _byte_gathers(text) == [f"u8[{lane_block(cfg.window)}]"]
    assert _word_fetches(text, cfg.window) == (
        [f"s32[{lane_block(cfg.window)},{WORD_ROW}]"] * 9, [])
    # The tick pays for its rows' survivors: both passes of the lane stage
    # are loops whose trip count the device reads from the row.
    assert len(_whiles_of_no_constant_trip_count(text)) >= 2


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
