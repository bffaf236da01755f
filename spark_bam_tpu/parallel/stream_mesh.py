"""Mesh-sharded streaming workloads: one BAM across all chips — and hosts.

THE sharding engine for both scale tiers (VERDICT r4 item 6: one
codepath):

- single-host multi-chip: ``count_reads_sharded`` / ``check_bam_sharded``
  assemble rows over the local mesh (the CLI ``--sharded`` modes);
- multi-host: ``parallel/multihost.py --bam`` calls the same functions
  with ``num_processes``/``process_id`` — each process assembles only its
  own row slice, and the tiny reductions ride the global mesh's
  collectives (``lax.psum`` over ICI/DCN).

Row discipline (the property multi-host needs — any row computable from
``(path, metas)`` alone, no sequential carry):

- ``window_plan`` groups consecutive BGZF blocks into ≈window-sized
  uncompressed runs; row *g* OWNS group *g*'s uncompressed span, which
  tiles ``[0, total)`` exactly;
- each row's buffer extends past its owned span with following blocks
  until ≥ ``halo`` lookahead bytes are present (re-inflated overlap —
  ≤ halo + one block per row — traded for seam independence; the
  reference's analog is hadoop-bam re-reading across split edges,
  load/.../SplitRDD.scala:43-79);
- a chain that outruns even the halo reports an *escape*; any escape
  aborts the device pass and the file re-runs through ``StreamChecker``'s
  deferral-exact spans path (single device) — same policy as
  ``StreamChecker.count_reads``. On real data with the default halo this
  never triggers.

Every row is inflated on the host, on every backend, and every workload is
fed by ONE assembly (``_ShardedStream._assemble_rows``): a step's rows are
inflated side by side on a pool (the native inflater, the producer the
one-chip stream has), each straight into its place in the operand of the
chip that owns the row, check-bam's truth of a row filled beside it on the
same worker, and every chip's operands put straight to that chip. The count
runs the one-chip stream's check-and-count program on them
(``mesh.make_shard_map_count_step``), check-bam and full-check the
every-position steps. The next step is inflated and put while the chips run
the current one, and the host blocks a step is assembled in are kept from
step to step and from pass to pass (``inflate.FRAMES``): written anew every
step they would be fresh pages, and their faults the chips' wait.

Workloads (SURVEY.md §2.8 maps file/block data-parallelism onto per-core
batch pipelines; §2.9 replaces Spark accumulators with ``psum``):

- ``count_reads_sharded`` — the count-reads workload (reference
  docs/benchmarks.md:53-59);
- ``check_bam_sharded`` — check-bam validation: verdicts vs the
  ``.records`` indexed ground truth at every uncompressed position,
  confusion matrix accumulated via ``psum`` (reference
  CheckerApp.scala:59-93's accumulator pipeline).
"""

from __future__ import annotations

import bisect
import collections
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_bam_tpu import obs
from spark_bam_tpu.bam.header import read_header
from spark_bam_tpu.bgzf.block import MAX_BLOCK_SIZE
from spark_bam_tpu.bgzf.flat import inflate_blocks
from spark_bam_tpu.core.channel import open_channel
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.parallel.mesh import make_mesh, mesh_steps
from spark_bam_tpu.tpu import inflate
from spark_bam_tpu.tpu.checker import PAD
from spark_bam_tpu.tpu.inflate import DeviceObserver, window_plan
from spark_bam_tpu.tpu.stream_check import (
    StreamChecker,
    _next_pow2,
    pad_contig_lengths,
)


def _plan_rows(metas: list, fresh: int, n_global: int, num_processes: int):
    """The row-planning arithmetic shared by the sharded engine and
    ``host_shard_plan`` (one implementation — a scheduler plan must match
    what the engine actually reads BY CONSTRUCTION): block groups, each
    group's first block index and uncompressed size/flat start, and the
    per-process row count (global rows padded to a multiple of the device
    count so every process loops identical step counts)."""
    groups = window_plan(metas, fresh)
    sizes = np.array(
        [sum(m.uncompressed_size for m in g) for g in groups], dtype=np.int64
    )
    flat_starts = np.zeros(len(groups), dtype=np.int64)
    first_block = np.zeros(len(groups), dtype=np.int64)
    if len(groups):
        np.cumsum(sizes[:-1], out=flat_starts[1:])
        np.cumsum([len(g) for g in groups[:-1]], out=first_block[1:])
    n_rows = -(-max(len(groups), 1) // n_global) * n_global
    per_proc = n_rows // num_processes
    return groups, sizes, flat_starts, first_block, per_proc


def _halo_block_range(
    metas: list, groups: list, first_block, g0: int, g1: int, halo: int
) -> tuple[int, int]:
    """Block index range [b0, b1) covering groups [g0, g1) plus trailing
    blocks until ≥ ``halo`` lookahead bytes — the engine's row extension
    and the plan's per-host read range, one implementation."""
    b0 = int(first_block[g0])
    b1 = b0 + sum(len(groups[g]) for g in range(g0, g1))
    extra = 0
    while b1 < len(metas) and extra < halo:
        extra += metas[b1].uncompressed_size
        b1 += 1
    return b0, b1


#: Device bytes one vmapped window row of the mesh steps may need, per
#: window byte: the v5e compiler reports 2.7 GiB of temporaries for one
#: 32 MiB row and 15.9 GiB for five (more than the chip holds), so the
#: budget is 128x, which admits three.
_ROW_DEVICE_BYTES_PER_WINDOW_BYTE = 128


def _rows_fitting_device(device, kernel_window: int) -> int:
    """Rows of ``kernel_window`` one device can check in a single step: by
    the memory the device reports (a TPU does; the CPU backend reports
    none, and is not bounded here)."""
    stats = device.memory_stats()
    limit = (stats or {}).get("bytes_limit")
    if not limit:
        return 1 << 30
    return max(
        1, limit // (_ROW_DEVICE_BYTES_PER_WINDOW_BYTE * (kernel_window + PAD))
    )


#: What a slot's ``used`` holds where its row was written and no truth set.
_NO_TRUTH = np.empty(0, dtype=np.int64)


class _ShardedStream:
    """Shared plumbing: plan the block groups, assemble this process's row
    slice into mesh-wide batches (double-buffered), build sharded args."""

    def __init__(
        self,
        path,
        config: Config,
        mesh,
        window_uncompressed: int | None,
        halo: int | None,
        metas: list | None,
        workload: str = "count",
        num_processes: int = 1,
        process_id: int = 0,
        chunk_bytes: int = 192 << 20,
    ):
        from spark_bam_tpu.bgzf.index_blocks import blocks_metadata

        self.path = path
        self.config = config
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_global = int(self.mesh.devices.size)
        self.axis = self.mesh.axis_names[0]
        self.num_processes = num_processes
        self.process_id = process_id

        # The phases of a pass's head on the thread that feeds the chips,
        # each under a span of its own: the header and the contig lengths'
        # put, the member walk, the row plan (docs/observability.md).
        with obs.span("load.open", path=str(path)):
            header = read_header(path)
            lens_list = header.contig_lengths.lengths_list()
            self.num_contigs = len(lens_list)
            self.lengths = pad_contig_lengths(
                np.asarray(lens_list, dtype=np.int32))
            self.header_end = header.uncompressed_size
            self.lengths_d = jax.device_put(
                jnp.asarray(self.lengths), NamedSharding(self.mesh, P()))
            self.nc = jnp.int32(self.num_contigs)

        self.fresh = window_uncompressed or config.window_size
        halo = config.halo_size if halo is None else halo
        self.halo = min(halo, self.fresh // 2)
        if metas is None:
            # The whole-file header walk, under the name the one-chip
            # stream gives it (``InflatePipeline``).
            with obs.span("bgzf.read", kind="metadata_scan", path=str(path)):
                metas = list(blocks_metadata(path))
        self.metas = metas
        with obs.span("mesh.plan", members=len(metas)):
            self._plan(num_processes, chunk_bytes)
        self._zero_rows: dict = {}
        # The host blocks of the steps handed to the consumer, oldest first,
        # until their results are known to be read (``_hand_back``).
        self._handed: collections.deque = collections.deque()
        # What the steps' spans say they serve: ``count`` (rows flat in a
        # device's operand, the header's bytes owned by nobody) or one of
        # the every-position workloads ``check_bam`` / ``full_check`` (a
        # row dimension, header bytes included).
        self.workload = workload
        self.every_position = workload != "count"

    def _plan(self, num_processes: int, chunk_bytes: int) -> None:
        """The row plan and what follows from it: the kernel's width, this
        process's devices, the rows a step holds, the rows' sharding."""
        (
            self.groups, self.sizes, self.flat_starts, self.first_block,
            self.per_proc,
        ) = _plan_rows(self.metas, self.fresh, self.n_global, num_processes)
        self.total = int(self.sizes.sum())
        # Row buffer bound: owned span (≤ fresh, or one oversized block) +
        # halo + ≤ one block of halo-extension overshoot.
        row_bound = max(self.fresh, MAX_BLOCK_SIZE) + self.halo + MAX_BLOCK_SIZE
        self.kernel_window = _next_pow2(
            min(row_bound, max(self.total, 1 << 16))
        )

        n_local = self.n_local = self.n_global // num_processes
        # THIS process's devices: another process's reports no memory to
        # this one, and takes no operand from it.
        self.local_devices = [
            d for d in self.mesh.devices.flat
            if d.process_index == jax.process_index()
        ]
        kw = self.kernel_window
        self.step_rows_local = n_local * min(
            max(1, chunk_bytes // ((kw + PAD) * max(n_local, 1))),
            _rows_fitting_device(self.local_devices[0], kw),
        )
        if self.per_proc:
            self.step_rows_local = min(self.step_rows_local, self.per_proc)
        self.row_sharding = NamedSharding(self.mesh, P(self.axis))

    # ------------------------------------------------------------- assembly
    def _row(self, ch, g: int, into: np.ndarray | None = None):
        """Inflate global row ``g`` on the host, into the frame ``into``
        where one is given (what is left of the frame zeroed), else into a
        buffer of its own: returns (buf, n, at_eof)."""
        b0, b1 = _halo_block_range(
            self.metas, self.groups, self.first_block, g, g + 1, self.halo
        )
        view = inflate_blocks(
            ch, self.metas[b0:b1], threads=8,
            into=None if into is None else (into, 0))
        return view.data, view.size, b1 == len(self.metas)

    def _row_span(self, g: int, n: int, at_eof: bool, header_clamp: bool):
        """Row ``g``'s owned span ``[lo, own)`` in row-local offsets, given
        the ``n`` bytes its buffer holds: ``(own, lo)``."""
        own = n if at_eof and g == len(self.groups) - 1 else int(self.sizes[g])
        he = self.header_end if header_clamp else 0
        return own, min(max(he - int(self.flat_starts[g]), 0), own)

    def _steps(self, assemble):
        """Yield ``(step operands, positions_done, c0)`` per step (``c0`` =
        the step's first process-local row index; ``row_slots`` says where
        its rows lie), assembling and putting the next step's rows on one
        worker thread while the caller's device work runs (one step of
        lookahead): ``assemble(ch, c0)`` runs on that thread and gives the
        operands and the host blocks they were put from."""
        if not self.per_proc:
            return
        steps = list(range(0, self.per_proc, self.step_rows_local))
        # The assembly thread begins with an empty context: it takes the
        # pass's here (``mesh.assemble`` / ``mesh.h2d`` in the pass's trace).
        assemble = obs.trace.carried(assemble)
        with open_channel(self.path) as ch, ThreadPoolExecutor(1) as pool:
            pending = pool.submit(assemble, ch, steps[0])
            for i, c0 in enumerate(steps):
                # The wait for a step's operands: what the lookahead
                # exists to hide (all of the first step's assembly).
                with obs.span("mesh.stall", c0=c0):
                    arrays, blocks = pending.result()
                # The caller is back for step i, so it has read step i - 2
                # (the count reads a step's totals one step late): that
                # step's blocks are what step i + 1 is assembled in. Three
                # steps' blocks are alive: assembled, dispatched, unread.
                self._hand_back(unread=1)
                if i + 1 < len(steps):
                    pending = pool.submit(assemble, ch, steps[i + 1])
                # Highest global row completed this step (process-major row
                # order: the last process owns the file's final groups).
                g_hi = min(
                    (self.num_processes - 1) * self.per_proc
                    + c0 + self.step_rows_local,
                    len(self.groups),
                ) - 1
                done = int(self.flat_starts[g_hi] + self.sizes[g_hi])
                self._handed.append(blocks)
                yield arrays, done, c0
            self._hand_back(unread=1)

    def _hand_back(self, unread: int) -> None:
        """Give the kept set the host blocks of the steps handed out, all
        but the newest ``unread``. On the chip a put that was waited for has
        copied its block; on the CPU backend it may alias it, so a block is
        not written again before the result of the step that read it is on
        the host."""
        while len(self._handed) > unread:
            for arrays, used in self._handed.popleft():
                inflate.FRAMES.give(
                    arrays, keep=3 * self.n_local, note=used)

    def release(self) -> None:
        """The caller has read every step it was handed: the last steps'
        blocks go back too, for the next pass. (A pass that ends otherwise
        drops them, which is always correct.)"""
        self._hand_back(unread=0)

    def forget(self) -> None:
        """A pass that starts over over these rows drops the blocks of the
        steps it had out: a step still running may read them."""
        self._handed.clear()

    def row_slots(self, c0: int) -> list[tuple[int, int, int]]:
        """Where the step at local row offset ``c0`` puts its rows:
        ``(global row, local device, slot on that device)`` for every live
        row, dealt round-robin over this process's devices so the chips of
        a step are loaded within one row of each other whatever the step's
        width (a last step of 8 rows in a step 12 wide lands 2 a chip, not
        3/3/2/0). A device's block of the step's operands is its slots in
        order."""
        g0 = self.process_id * self.per_proc + c0
        return [
            (g0 + j, j % self.n_local, j // self.n_local)
            for j in range(self.step_rows_local)
            if c0 + j < self.per_proc and g0 + j < len(self.groups)
        ]

    def step_row(self, c0: int, i: int) -> int:
        """The global row behind index ``i`` of a result the step at ``c0``
        gives a row, in the order its operands are sharded: process-major,
        then device-major (``row_slots``). A padding slot's is a row the
        file or the process does not have."""
        per_dev = self.step_rows_local // self.n_local
        p, local = divmod(i, self.step_rows_local)
        d, s = divmod(local, per_dev)
        return p * self.per_proc + c0 + s * self.n_local + d

    def _assemble_rows(self, ch, c0: int, rows_pool, truth=None):
        """One step's operands, ON the devices, for every workload, and the
        host blocks they were put from: the step's rows are inflated side by
        side (``rows_pool``; one ``mesh.row_inflate`` a row), each straight
        into its slot of its device's block, check-bam's truth of the row
        filled beside it on the same worker once it is known up to the row's
        end (``truth``, a ``_Truth``: ``checkbam.truth_wait``, then
        ``mesh.truth_fill``), and every device's operands go straight to
        that chip (``mesh.h2d``, waited for). The count's rows lie flat in a
        device's block (``mesh.make_shard_map_count_step`` has the layout),
        an every-position step's as ``(rows, W + PAD)`` with a ``(rows, W)``
        truth. Padding slots are zeros and own nothing; a device without a
        live row keeps resident operands of them.

        A device's blocks come from the kept set (``inflate.FRAMES``; made
        anew, zeros, when it has none: ``mesh.block_reuse`` is the share of
        the step's that were kept). With them comes ``used``, what each slot
        holds of the blocks' earlier step: None while it is zeros, else the
        offsets set in its truth. A live slot needs no clearing but those
        (the inflater zeroes what is left of the slot behind the row); a
        padding slot that was live is zeroed, row and truth."""
        kw = self.kernel_window
        width = kw + PAD
        per_dev = self.step_rows_local // self.n_local
        with_truth = truth is not None
        # A device's block as its step takes it.
        shape = (per_dev, width) if self.every_position else (per_dev * width,)
        specs = [((per_dev * width,), np.uint8)] + (
            [((per_dev, kw), np.bool_)] if with_truth else [])

        slots = self.row_slots(c0)
        bufs, kept = {}, 0
        for d in sorted({d for _g, d, _s in slots}):
            arrays, used = inflate.FRAMES.take(specs, np.zeros)
            kept += used is not None
            bufs[d] = (arrays, [None] * per_dev if used is None else used)
        if bufs:
            obs.observe("mesh.block_reuse", kept / len(bufs))
        k = self.step_rows_local
        ns = np.zeros(k, dtype=np.int32)
        eofs = np.zeros(k, dtype=bool)
        los = np.zeros(k, dtype=np.int32)
        owns = np.zeros(k, dtype=np.int32)

        def untrue(d, s):
            """Slot ``s`` of device ``d``'s truth, what its block's earlier
            step set there cleared."""
            arrays, used = bufs[d]
            if used[s] is not None:
                arrays[1][s][used[s]] = False
            return arrays[1][s]

        def fill(slot):
            g, d, s = slot
            arrays, used = bufs[d]
            with obs.span("mesh.row_inflate", row=g):
                _buf, n, at_eof = self._row(
                    ch, g, arrays[0][s * width: (s + 1) * width])
            i = d * per_dev + s  # device-major, as the operands are
            ns[i], eofs[i] = n, at_eof
            owns[i], los[i] = self._row_span(
                g, n, at_eof, not self.every_position)
            if with_truth:
                base = int(self.flat_starts[g])
                with obs.span("checkbam.truth_wait", row=g):
                    truth.wait(base + n)
                with obs.span("mesh.truth_fill", row=g):
                    used[s] = truth.fill(untrue(d, s), base, n)
            else:
                used[s] = _NO_TRUTH

        attrs = dict(c0=c0, rows=len(slots), workload=self.workload)
        with obs.span("mesh.assemble", **attrs):
            live = {(d, s) for _g, d, s in slots}
            for d, (arrays, used) in bufs.items():
                for s in range(per_dev):
                    if (d, s) not in live and used[s] is not None:
                        arrays[0][s * width: (s + 1) * width] = 0
                        if with_truth:
                            untrue(d, s)
                        used[s] = None
            rows = [rows_pool.submit(obs.trace.carried(fill), slot)
                    for slot in slots]
            # Every row is waited for before one's error is raised: a worker
            # reads the file's mapping, which the error's way out closes.
            wait(rows)
            for row in rows:
                row.result()
        nbytes = len(bufs) * per_dev * (width + (kw if with_truth else 0))
        with obs.span("mesh.h2d", bytes=nbytes, **attrs):
            shards = []  # a local device: its operands, on it
            for d, device in enumerate(self.local_devices):
                if d not in bufs and d in self._zero_rows:
                    shards.append(self._zero_rows[d])
                    continue
                # Resident operands may alias their host blocks for the
                # pass's life: those are made for them, and never kept.
                windows, *truth = (
                    bufs[d][0] if d in bufs
                    else [np.zeros(*spec) for spec in specs])
                shards.append([
                    jax.device_put(a, device)
                    for a in (windows.reshape(shape), *truth)])
                if d not in bufs:
                    self._zero_rows[d] = shards[-1]
            windows, *truth = (
                jax.make_array_from_single_device_arrays(
                    (self.n_global * one[0].shape[0],) + one[0].shape[1:],
                    self.row_sharding, list(one))
                for one in zip(*shards)
            )
            ns, eofs, los, owns = (
                jax.make_array_from_process_local_data(self.row_sharding, a)
                for a in (ns, eofs, los, owns)
            )
            args = [windows, ns, eofs, *truth, los, owns]
            # Waited for HERE, registry or none: the span is the transfer,
            # and the feeding thread is handed operands that have arrived.
            jax.block_until_ready(args)
        obs.count("mesh.rows", len(slots))
        obs.count("mesh.h2d_bytes", nbytes)
        return args + [self.lengths_d, self.nc], list(bufs.values())

    def row_batches(self, truth=None):
        """A workload's steps: ``(operands on the devices, done, c0)``;
        ``truth``: check-bam's, a ``_Truth``."""
        # Rows side by side, each on the inflater's own eight threads.
        with ThreadPoolExecutor(min(self.step_rows_local, 8)) as rows_pool:
            yield from self._steps(
                lambda ch, c0: self._assemble_rows(
                    ch, c0, rows_pool, truth)
            )


def _mostly_dirty(dirty: list, steps: int) -> bool:
    """The escape-everywhere guard: stop burning device work when the
    input is dirty nearly everywhere (undersized halo) — all-dirty early,
    or ≥90% dirty once enough steps have run (a lone clean step must not
    disable the guard)."""
    return (steps >= 4 and len(dirty) == steps) or (
        steps >= 8 and len(dirty) * 10 >= steps * 9
    )


class _RowGrowth:
    """The shared grown-buffer protocol of the escape-localized patch
    primitives: global row ``g``'s block range extended with halo
    lookahead, re-inflated at geometrically-doubled spans until the
    resolver is satisfied, with one adversarial-growth cap at
    ``(reads_to_check + 2) x max_read_size`` of lookahead."""

    def __init__(self, st: "_ShardedStream", g: int):
        self.st = st
        self.lo_abs = int(st.flat_starts[g])
        self.hi_abs = self.lo_abs + int(st.sizes[g])
        self.b0 = int(st.first_block[g])
        b_end = (
            int(st.first_block[g + 1]) if g + 1 < len(st.groups)
            else len(st.metas)
        )
        self.nblocks = len(st.metas)
        self.cap_bytes = (
            (st.config.reads_to_check + 2) * st.config.max_read_size
        )
        self.b1 = min(
            b_end + max(1, st.halo // MAX_BLOCK_SIZE + 1), self.nblocks
        )

    def view(self, ch):
        return inflate_blocks(ch, self.st.metas[self.b0: self.b1], threads=8)

    @property
    def at_eof(self) -> bool:
        return self.b1 == self.nblocks

    def grow(self, view_size: int) -> bool:
        """Double the block span; False once lookahead exceeds the cap
        (adversarial size fields — callers bail to the whole-file path)."""
        if view_size - (self.hi_abs - self.lo_abs) > self.cap_bytes:
            return False
        self.b1 = min(self.b0 + 2 * (self.b1 - self.b0), self.nblocks)
        return True


def _exact_row_true_positions(
    st: "_ShardedStream", g: int, lo_clamp: int, ch
):
    """Exact absolute record-start positions inside global row ``g``'s
    owned span, via the native tri-state walk over a geometrically-grown
    buffer (only still-uncertain candidates re-check per growth round);
    ``ch`` is an open channel on ``st.path`` (callers patch many rows —
    one open serves them all).

    The escape-localized patch primitive: a row whose device verdicts
    escaped (ultra chains beyond the halo) is re-derived from
    ``(path, metas)`` alone — the row discipline — without touching any
    other row. Returns None when the native library is unavailable or
    the lookahead outgrows the adversarial cap; callers fall back to the
    whole-file deferral-exact path, which bounds memory by
    construction."""
    from spark_bam_tpu.native.build import eager_check_window_native

    rg = _RowGrowth(st, g)
    lo_eval = max(rg.lo_abs, lo_clamp)
    if lo_eval >= rg.hi_abs:
        return np.empty(0, dtype=np.int64)
    lens = st.lengths[: st.num_contigs]
    # Candidates walk the owned span in bounded chunks: per-position state
    # (int64 cand_abs + uint8 res ≈ 9 bytes/position) over a whole
    # multi-MB row would cost ~9x the row's uncompressed size in host
    # memory; a 1 Mi-position chunk caps it at ~9 MB. ``rg`` persists
    # across chunks, so lookahead growth won by an early chunk serves the
    # rest of the row, and the inflated view is reused until it grows.
    chunk_positions = 1 << 20
    obs.count("mesh.patch_rows")
    view = rg.view(ch)
    hits: list[np.ndarray] = []
    for c_lo in range(lo_eval, rg.hi_abs, chunk_positions):
        c_hi = min(c_lo + chunk_positions, rg.hi_abs)
        cand_abs = np.arange(c_lo, c_hi, dtype=np.int64)
        res = np.full(len(cand_abs), 2, dtype=np.uint8)
        obs.count("mesh.patch_chunks")
        obs.observe("mesh.patch_chunk_positions", len(cand_abs))
        while True:
            unc = np.flatnonzero(res == 2)
            tri = eager_check_window_native(
                view.data, cand_abs[unc] - rg.lo_abs, lens,
                reads_to_check=st.config.reads_to_check, exact_eof=rg.at_eof,
            )
            if tri is None:
                return None
            res[unc] = tri
            if rg.at_eof or not (res == 2).any():
                hits.append(cand_abs[res == 1])
                break
            if not rg.grow(view.size):
                return None
            view = rg.view(ch)
    return (
        np.concatenate(hits) if hits else np.empty(0, dtype=np.int64)
    )


def _exact_row_flags(st: "_ShardedStream", g: int, ch):
    """Exact (fail_mask, reads_before) for global row ``g``'s owned span
    via the NumPy engine over a geometrically-grown buffer — the
    flags-projection counterpart of ``_exact_row_true_positions`` (the
    native tri-state walk yields verdicts only; full-check patches need
    the complete 19-flag masks, which only the full flag pass produces).
    Grows until every owned candidate is exact and unescaped (or EOF);
    returns None past the adversarial-growth cap."""
    from spark_bam_tpu.check.vectorized import check_flat

    rg = _RowGrowth(st, g)
    if rg.lo_abs >= rg.hi_abs:
        return np.empty(0, np.int32), np.empty(0, np.int32)
    span = rg.hi_abs - rg.lo_abs
    lens = st.lengths[: st.num_contigs]
    while True:
        view = rg.view(ch)
        # candidates=None takes the survivor-compaction fast path (~99%
        # of positions resolve elementwise from the flag pass); the
        # owned span is a slice of the all-position result.
        res = check_flat(
            view.data, lens, at_eof=rg.at_eof,
            reads_to_check=st.config.reads_to_check,
        )
        need = (res.escaped | ~res.exact)[:span]
        if rg.at_eof or not need.any():
            return (
                np.asarray(res.fail_mask[:span], dtype=np.int32),
                np.asarray(res.reads_before[:span], dtype=np.int32),
            )
        if not rg.grow(view.size):
            return None


def _step_global_rows(st: "_ShardedStream", c0: int) -> list[int]:
    """Global group indices a sharded step at local row offset ``c0``
    covered, across ALL processes (fill rows excluded) — the rows a
    dirty-step patch must recompute so every process lands the same
    global result."""
    rows = []
    for p in range(st.num_processes):
        for j in range(c0, min(c0 + st.step_rows_local, st.per_proc)):
            g = p * st.per_proc + j
            if g < len(st.groups):
                rows.append(g)
    return rows


class _StepObserver(DeviceObserver):
    """The count steps' device times, taken OFF the thread that feeds the
    chips (as ``DeviceObserver`` does for the one-chip stream, and only
    under a live registry), under the mesh's names: ``mesh.step_device_ms =
    t_ready(k) − max(t_dispatch(k), t_ready(k−1))``."""

    @staticmethod
    def _observe(device_ms: float) -> None:
        obs.observe("mesh.step_device_ms", device_ms, unit="ms")


def _count_steps(st: "_ShardedStream", config: Config, progress):
    """The count pass over ``st``'s steps: ``(count, escapes, steps, dirty,
    whole_file)``.

    Step k+1's operands are put and its program dispatched BEFORE step k's
    totals are read, so the devices never wait between steps for a
    transfer or for the host; totals, and with them the escape guard, are
    one step late."""
    # Cached per (mesh, params): repeat invocations — and the serve/
    # daemon's ticks — reuse one traced executable instead of re-jitting.
    with obs.span("load.open", program="count_step"):
        step = mesh_steps(st.mesh, st.axis).count_step(
            reads_to_check=config.reads_to_check,
            funnel=config.funnel_enabled(),
        )
        observer = _StepObserver.maybe()
    batches = st.row_batches()
    count = escapes = steps = 0
    dirty: list[int] = []  # local row offsets (c0) of escaped steps

    def settle(out, done, c0) -> bool:
        """Read one step's totals; True when the pass should stop."""
        nonlocal count, escapes, steps
        totals = np.asarray(out)
        esc = int(totals[1])
        steps += 1
        obs.count("mesh.steps")
        if esc:
            obs.count("mesh.dirty_steps")
            obs.count("mesh.escapes", esc)
            # Escape-localized handling: the dirty STEP's device totals
            # are untrusted (an escaped chain's verdict can be wrong in
            # either direction), but every other step stands. Record the
            # step for a host-side exact patch instead of discarding the
            # whole device pass.
            escapes += esc
            dirty.append(c0)
        else:
            count += int(totals[0])
            # The funnel's evidence, as the one-device stream names it:
            # stage-0 survivors and the lanes run for them, over the rows.
            obs.count("funnel.survivors", int(totals[2]))
            obs.count("funnel.lanes", int(totals[3]))
        if progress is not None:
            progress(steps, done, st.total)
        # Pathological guard: if nearly every step escapes, the halo is
        # undersized for this input — stop burning device work and take
        # the whole-file exact path.
        return _mostly_dirty(dirty, steps)

    whole_file = False  # True: the guard stopped the pass
    unread = None       # the dispatched step whose totals are not read yet
    # Closing the batch generator on early exit (escape break, error)
    # shuts down the assembly pool and channel before any fallback
    # reopens the file.
    try:
        for args, done, c0 in batches:
            with obs.span("mesh.step", workload="count", c0=c0):
                t_dispatch = time.perf_counter()
                out = step(*args)  # the step's totals
                obs.dispatched()
                if observer is not None:
                    observer.window(None, 0.0, out, t_dispatch)
                whole_file = unread is not None and settle(*unread)
            unread = (out, done, c0)
            if whole_file:
                break
        if not whole_file and unread is not None:
            with obs.span("mesh.step", workload="count", c0=unread[2]):
                whole_file = settle(*unread)
    finally:
        with obs.span("load.drain"):
            batches.close()
            if observer is not None:
                observer.close()
    if whole_file and unread is not None:
        # The step in flight is dropped, and waited for: the whole-file
        # path that takes over finds an idle mesh.
        jax.block_until_ready(unread[0])
    st.release()
    return count, escapes, steps, dirty, whole_file


def count_reads_sharded(
    path,
    config: Config = Config(),
    mesh=None,
    window_uncompressed: int | None = None,
    halo: int | None = None,
    metas: list | None = None,
    progress: Callable[[int, int, int], None] | None = None,
    stats_out: dict | None = None,
    num_processes: int = 1,
    process_id: int = 0,
    chunk_bytes: int = 192 << 20,
) -> int:
    """Record count of ``path`` computed across ``mesh`` (default: all
    devices; multi-host callers pass their process coordinates and get the
    globally reduced count on every process). ``progress(steps_done,
    positions_done, total_positions)`` fires as each sharded step's totals
    are read. Every row is inflated on the host and checked on the chip
    that owns it (``jit_count_step``). ``stats_out``, when given, receives
    ``{"steps", "escapes", "fallback", "patched_steps", "rows"}`` — escaped
    steps are normally re-derived exactly on host (``patched_steps`` counts
    them, and ``check.count_escape_retries``; the other steps' device totals
    stand); ``fallback`` is True only when the whole-file exact path ran
    instead (no native library, adversarial lookahead growth, or an
    escape-everywhere input; ``check.fused_demotions``)."""
    st = _ShardedStream(
        path, config, mesh, window_uncompressed, halo, metas,
        num_processes=num_processes, process_id=process_id,
        chunk_bytes=chunk_bytes,
    )
    count, escapes, steps, dirty, whole_file = _count_steps(
        st, config, progress)

    patched = None
    if dirty and not whole_file:
        patched = 0
        rows = {g for c0 in dirty for g in _step_global_rows(st, c0)}
        with open_channel(path) as ch:
            for g in rows:
                pos = _exact_row_true_positions(st, g, st.header_end, ch)
                if pos is None:
                    patched = None  # no native lib / adversarial growth
                    break
                patched += len(pos)
        if patched is not None:
            obs.count("check.count_escape_retries", len(dirty))

    if stats_out is not None:
        stats_out.update(
            steps=steps, escapes=escapes,
            fallback=bool(escapes) and patched is None,
            patched_steps=0 if patched is None else len(dirty),
            rows=len(st.groups),
        )
    if escapes and patched is None:
        # Whole-file exact fallback (no native library, adversarial
        # lookahead growth, or an escape-everywhere input): resolve
        # through the single-device deferral path (reusing this pass's
        # block-metadata scan). Multi-host: every process computes the
        # same exact count — redundant but correct.
        obs.count("check.fused_demotions")
        return StreamChecker(
            path, config, window_uncompressed=st.fresh, halo=st.halo,
            metas=st.metas,
        ).count_reads()
    return count + (patched or 0)


def full_check_summary_sharded(
    path,
    config: Config = Config(),
    mesh=None,
    window_uncompressed: int | None = None,
    halo: int | None = None,
    metas: list | None = None,
    progress: Callable[[int, int, int], None] | None = None,
    k_positions: int = 4096,
    fallback_use_device: bool = True,
    stats_out: dict | None = None,
) -> dict:
    """The full-check workload's aggregations across the mesh — the third
    sharded workload (reference FullCheck.scala:112-417 as a Spark job;
    here one ``shard_map`` step per row batch): per-flag totals,
    considered-position count, and the critical / two-check sites with
    their masks. Same return shape as
    ``tpu.stream_check.full_check_summary_streaming`` plus ``devices``.

    Exactness policy mirrors the other sharded workloads: a step with
    deferred lanes (escaped or edge-inexact masks) keeps its device
    results OUT of the aggregation and its rows re-derive exactly on
    host (the escape-localized patch, via the NumPy engine's full flag
    pass over grown buffers). The whole-file single-device streaming
    summary remains the fallback for nearly-all-dirty inputs,
    adversarial lookahead growth, and per-row compaction overflow
    (> ``k_positions`` sites in one row) — ``devices`` = 1 then;
    ``fallback_use_device`` selects its engine (the CLI passes its
    hang-proof backend probe's verdict).
    Single-process only (the compacted site arrays are row-sharded device
    outputs; multi-host full-check would need an all-gather of variable
    site lists)."""
    from spark_bam_tpu.check.flags import FLAG_NAMES

    if jax.process_count() > 1:
        raise NotImplementedError(
            "full_check_summary_sharded is single-process only (row-sharded "
            "site outputs are not multi-host addressable); run it on one "
            "host or use the single-device streaming summary"
        )
    st = _ShardedStream(
        path, config, mesh, window_uncompressed, halo, metas,
        workload="full_check",
    )
    step = mesh_steps(st.mesh, st.axis).full_step(
        reads_to_check=config.reads_to_check, k_positions=k_positions,
    )
    n_flags = len(FLAG_NAMES)
    agg = np.zeros(5 + n_flags, dtype=np.int64)
    crit_pos: list[np.ndarray] = []
    crit_mask: list[np.ndarray] = []
    two_pos: list[np.ndarray] = []
    two_mask: list[np.ndarray] = []
    fallback = False
    defers = 0
    dirty: list[int] = []  # local row offsets (c0) of deferred steps
    steps = 0
    batches = st.row_batches()
    try:
        for args, done, c0 in batches:
            with obs.span("mesh.step", workload="full_check", c0=c0):
                totals, ci, cm, ti, tm = step(*args)
                totals = np.asarray(totals).astype(np.int64)
            steps += 1
            obs.count("mesh.steps")
            if totals[4]:
                obs.count("mesh.dirty_steps")
                # Deferred lanes: the device masks for this STEP are not
                # exact — skip its totals/sites and patch its rows on
                # host below (escape-localized, like count/check-bam).
                defers += int(totals[4])
                dirty.append(c0)
                if _mostly_dirty(dirty, steps):
                    fallback = True
                    break
                if progress is not None:
                    progress(steps, done, st.total)
                continue
            agg += totals
            ci, cm, ti, tm = (np.asarray(a) for a in (ci, cm, ti, tm))
            # A step's rows in the file's order, not in the operands'.
            for g, j in sorted(
                    (st.step_row(c0, j), j) for j in range(ci.shape[0])):
                if g >= len(st.groups):
                    continue  # padding row: no sites by construction
                base = int(st.flat_starts[g])
                for idx, masks, acc_p, acc_m in (
                    (ci[j], cm[j], crit_pos, crit_mask),
                    (ti[j], tm[j], two_pos, two_mask),
                ):
                    sel = idx >= 0
                    if sel.any():
                        acc_p.append(base + idx[sel].astype(np.int64))
                        acc_m.append(masks[sel].astype(np.int32))
            if progress is not None:
                progress(steps, done, st.total)
    finally:
        batches.close()
    st.release()  # every step's totals were read as it was dispatched

    if dirty and not fallback:
        from spark_bam_tpu.check.flags import (
            BIT,
            considered_mask,
            num_failing_fields,
        )

        bit0 = int(BIT["tooFewFixedBlockBytes"])
        rows = {g for c0 in dirty for g in _step_global_rows(st, c0)}
        with open_channel(path) as ch:
            for g in rows:
                out = _exact_row_flags(st, g, ch)
                if out is None:
                    fallback = True  # adversarial lookahead growth
                    break
                fm, rb = out
                base = int(st.flat_starts[g])
                agg[0] += int((fm == 0).sum())
                agg[1] += int(((fm == bit0) & (rb == 0)).sum())
                considered = considered_mask(fm, rb)
                masked = fm[considered]
                for i in range(n_flags):
                    agg[5 + i] += int(((masked >> i) & 1).sum())
                nf = num_failing_fields(fm, rb)
                ones = np.flatnonzero(considered & (nf == 1))
                twos = np.flatnonzero(considered & (nf == 2))
                agg[2] += len(ones)
                agg[3] += len(twos)
                if len(ones):
                    crit_pos.append(base + ones)
                    crit_mask.append(fm[ones].astype(np.int32))
                if len(twos):
                    two_pos.append(base + twos)
                    two_mask.append(fm[twos].astype(np.int32))

    n_crit = sum(map(len, crit_pos))
    n_two = sum(map(len, two_pos))
    if not fallback and (n_crit != int(agg[2]) or n_two != int(agg[3])):
        fallback = True  # a row overflowed the compaction buffer
    if stats_out is not None:
        # ``fallback`` tells hardware smokes whether the MESH pass itself
        # produced the summary (same contract as count_reads_sharded).
        stats_out.update(
            steps=steps, fallback=fallback, defers=defers,
            patched_steps=0 if fallback else len(dirty),
        )
    if fallback:
        from spark_bam_tpu.tpu.stream_check import (
            full_check_summary_streaming,
        )

        out = full_check_summary_streaming(
            path, config, window_uncompressed=st.fresh, halo=st.halo,
            use_device=fallback_use_device, metas=st.metas,
        )
        out["devices"] = 1
        return out

    def cat(parts, dtype):
        return np.concatenate(parts) if parts else np.empty(0, dtype=dtype)

    cp, cm = cat(crit_pos, np.int64), cat(crit_mask, np.int32)
    tp_, tm_ = cat(two_pos, np.int64), cat(two_mask, np.int32)
    if dirty:
        # Patched rows appended their sites after the clean steps'; the
        # report lists sites in ascending file order — restore it. (The
        # streaming summary sorts its deferred re-emissions the same way,
        # so the two paths agree on site ORDER whenever they agree on the
        # site set — same-order output is a consequence of both sorting,
        # not a standalone guarantee.)
        o = np.argsort(cp, kind="stable")
        cp, cm = cp[o], cm[o]
        o = np.argsort(tp_, kind="stable")
        tp_, tm_ = tp_[o], tm_[o]
    return {
        "per_flag": {
            name: int(agg[5 + i]) for i, name in enumerate(FLAG_NAMES)
        },
        # passes (mask==0) and the bare at-EOF markers are the only owned
        # positions NOT considered; the total is host-derived so no
        # position-scale counter rides the collective.
        "considered": st.total - int(agg[0]) - int(agg[1]),
        "critical_positions": cp,
        "critical_masks": cm,
        "two_check_positions": tp_,
        "two_check_masks": tm_,
        "positions": st.total,
        "devices": st.n_global,
    }


def host_shard_plan(
    path,
    num_hosts: int,
    devices_per_host: int,
    config: Config = Config(),
    window_uncompressed: int | None = None,
    halo: int | None = None,
    metas: list | None = None,
) -> list[dict]:
    """The per-host IO footprint of a ``num_hosts × devices_per_host``
    sharded run BEFORE any backend comes up — the scheduler-facing
    locality surface (reference ``SplitRDD.preferredLocations``,
    load/.../SplitRDD.scala:43-79: tell the scheduler where the bytes are;
    here: tell it which bytes each process will read, so it can place
    processes near data or pre-warm caches).

    Returns one dict per host: ``host`` (process id), ``groups`` (owned
    block-group index range, end-exclusive), ``compressed_range`` (the
    [lo, hi) file byte range the host reads, INCLUDING its trailing halo
    overlap), ``uncompressed`` (owned flat bytes). Owned group ranges
    partition the file exactly; compressed ranges overlap by ≤ halo + one
    block at each seam. Uses the same row arithmetic as the sharded
    engine, so the plan is exact, not an estimate."""
    from spark_bam_tpu.bgzf.index_blocks import blocks_metadata

    fresh = window_uncompressed or config.window_size
    h = config.halo_size if halo is None else halo
    h = min(h, fresh // 2)
    metas = list(blocks_metadata(path)) if metas is None else metas
    n_global = num_hosts * devices_per_host
    # The engine's own planning arithmetic (_plan_rows/_halo_block_range):
    # the plan matches what the engine reads by construction.
    groups, sizes, _flat_starts, first_block, per_proc = _plan_rows(
        metas, fresh, n_global, num_hosts
    )

    plan = []
    for p in range(num_hosts):
        g0 = min(p * per_proc, len(groups))
        g1 = min((p + 1) * per_proc, len(groups))
        if g0 == g1:
            plan.append({
                "host": p, "groups": (g0, g0),
                "compressed_range": (0, 0), "uncompressed": 0,
            })
            continue
        b0, b1 = _halo_block_range(metas, groups, first_block, g0, g1, h)
        lo = metas[b0].start
        hi = metas[b1 - 1].start + metas[b1 - 1].compressed_size
        plan.append({
            "host": p,
            "groups": (g0, g1),
            "compressed_range": (int(lo), int(hi)),
            "uncompressed": int(sizes[g0:g1].sum()),
        })
    return plan


#: Text of the sidecar the truth's loader parses at a time: one piece is a
#: few ms of numpy beside the assembly's threads (PERF.md, PR 45: the sizes
#: tried on the chip).
TRUTH_PIECE_BYTES = 512 << 10
#: ``_Truth``'s reach once its loader has ended: past every offset.
_ALL = np.iinfo(np.int64).max


class _TruthUnordered(Exception):
    """The sidecar is not in file order: what a row was filled with from
    the prefix may lack a truth, and the pass starts over (``_Truth.sort``)."""


class _Truth:
    """check-bam's ``.records`` ground truth as absolute flat offsets,
    loaded BESIDE the steps: one thread (it carries the pass's trace; span
    ``checkbam.truth_load`` around the whole load) parses the sidecar a
    piece at a time (no object a record), maps the piece through the block
    table and appends it, and a row is filled as soon as the truth is known
    up to the row's end. The sidecar is written in file order (upstream's
    ``IndexRecords``, ``bam/index_records``), which is what lets a prefix
    of it be the whole truth of a prefix of the file; the loader checks
    that on every piece, and a sidecar that is not in order raises
    ``_TruthUnordered`` from every wait until ``sort`` has made it whole.
    The loader's own error (a line that is no position, a block position
    the table lacks) is raised by whichever wait meets it first."""

    def __init__(self, path, records_path, metas,
                 piece_bytes: int = TRUTH_PIECE_BYTES):
        self.path = path
        self.records_path = (
            str(path) + ".records" if records_path is None else records_path)
        self._metas = metas
        self._piece_bytes = piece_bytes
        # Appended by the loader alone, in file order: the pieces, and each
        # piece's last offset (what a row's fill finds its pieces by).
        self._pieces: list[np.ndarray] = []
        self._lasts: list[int] = []
        self._cond = threading.Condition()
        self._covered = -1       # every offset below this one is known
        self._unordered = False
        self._error: Exception | None = None
        self._stop = False
        self._thread = threading.Thread(
            target=obs.trace.carried(self._load), name="checkbam-truth",
            daemon=True)
        self._thread.start()

    def _load(self) -> None:
        error = None
        try:
            with obs.span("checkbam.truth_load", path=str(self.records_path)):
                self._parse()
        except Exception as e:  # the thread's boundary: a wait raises it
            error = e
        with self._cond:
            self._error = error
            self._covered = _ALL
            self._cond.notify_all()

    def _parse(self) -> None:
        from spark_bam_tpu.bam.index_records import iter_records_arrays
        from spark_bam_tpu.bgzf.flat import metas_block_table

        block_starts, block_flat = metas_block_table(self._metas)
        for blocks, offs in iter_records_arrays(
                self.records_path, self._piece_bytes):
            if self._stop:
                return
            idx = np.searchsorted(block_starts, blocks)
            if idx.max() >= len(block_starts) or not np.array_equal(
                    block_starts[idx], blocks):
                raise ValueError(
                    f"{self.records_path}: block positions not in "
                    f"{self.path}'s block table (stale sidecar?)")
            flats = block_flat[idx] + offs
            ordered = flats[0] >= self._covered and bool(
                (flats[1:] >= flats[:-1]).all())
            with self._cond:
                self._pieces.append(flats)
                self._lasts.append(int(flats[-1]))
                self._unordered |= not ordered
                self._covered = self._lasts[-1]
                self._cond.notify_all()

    def wait(self, end: int) -> None:
        """Until every true offset below ``end`` is known."""
        with self._cond:
            self._cond.wait_for(
                lambda: self._covered >= end or self._unordered)
        if self._error is not None:
            raise self._error
        if self._unordered:
            raise _TruthUnordered(self.records_path)

    def fill(self, row, base: int, n: int):
        """Sets in ``row``, a row's bool a position, the truth's offsets that
        lie in the ``n`` bytes the row holds from flat offset ``base`` on
        (``wait(base + n)`` has returned), and returns them (what the row's
        next user clears)."""
        end = base + n
        # The pieces that may hold such an offset: from the first whose last
        # offset reaches ``base`` to the first whose last reaches ``end``.
        lo = bisect.bisect_left(self._lasts, base)
        hi = bisect.bisect_left(self._lasts, end, lo) + 1
        found = []
        for piece in self._pieces[lo:hi]:
            i0, i1 = np.searchsorted(piece, (base, end))
            found.append(piece[i0:i1] - base)
        at = np.concatenate(found) if found else _NO_TRUTH
        row[at] = True
        return at

    def whole(self) -> np.ndarray:
        """Every true offset, sorted: what follows the last step asks for it
        (the loader is long done by then)."""
        self.wait(_ALL)
        if len(self._pieces) > 1:
            self._become(np.concatenate(self._pieces))
        return self._pieces[0] if self._pieces else _NO_TRUTH

    def sort(self) -> None:
        """A sidecar out of order, loaded whole and sorted first (``np.sort``
        over all of it: the load before PR 45); every wait returns at once."""
        self._thread.join()
        with self._cond:
            self._unordered = False
        self._become(np.sort(self.whole()))

    def _become(self, flats: np.ndarray) -> None:
        """One piece holds them all (and there are some)."""
        with self._cond:
            self._pieces = [flats]
            self._lasts = [int(flats[-1])]

    def close(self) -> None:
        """The loader stopped and joined: no thread outlives the pass."""
        with self._cond:
            self._stop = True
        self._thread.join()


def _in_sorted(values: np.ndarray, among: np.ndarray) -> np.ndarray:
    """Which of ``values`` are in the sorted ``among`` (a binary search a
    value: the truth of a 60 GB file is 170 million offsets)."""
    if not len(among):
        return np.zeros(len(values), dtype=bool)
    i = np.minimum(np.searchsorted(among, values), len(among) - 1)
    return among[i] == values


def _confusion(pred: np.ndarray, truth: np.ndarray):
    """``(tp, fp positions, fn positions)`` of sorted predicted record
    starts against sorted true ones."""
    hit = _in_sorted(pred, truth)
    return int(hit.sum()), pred[~hit], truth[~_in_sorted(truth, pred)]


def _confusion_result(tp: int, fp, fn, total: int, devices: int) -> dict:
    return {
        "true_positives": tp,
        "false_positives": len(fp),
        "false_negatives": len(fn),
        "true_negatives": total - tp - len(fp) - len(fn),
        "positions": total,
        "devices": devices,
        "false_positive_positions": fp,
        "false_negative_positions": fn,
    }


def check_bam_sharded(
    path,
    config: Config = Config(),
    mesh=None,
    records_path=None,
    window_uncompressed: int | None = None,
    halo: int | None = None,
    metas: list | None = None,
    progress: Callable[[int, int, int], None] | None = None,
    num_processes: int = 1,
    process_id: int = 0,
) -> dict:
    """check-bam across the mesh: the vectorized checker's verdict vs the
    ``.records`` indexed ground truth at **every uncompressed position** of
    the file (header bytes included — reference check-bam semantics), the
    confusion matrix ``psum``'d per sharded step.

    Returns ``{"true_positives", "false_positives", "false_negatives",
    "true_negatives", "positions", "devices", "false_positive_positions",
    "false_negative_positions"}`` (``devices`` = the mesh size the verdicts
    actually ran on). The two position lists are sorted absolute flat
    offsets (int64), complete: every position where the verdict stands
    without the truth (``fp``) or the truth without the verdict (``fn``),
    read from each step's mismatch list (``mesh.MISMATCH_LIST`` slots a
    row). A row with more mismatches than slots is re-derived exactly on
    the host (``checkbam.list_overflows``), as the rows of a step with
    escaped chains are (``mesh.dirty_steps``); when that cannot be done the
    whole file goes through the single-device deferral-exact spans path
    (``check.fused_demotions``), so the returned matrix and lists are
    always exact. The sidecar is parsed beside the steps (``_Truth``); one
    whose lines are not in file order starts the steps over with the truth
    sorted first (``checkbam.truth_restarts``), to the same answer.
    """
    st = _ShardedStream(
        path, config, mesh, window_uncompressed, halo, metas,
        workload="check_bam", num_processes=num_processes,
        process_id=process_id,
    )
    # The truth is loaded beside what follows: the first rows wait for the
    # part of the sidecar that lies before their end, nothing for all of it.
    truth = _Truth(path, records_path, st.metas)
    try:
        try:
            return _check_bam_steps(st, truth, progress)
        except _TruthUnordered:
            # Rows filled from a prefix of such a sidecar may lack a truth:
            # the pass starts over with the truth whole and sorted first.
            obs.count("checkbam.truth_restarts")
            truth.sort()
            st.forget()
            return _check_bam_steps(st, truth, progress)
    finally:
        truth.close()


def _check_bam_steps(st: _ShardedStream, truth: _Truth, progress) -> dict:
    """``check_bam_sharded``'s steps over ``st``'s rows and what follows
    the last one."""
    from spark_bam_tpu.parallel.mesh import MISMATCH_LIST

    path, config = st.path, st.config
    if st.num_processes > 1:
        # Every process leaves the steps' collectives at the same step: an
        # error of the loader or a start over is met before the first one.
        truth.whole()
    with obs.span("load.open", program="confusion_step"):
        step = mesh_steps(st.mesh, st.axis).confusion_step(
            reads_to_check=config.reads_to_check,
            funnel=config.funnel_enabled(),
        )
        observer = _StepObserver.maybe()

    # Device stats are [tp, fp, fn, escapes, survivors, lanes] — record-scale
    # counters only.
    # Position totals and tn are host-derived (owned spans tile [0, total)
    # exactly), which keeps the device reduction int32-safe at mesh scale.
    agg = np.zeros(3, dtype=np.int64)
    differ: list[np.ndarray] = []  # flat offsets where verdict != truth
    steps = 0
    dirty: list[int] = []     # local row offsets (c0) of escaped steps
    overflowed: set = set()   # global rows with more mismatches than slots
    whole_file = False
    batches = st.row_batches(truth)
    try:
        for args, done, c0 in batches:
            with obs.span("mesh.step", workload="check_bam", c0=c0):
                t_dispatch = time.perf_counter()
                out = step(*args)
                obs.dispatched()
                if observer is not None:
                    observer.window(None, 0.0, out[0], t_dispatch)
                totals, at, counts = (np.asarray(a) for a in out)
            steps += 1
            obs.count("mesh.steps")
            # The funnel's evidence (see count_reads_sharded): the rows'
            # stage-0 survivors and the lanes the step ran for them.
            obs.count("funnel.survivors", int(totals[4]))
            obs.count("funnel.lanes", int(totals[5]))
            obs.observe("mesh.step_lanes", int(totals[5]))
            if totals[3]:
                obs.count("mesh.dirty_steps")
                # Escape-localized handling (see count_reads_sharded):
                # the dirty step's confusion counters and list are
                # untrusted and its rows re-derive exactly on host below.
                dirty.append(c0)
            else:
                agg += totals[:3]
                # The gathered lists lie as the operands are sharded.
                for i in np.flatnonzero(counts):
                    g = st.step_row(c0, int(i))
                    if counts[i] > MISMATCH_LIST:
                        overflowed.add(int(g))
                    else:
                        differ.append(
                            st.flat_starts[g]
                            + at[i, : counts[i]].astype(np.int64))
            if progress is not None:
                progress(steps, done, st.total)
            if _mostly_dirty(dirty, steps):
                whole_file = True
                break
    finally:
        with obs.span("load.drain", what="close"):
            batches.close()
            if observer is not None:
                observer.close()
    st.release()  # every step's lists were read as it was dispatched
    # What follows the last step: the rows of dirty steps and of overflowed
    # lists re-derived on the host, the listed positions sorted and split
    # by the truth, the matrix.
    with obs.span("load.drain", what="result"):
        truth_flats = truth.whole()
        redo = {g for c0 in dirty for g in _step_global_rows(st, c0)}
        if (redo or overflowed) and not whole_file:
            obs.count("checkbam.list_overflows", len(overflowed))
            with open_channel(path) as ch:
                for g in sorted(redo | overflowed):
                    pos = _exact_row_true_positions(st, g, 0, ch)
                    if pos is None:
                        whole_file = True  # no native lib / adversarial growth
                        break
                    lo = int(st.flat_starts[g])
                    i0, i1 = np.searchsorted(
                        truth_flats, (lo, lo + int(st.sizes[g])))
                    tp_g, fp_g, fn_g = _confusion(pos, truth_flats[i0:i1])
                    differ += [fp_g, fn_g]
                    if g in redo:  # an overflowed row's sums stand
                        agg += (tp_g, len(fp_g), len(fn_g))
        if whole_file:
            return _check_bam_exact(
                path, config, st.fresh, st.halo, st.metas, truth_flats,
                st.total,
            )
        differ = np.sort(
            np.concatenate(differ) if differ else np.empty(0, dtype=np.int64))
        missed = _in_sorted(differ, truth_flats)  # truth without the verdict
        obs.count("checkbam.mismatches", len(differ))
        if (len(differ) - int(missed.sum()), int(missed.sum())) != (
                int(agg[1]), int(agg[2])):
            raise RuntimeError(
                f"{path}: the steps listed {len(differ)} mismatches and summed "
                f"{int(agg[1])} + {int(agg[2])}"
            )
        return _confusion_result(
            int(agg[0]), differ[~missed], differ[missed], st.total, st.n_global)


def _check_bam_exact(
    path, config, fresh, halo, metas, truth_flats, total
) -> dict:
    """Escape fallback: predicted-boundary set from the deferral-exact
    single-device spans, confusion by set arithmetic. It leaves the mesh,
    and says so (``check.fused_demotions``)."""
    obs.count("check.fused_demotions")
    checker = StreamChecker(
        path, config, window_uncompressed=fresh, halo=halo, metas=metas
    )
    parts = [base + np.flatnonzero(v) for base, v in checker.spans()]
    pred = (
        np.sort(np.concatenate(parts)) if parts
        else np.empty(0, dtype=np.int64)
    )
    tp, fp, fn = _confusion(pred, truth_flats)
    obs.count("checkbam.mismatches", len(fp) + len(fn))
    return _confusion_result(tp, fp, fn, total, 1)  # single-device
