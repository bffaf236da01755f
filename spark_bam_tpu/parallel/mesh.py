"""Device-mesh execution: sharded checking across chips.

The workload is data-parallel over windows of uncompressed bytes
(SURVEY.md §2.8-2.9): a batch of B windows shards across the mesh's ``data``
axis, every device runs the same check kernel on its shard, and the tiny
confusion-matrix / flag-histogram reductions ride ``psum`` over ICI —
replacing the reference's Spark accumulators (CheckerApp.scala:59-70).

Cross-shard record chains are handled the same way as cross-window chains on
one chip: each window carries a trailing halo of the next shard's bytes
(≤ a few MB — the "halo exchange" in SURVEY §2.9 is done host-side at batch
assembly; on multi-host deployments this is the only inter-host data motion).

``sharded_check_step`` is the framework's "training step" equivalent: the
jitted, mesh-partitioned unit of work the driver dry-runs for multi-chip
validation (``__graft_entry__.dryrun_multichip``).
"""

from __future__ import annotations

import functools
import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_bam_tpu import obs
from spark_bam_tpu.tpu.checker import PAD, check_window, count_window

#: Slots of the confusion step's mismatch list, a row: the row-local
#: positions of the owned positions where verdict and truth differ. A real
#: file holds a handful in all (upstream's ``1.bam``: 5 in 1.6 M positions);
#: a row with more reports its count and is re-derived on the host.
MISMATCH_LIST = 64


def make_mesh(devices=None, axis: str = "data") -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), (axis,))


def local_mesh(axis: str = "data") -> Mesh:
    """Mesh over THIS process's addressable devices — the per-host
    serving loop's mesh (fabric/worker.py). A step compiled over the
    global multi-host mesh is collective: every process must enter every
    dispatch, which deadlocks a worker answering only its own requests.
    Single-host, this is exactly ``make_mesh()``."""
    return make_mesh(jax.local_devices(), axis)


def _instrument_step(kind: str, step):
    """Wrap a jit'd mesh step so each call emits a ``mesh.dispatch`` span
    (joining whatever trace is bound — the batcher row's request trace).
    Measures host dispatch/enqueue time, not device compute: the arrays
    come back asynchronous, and the caller's own span (``serve.tick``,
    a turn later: the batcher launches the next tick first) covers the
    sync. When obs is disabled this is one enabled() check per
    dispatch."""

    def dispatch(*args):
        if not obs.enabled():
            return step(*args)
        with obs.span("mesh.dispatch", step=kind):
            return step(*args)

    dispatch.__wrapped__ = step
    return dispatch


class MeshSteps:
    """Resident per-mesh step registry: shardings and jit'd ``shard_map``
    steps built ONCE and reused for the mesh's lifetime.

    Every ``make_shard_map_*_step`` call closes over fresh Python
    functions, so calling a maker per request yields a distinct jit object
    and a full re-trace each time — fine for one-shot batch jobs, fatal
    for a serving daemon dispatching per tick. ``MeshSteps`` keys each
    step by its static parameters, so same-shape requests share one
    compiled executable (the serve/ tier's "build at startup, serve
    forever" contract — ROADMAP item 3).

    Thread-safe: the serving loop builds steps from worker threads.
    """

    def __init__(self, mesh: Mesh, axis: str = "data"):
        self.mesh = mesh
        self.axis = axis
        self.data_sharding = NamedSharding(mesh, P(axis))
        self.replicated = NamedSharding(mesh, P())
        self._steps: dict = {}
        self._lock = threading.Lock()

    def put(self, arr):
        """Place a batch-dim array with ``P(axis)`` sharding."""
        return jax.device_put(arr, self.data_sharding)

    def put_replicated(self, arr):
        return jax.device_put(arr, self.replicated)

    def _get(self, key, maker):
        with self._lock:
            step = self._steps.get(key)
            if step is None:
                step = self._steps[key] = _instrument_step(key[0], maker())
            return step

    def count_step(self, reads_to_check: int = 10, funnel: bool = False):
        return self._get(
            ("count", reads_to_check, funnel),
            lambda: make_shard_map_count_step(
                self.mesh, reads_to_check=reads_to_check, axis=self.axis,
                funnel=funnel,
            ),
        )

    def confusion_step(self, reads_to_check: int = 10, funnel: bool = False):
        return self._get(
            ("confusion", reads_to_check, funnel),
            lambda: make_shard_map_confusion_step(
                self.mesh, reads_to_check=reads_to_check, axis=self.axis,
                funnel=funnel,
            ),
        )

    def full_step(self, reads_to_check: int = 10, k_positions: int = 4096):
        return self._get(
            ("full", reads_to_check, k_positions),
            lambda: make_shard_map_full_step(
                self.mesh, reads_to_check=reads_to_check, axis=self.axis,
                k_positions=k_positions,
            ),
        )

    def serve_step(self, reads_to_check: int = 10, funnel: bool = False):
        return self._get(
            ("serve", reads_to_check, funnel),
            lambda: make_shard_map_serve_step(
                self.mesh, reads_to_check=reads_to_check, axis=self.axis,
                funnel=funnel,
            ),
        )

    def agg_step(self, plan, nc: int):
        """Sharded aggregate-reduction carry step (agg/kernels.py) for
        one (plan, contig-count) shape — the serve ``aggregate`` op's
        compiled-once tick. The plan is a frozen ``AggConfig`` and so
        hashes into the registry key like any other static param."""
        from spark_bam_tpu.agg.kernels import make_shard_map_agg_step

        return self._get(
            ("agg", plan, nc),
            lambda: make_shard_map_agg_step(
                self.mesh, plan, nc, axis=self.axis
            ),
        )


_mesh_steps: dict = {}
_mesh_steps_lock = threading.Lock()


def mesh_steps(mesh: Mesh, axis: str = "data") -> MeshSteps:
    """The process-wide ``MeshSteps`` registry for ``mesh`` — every tier
    (stream_mesh workloads, the serve/ daemon) shares the same compiled
    steps instead of rebuilding them per call."""
    key = (mesh, axis)
    with _mesh_steps_lock:
        st = _mesh_steps.get(key)
        if st is None:
            st = _mesh_steps[key] = MeshSteps(mesh, axis)
        return st


def init_distributed(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> int:
    """Multi-host bring-up: initialize jax.distributed (NCCL/MPI analog is
    XLA's ICI/DCN collectives; the reference's Spark cluster role).

    With no arguments, reads the standard JAX coordination env vars
    (JAX_COORDINATOR_ADDRESS etc.) or no-ops on single-host. Returns the
    global device count. Each host then feeds its own windows (the workload
    needs no cross-host data motion beyond ≤64 KiB halos at shard seams —
    SURVEY.md §2.9).
    """
    import os

    if coordinator or os.environ.get("JAX_COORDINATOR_ADDRESS"):
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    return len(jax.devices())


@functools.partial(jax.jit, static_argnames=("reads_to_check",))
def sharded_check_step(
    windows: jnp.ndarray,      # (B, W+PAD) uint8, batch-dim sharded over the mesh
    ns: jnp.ndarray,           # (B,) int32 valid byte counts
    at_eofs: jnp.ndarray,      # (B,) bool
    truth: jnp.ndarray,        # (B, W) bool: indexed ground truth (or zeros)
    lengths: jnp.ndarray,      # (Cmax,) int32, replicated
    num_contigs: jnp.ndarray,  # () int32
    reads_to_check: int = 10,
):
    """One sharded unit of work: per-window check + global stat reduction.

    Inputs carry their sharding (GSPMD): place the batch with
    ``shard_windows`` and XLA partitions the vmap across devices and lowers
    the stat sums to all-reduces over ICI.

    Returns (per-window verdicts (B, W) bool, escapes, global stats dict).
    """

    def one(window, n, at_eof, tr):
        res = check_window(
            window, lengths, num_contigs, n, at_eof, reads_to_check=reads_to_check
        )
        w = window.shape[0] - PAD
        with jax.named_scope("reduce"):
            in_range = jnp.arange(w, dtype=jnp.int32) < n
            v = res["verdict"] & in_range
            t = tr & in_range
            stats = jnp.stack(
                [
                    jnp.sum((v & t).astype(jnp.int32)),    # true positives
                    jnp.sum((v & ~t).astype(jnp.int32)),   # false positives
                    jnp.sum((~v & t).astype(jnp.int32)),   # false negatives
                    jnp.sum((~v & ~t).astype(jnp.int32)),  # true negatives
                    jnp.sum(in_range.astype(jnp.int32)),   # positions checked
                ]
            )
            return v, res["escaped"] & in_range, stats

    verdicts, escapes, stats = jax.vmap(one)(windows, ns, at_eofs, truth)
    totals = jnp.sum(stats, axis=0)
    return verdicts, escapes, {
        "true_positives": totals[0],
        "false_positives": totals[1],
        "false_negatives": totals[2],
        "true_negatives": totals[3],
        "positions": totals[4],
    }


def shard_windows(
    mesh: Mesh,
    windows: np.ndarray,
    axis: str = "data",
):
    """Place a (B, W+PAD) batch with batch-dim sharding over the mesh.

    Delegates to the mesh's cached ``MeshSteps`` shardings so repeated
    placements (a serving loop's per-tick batches) reuse one
    ``NamedSharding`` instead of constructing it per call."""
    return mesh_steps(mesh, axis).put(windows)


def make_shard_map_check_step(mesh: Mesh, reads_to_check: int = 10, axis: str = "data"):
    """Explicit-collective variant of the sharded step.

    Where ``sharded_check_step`` lets GSPMD infer the partitioning, this one
    is written per-shard with ``shard_map``: each device runs the kernel on
    its local windows and the stats reduce with an explicit ``lax.psum``
    over the mesh axis — the XLA collective riding ICI. Semantically
    identical; kept as the explicit form the multi-host deployment uses.
    """

    def check_step(windows, ns, at_eofs, truth, lengths, num_contigs):
        def one(window, n, at_eof, tr):
            res = check_window(
                window, lengths, num_contigs, n, at_eof,
                reads_to_check=reads_to_check,
            )
            w = window.shape[0] - PAD
            with jax.named_scope("reduce"):
                in_range = jnp.arange(w, dtype=jnp.int32) < n
                v = res["verdict"] & in_range
                t = tr & in_range
                return v, jnp.stack([
                    jnp.sum((v & t).astype(jnp.int32)),
                    jnp.sum((v & ~t).astype(jnp.int32)),
                    jnp.sum((~v & t).astype(jnp.int32)),
                    jnp.sum((~v & ~t).astype(jnp.int32)),
                    jnp.sum(in_range.astype(jnp.int32)),
                ])

        verdicts, stats = jax.vmap(one)(windows, ns, at_eofs, truth)
        with jax.named_scope("reduce"):
            totals = jax.lax.psum(jnp.sum(stats, axis=0), axis)  # ← ICI
        return verdicts, totals

    return jax.jit(
        jax.shard_map(
            check_step,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis), P(), P()),
            out_specs=(P(axis), P()),
            # The kernel's scan carries start from unvarying constants; skip
            # the replication check rather than thread pvary through shared
            # kernel code.
            check_vma=False,
        )
    )


def make_shard_map_count_step(
    mesh: Mesh, reads_to_check: int = 10, axis: str = "data",
    funnel: bool = False,
):
    """Sharded count-reads step, the whole-file count's step on every
    backend: each device runs the one-chip stream's window program
    (``checker.count_window``: the check and its owned-span count
    reduction, nothing scattered back over the window) on its rows of
    host-inflated bytes, and ``(boundary count, owned escapes, stage-0
    survivors, lanes run)`` all-reduces with ``lax.psum`` — the count-reads
    workload (reference docs/benchmarks.md:53-59) as one mesh-partitioned
    unit. Rows carry per-row owned spans [lo, own) so halo bytes and the
    BAM header are counted exactly once globally. The last two are the
    funnel's evidence (``funnel.survivors``, ``funnel.lanes``): record-scale
    in every step whose escapes read 0 (a row over its lane capacity
    escapes whole), which are the steps the caller reads them from.

    ``windows`` is the rows' concatenation, FLAT and sharded over the mesh
    axis (``(rows · (W+PAD),)`` u8), so a device's block is its own rows'
    bytes as the one-chip program takes them: a leading row dimension
    would cost a device holding one u8 row four times its bytes on a TPU
    (``(1, N)`` u8 is tiled four rows high) and a relayout in the program.
    A device with one row runs the window program on its block as it is;
    with more it splits the block and ``vmap``s. The per-row scalars are
    ``(rows,)`` in the same device-major order. The compiled program is
    ``jit_count_step``."""

    def one(window, n, at_eof, lo, own, lengths, nc):
        r = count_window(
            window, lengths, nc, n, at_eof, lo, own,
            reads_to_check=reads_to_check, funnel=funnel,
        )
        return jnp.stack([
            r["count"], r["esc_count"], r["survivors"], r["lanes"],
        ]).astype(jnp.int32)

    def count_step(windows, ns, at_eofs, los, owns, lengths, nc):
        rows = ns.shape[0]  # this device's
        if rows == 1:
            stats = one(windows, ns[0], at_eofs[0], los[0], owns[0],
                        lengths, nc)
        else:
            stats = jnp.sum(jax.vmap(
                lambda wd, n, e, lo, ow: one(wd, n, e, lo, ow, lengths, nc)
            )(windows.reshape(rows, -1), ns, at_eofs, los, owns), axis=0)
        with jax.named_scope("reduce"):
            return jax.lax.psum(stats, axis)  # ← ICI

    return jax.jit(
        jax.shard_map(
            count_step,
            mesh=mesh,
            in_specs=(P(axis),) * 5 + (P(), P()),
            out_specs=P(),
            check_vma=False,
        )
    )


def _rows_in_turn(one, *operands):
    """``one`` over a device's rows, one row after another inside the step
    (``lax.map``), not batched. Each row then runs the flat one-row program:
    its lane gathers read an ``(N,)`` row and not a row-major ``(k, N)``
    operand, its block loops run to its OWN survivors (under ``vmap`` every
    row runs to the rows' maximum, with a carry as wide as all rows selected
    a trip), and the step's temporaries are one row's. On the chip (PR 34,
    ``PERF.md`` §6): the confusion step of three 32 MiB rows 531.5 ms in
    turn against 842.7 batched (2.01 against 3.47 GiB of temporaries), the
    served step of eight 1 MiB rows 40.1 against 57.1 at the same block."""
    return jax.lax.map(lambda row: one(*row), operands)


def _list_positions(mask, slots: int, block: int = 1024):
    """The first ``slots`` set positions of a position-wide ``mask`` in
    ascending order (-1 beyond them) and the number set. Two levels: the
    count of every ``block`` positions (a reduction over rows of ``block``
    lanes: no 32-wide word packing, which a TPU lays out at four times its
    bytes), their prefix, then for each slot the block holding it and the
    rank inside that block's own ``block`` bits."""
    if mask.shape[0] % block:
        mask = jnp.pad(mask, (0, -mask.shape[0] % block))
    w = mask.shape[0]
    blocks = mask.reshape(w // block, block)
    per_block = jnp.sum(blocks, axis=1, dtype=jnp.int32)
    upto = jnp.cumsum(per_block)
    k = jnp.arange(slots, dtype=jnp.int32)
    b = jnp.minimum(
        jnp.searchsorted(upto, k + 1, side="left").astype(jnp.int32),
        w // block - 1)
    inside = k - (jnp.take(upto, b) - jnp.take(per_block, b))
    bits = jnp.take(blocks, b, axis=0)  # (slots, block)
    hit = bits & (jnp.cumsum(bits, axis=1, dtype=jnp.int32)
                  == inside[:, None] + 1)
    at = b * block + jnp.argmax(hit, axis=1).astype(jnp.int32)
    return jnp.where(k < upto[-1], at, jnp.int32(-1)), upto[-1]


def make_shard_map_confusion_step(
    mesh: Mesh, reads_to_check: int = 10, axis: str = "data",
    funnel: bool = False,
):
    """Sharded check-bam step: verdicts vs indexed truth at every owned
    position, the (tp, fp, fn, escapes) counters ``psum``'d over the mesh
    axis — the check-bam validation workload (reference
    CheckerApp.scala:59-70's accumulators) as one mesh-partitioned unit:
    per-row ``check_window`` + owned-span mask [lo, own), a device's rows
    one after another (``_rows_in_turn``). ``funnel=True`` runs the
    two-stage candidate funnel per row (verdicts are what this step
    projects; the funnel preserves them), its lane stage as many blocks as
    hold the row's survivors.

    Returns ``(totals, differ_pos, differ_count)``; ``totals`` is ``[tp, fp,
    fn, escapes, survivors, lanes]``, the last two the funnel's evidence
    (``funnel.survivors``, ``funnel.lanes``, ``mesh.step_lanes``): the rows'
    stage-0 survivors and the lanes their blocks ran. Beside the sums the step
    says WHERE verdict and truth differ (reference CheckerApp.scala:102-134
    prints those positions): ``differ_pos`` ``(rows, MISMATCH_LIST)`` int32,
    each row's owned mismatching positions in ascending row-local order, -1
    beyond them, and ``differ_count`` ``(rows,)``, the row's mismatches (a
    row with more than the slots is re-derived by the caller). A position in
    a row's halo is the next row's to report. Both are gathered over the
    mesh axis, so every process reads every row's, in the order the rows'
    operands are sharded.

    Every counter psum'd here is record-scale (≤ positions/40 per step),
    never position-scale: the reduction is int32 and a position-scale
    counter overflows past ~64 devices × 32 MB windows. Position totals
    and true negatives are host-derived: the caller knows its owned spans
    (tn = positions - tp - fp - fn). The compiled program is
    ``jit_confusion_step``."""

    def one(window, n, at_eof, tr, lo, own, lengths, num_contigs):
        res = check_window(
            window, lengths, num_contigs, n, at_eof,
            reads_to_check=reads_to_check, funnel=funnel,
        )
        w = window.shape[0] - PAD
        with jax.named_scope("reduce"):
            i = jnp.arange(w, dtype=jnp.int32)
            m = (i >= lo) & (i < own)
            v = res["verdict"] & m
            t = tr & m
            differ_pos, differ_count = _list_positions(v ^ t, MISMATCH_LIST)
            return jnp.stack([
                jnp.sum((v & t).astype(jnp.int32)),    # true positives
                jnp.sum((v & ~t).astype(jnp.int32)),   # false positives
                jnp.sum((~v & t).astype(jnp.int32)),   # false negatives
                jnp.sum((res["escaped"] & m).astype(jnp.int32)),
                res["survivors"], res["lanes"],
            ]), differ_pos, differ_count

    def confusion_step(windows, ns, at_eofs, truth, los, owns, lengths, nc):
        stats, differ_pos, differ_count = _rows_in_turn(
            lambda wd, n, e, t, lo, ow: one(wd, n, e, t, lo, ow, lengths, nc),
            windows, ns, at_eofs, truth, los, owns)
        with jax.named_scope("reduce"):
            sums = jnp.sum(stats, axis=0)
        # The step's cross-chip tail under a name of its own, so that a
        # device trace tells it from ``check`` and ``reduce``.
        with jax.named_scope("collect"):
            return (
                jax.lax.psum(sums, axis),  # ← ICI
                jax.lax.all_gather(differ_pos, axis, tiled=True),
                jax.lax.all_gather(differ_count, axis, tiled=True),
            )

    return jax.jit(
        jax.shard_map(
            confusion_step,
            mesh=mesh,
            in_specs=(P(axis),) * 6 + (P(), P()),
            out_specs=P(),
            check_vma=False,
        )
    )


def make_shard_map_full_step(
    mesh: Mesh, reads_to_check: int = 10, axis: str = "data",
    k_positions: int = 4096,
):
    """Sharded full-check step (the third mesh workload, after count-reads
    and check-bam): every owned position's 19-flag mask, reduced to the
    FullCheck report's aggregations (reference FullCheck.scala:112-417)
    in one mesh-partitioned unit.

    Returns ``(totals, crit_idx, crit_mask, two_idx, two_mask)``:

    - ``totals`` (replicated, ``psum`` over ICI): ``[passes, bare_eof,
      crit_ct, two_ct, defer_ct, per_flag[0..18]]``. ``passes`` (mask==0
      record starts) and ``bare_eof`` (the lone at-EOF marker rule) let
      the caller derive the position-scale ``considered`` total from its
      owned spans without a position-scale device counter. The per-flag
      counts ARE position-scale per step — int32 stays safe because one
      step's positions are bounded by the host chunk budget (≪ 2^31);
      callers accumulate across steps in int64.
    - ``crit_idx``/``crit_mask`` (row-sharded, (B, K)): per-row compacted
      window-relative positions (fill −1) and masks where exactly one
      check failed — the report's "critical" sites; ``two_*`` likewise
      for two-check sites. A row with more than K sites under-reports the
      compaction vs its count — callers detect the mismatch and fall back
      to the exact single-device path (same policy as escapes).
    - ``defer_ct``: owned lanes whose masks are not yet exact (escaped or
      edge-inexact — the lanes the streaming engine defers); any nonzero
      means the device pass must be abandoned for the deferral-exact path.
    """
    from spark_bam_tpu.check.flags import BIT, FLAG_NAMES

    bit0 = int(BIT["tooFewFixedBlockBytes"])
    n_flags = len(FLAG_NAMES)

    def one(window, n, at_eof, lo, own, lengths, num_contigs):
        res = check_window(
            window, lengths, num_contigs, n, at_eof,
            reads_to_check=reads_to_check,
        )
        w = window.shape[0] - PAD
        return row_report(res, w, lo, own)

    @jax.named_scope("reduce")
    def row_report(res, w, lo, own):
        i = jnp.arange(w, dtype=jnp.int32)
        m = (i >= lo) & (i < own)
        fm = jnp.where(m, res["fail_mask"], 0)
        rb = jnp.where(m, res["reads_before"], 0)
        passes = jnp.sum((m & (fm == 0)).astype(jnp.int32))
        bare_eof = jnp.sum((m & (fm == bit0) & (rb == 0)).astype(jnp.int32))
        considered = m & (fm != 0) & ~((fm == bit0) & (rb == 0))
        pop = jnp.zeros_like(fm)
        for b in range(n_flags):
            pop = pop + ((fm >> b) & 1)
        nf = pop + (rb > 0).astype(jnp.int32)
        crit = considered & (nf == 1)
        two = considered & (nf == 2)
        defer = m & (res["escaped"] | ~res["exact"])
        per_flag = jnp.stack([
            jnp.sum((considered & (((fm >> b) & 1) == 1)).astype(jnp.int32))
            for b in range(n_flags)
        ])
        head = jnp.stack([
            passes,
            bare_eof,
            jnp.sum(crit.astype(jnp.int32)),
            jnp.sum(two.astype(jnp.int32)),
            jnp.sum(defer.astype(jnp.int32)),
        ])
        (crit_idx,) = jnp.nonzero(crit, size=k_positions, fill_value=-1)
        (two_idx,) = jnp.nonzero(two, size=k_positions, fill_value=-1)
        crit_mask = jnp.where(crit_idx >= 0, fm[jnp.clip(crit_idx, 0)], 0)
        two_mask = jnp.where(two_idx >= 0, fm[jnp.clip(two_idx, 0)], 0)
        return (
            jnp.concatenate([head, per_flag]),
            crit_idx.astype(jnp.int32), crit_mask,
            two_idx.astype(jnp.int32), two_mask,
        )

    def full_step(windows, ns, at_eofs, los, owns, lengths, nc):
        stats, ci, cm, ti, tm = jax.vmap(
            lambda wd, n, e, lo, ow: one(wd, n, e, lo, ow, lengths, nc)
        )(windows, ns, at_eofs, los, owns)
        with jax.named_scope("reduce"):
            totals = jax.lax.psum(jnp.sum(stats, axis=0), axis)  # ← ICI
        return totals, ci, cm, ti, tm

    return jax.jit(
        jax.shard_map(
            full_step,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(), P()),
            out_specs=(P(), P(axis), P(axis), P(axis), P(axis)),
            check_vma=False,
        )
    )


def make_shard_map_serve_step(
    mesh: Mesh, reads_to_check: int = 10, axis: str = "data",
    funnel: bool = False,
):
    """Sharded serving step: PER-ROW (boundary count, owned escapes,
    stage-0 survivors, lanes run) with NO cross-device reduction —
    ``out_specs=P(axis)`` keeps each row's four on its shard so the host
    can scatter results back to the individual requests a batch coalesced
    (parallel/serve batching). A device's rows run one after another
    (``_rows_in_turn``), each its own blocks of lanes (``check_window``): a
    padding row runs none.

    Unlike the count step, ``lengths``/``num_contigs`` are per-row
    ``(B, Cmax)`` / ``(B,)`` inputs sharded with the batch: rows from
    DIFFERENT files (different contig dictionaries) share one dispatch,
    which is what lets a serving tick batch a fleet of BAMs together.
    The batch shape is fixed by the caller (pad to ``batch_rows``), so
    the jit traces exactly once per step config.
    """

    def one(window, n, at_eof, lo, own, lengths, num_contigs):
        res = check_window(
            window, lengths, num_contigs, n, at_eof,
            reads_to_check=reads_to_check, funnel=funnel,
        )
        w = window.shape[0] - PAD
        with jax.named_scope("reduce"):
            i = jnp.arange(w, dtype=jnp.int32)
            m = (i >= lo) & (i < own)
            return jnp.stack([
                jnp.sum((res["verdict"] & m).astype(jnp.int32)),
                jnp.sum((res["escaped"] & m).astype(jnp.int32)),
                res["survivors"], res["lanes"],
            ])

    def serve_step(windows, ns, at_eofs, los, owns, lengths, ncs):
        return _rows_in_turn(
            one, windows, ns, at_eofs, los, owns, lengths, ncs)

    return jax.jit(
        jax.shard_map(
            serve_step,
            mesh=mesh,
            in_specs=(P(axis),) * 7,
            out_specs=P(axis),
            check_vma=False,
        )
    )


def batch_windows(
    buf: np.ndarray,
    window: int,
    halo: int,
    batch: int,
    at_eof: bool = True,
    truth: np.ndarray | None = None,
):
    """Cut a flat buffer into a (B, W+PAD) batch of overlapping windows.

    Each window's trailing ``halo`` lets chains started in its owned span
    complete; ownership spans tile the buffer exactly. Returns (windows, ns,
    at_eofs, owned ranges, truth windows).
    """
    n_total = len(buf)
    step = max(window - halo, 1)
    starts = list(range(0, max(n_total, 1), step))
    # Trim starts that fall entirely beyond the buffer.
    starts = [s for s in starts if s == 0 or s < n_total]
    b = max(batch, len(starts))
    ws = np.zeros((b, window + PAD), dtype=np.uint8)
    ns = np.zeros(b, dtype=np.int32)
    eofs = np.zeros(b, dtype=bool)
    owned = []
    tr = np.zeros((b, window), dtype=bool)
    for i, s in enumerate(starts):
        e = min(s + window, n_total)
        ws[i, : e - s] = buf[s:e]
        ns[i] = e - s
        eofs[i] = at_eof and e == n_total
        own_end = e if e == n_total else min(s + step, n_total)
        owned.append((s, own_end))
        if truth is not None:
            tr[i, : e - s] = truth[s:e]
        if e == n_total:
            break
    return ws, ns, eofs, owned, tr
