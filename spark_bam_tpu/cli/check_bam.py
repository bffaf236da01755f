"""check-bam: evaluate two checkers at every uncompressed position.

Default compares spark-bam's eager checker against the seqdoop
(hadoop-bam-semantics) checker; ``-s``/``-u`` score eager/seqdoop against
the ``.records`` ground truth (reference cli/.../check/eager/CheckBam.scala).
``-s`` over a file the device engine would take (more than one kernel
window, a device backend) is scored by ``load.tpu_load.check_bam_tpu``:
verdicts vs the ``.records`` truth on every chip the process sees,
O(window) host memory, a compact confusion summary and the disagreeing
positions. ``--sharded`` asks for that same call whatever the file's size.
"""

from __future__ import annotations

from spark_bam_tpu.cli.app import (
    DEVICE_FROM_BYTES, CheckerContext, device_engine,
)
from spark_bam_tpu.cli.output import UsageError


def run(
    ctx: CheckerContext,
    spark_bam: bool = False,
    hadoop_bam: bool = False,
    sharded: bool = False,
) -> None:
    if sharded:
        # --sharded IS eager-vs-truth (the -s scoring) on the device, so
        # -s composes; -u (seqdoop oracle) and -i (byte ranges) have no
        # such implementation — reject rather than silently ignore.
        if hadoop_bam:
            raise UsageError(
                "--sharded scores the eager checker against the .records "
                "truth; the seqdoop oracle (-u) has no sharded path"
            )
        if ctx.ranges is not None:
            raise UsageError(
                "--sharded checks the whole file; -i/--intervals is not "
                "supported on the sharded path"
            )
    if sharded or (
        spark_bam and not hadoop_bam and ctx.ranges is None
        and ctx.has_records_index
    ):
        from spark_bam_tpu.bgzf.index_blocks import blocks_metadata

        # One scan: the decision, the report's sizes, the printed positions.
        metas = list(blocks_metadata(ctx.path))
        if sharded or _device_scores(ctx.config.backend, metas):
            _run_on_device(ctx, metas)
            return
    if spark_bam and not hadoop_bam:
        expected, actual = ctx.truth, ctx.eager_verdict
    elif hadoop_bam and not spark_bam:
        expected, actual = ctx.truth, ctx.seqdoop_verdict
    else:
        expected, actual = ctx.eager_verdict, ctx.seqdoop_verdict
    ctx.print_header_and_confusion(expected, actual)
    _print_cache_status(ctx)
    _print_funnel_status(ctx, device=False)


def _print_cache_status(ctx: CheckerContext) -> None:
    """check-bam doesn't consume the split cache, so this probes the
    sidecar: the operator sees whether the next load would be warm and,
    if not, why (docs/caching.md)."""
    from spark_bam_tpu.sbi.store import cache_status_line

    ctx.printer.echo(cache_status_line(ctx.path, ctx.config))


def _print_funnel_status(
    ctx: CheckerContext, device: bool = True, stats: dict | None = None
) -> None:
    from spark_bam_tpu.cli.app import funnel_status_line

    ctx.printer.echo(funnel_status_line(ctx.config, stats=stats, device=device))


def _device_scores(backend: str, metas: list) -> bool:
    """Whether ``-s`` is scored by ``check_bam_tpu``: a file of more than
    one kernel window whose eager engine is the device (``device_engine``,
    the context's own decision), read from the block table alone — the
    whole file's verdicts are never held on the host. A smaller file stays
    on the context's whole-view path, whatever engine scores it there."""
    size = sum(m.uncompressed_size for m in metas)
    return size >= DEVICE_FROM_BYTES and device_engine(backend, size)


def _run_on_device(ctx: CheckerContext, metas: list) -> None:
    from spark_bam_tpu.bgzf.flat import metas_block_table, pos_of_flat_tables
    from spark_bam_tpu.cli.app import print_report_header
    from spark_bam_tpu.core.pos import Pos
    from spark_bam_tpu.load.tpu_load import check_bam_tpu
    from spark_bam_tpu.utils.timer import heartbeat_progress

    # A 60 GB file is hours of steps: the operator hears of them.
    with heartbeat_progress(f"check-bam {ctx.path}") as progress:
        stats = check_bam_tpu(
            ctx.path, ctx.config, metas=metas, progress=progress)
    # Golden semantics: sum of data blocks, excluding the EOF sentinel
    # (the reference's compressedSizeAccumulator) — NOT the raw file size.
    compressed = sum(m.compressed_size for m in metas)
    num_reads = stats["true_positives"] + stats["false_negatives"]
    p = ctx.printer
    print_report_header(p, stats["positions"], compressed, num_reads)
    p.echo(f"checked across {stats['devices']} device(s)")
    _print_cache_status(ctx)
    # Mesh steps psum record-scale counters only, so no survivor totals
    # here — the line reports the mode the device step actually ran with.
    _print_funnel_status(ctx)
    fp = stats["false_positive_positions"]
    fn = stats["false_negative_positions"]
    if not len(fp) and not len(fn):
        p.echo("All calls matched!")
        return
    p.echo(f"{len(fp)} false positives, {len(fn)} false negatives")
    # Where they disagree, as upstream prints it (CheckerApp.scala:102-134),
    # under the printer's limit; no succeeding-read annotation: the bytes
    # are not on the host.
    tables = metas_block_table(metas)
    for what, flats in (("false positives", fp), ("false negatives", fn)):
        if len(flats):
            limit = p.limit or len(flats)
            p.print_limited(
                [Pos(*pos_of_flat_tables(*tables, int(f)))
                 for f in flats[:limit]],
                total=len(flats),
                header=f"{len(flats)} {what}:",
                truncated_header=lambda n, what=what, flats=flats: (
                    f"{n} of {len(flats)} {what}:"),
            )
