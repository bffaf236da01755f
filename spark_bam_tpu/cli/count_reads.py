"""count-reads: count via spark-bam and hadoop-bam loaders, compare
(reference cli/.../spark/compare/CountReads.scala:20-131)."""

from __future__ import annotations

from spark_bam_tpu import obs
from spark_bam_tpu.cli.output import Printer, UsageError
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.load.api import load_bam, load_reads
from spark_bam_tpu.load.hadoop import hadoop_bam_count
from spark_bam_tpu.utils.timer import Timer


def run(
    path,
    p: Printer,
    split_size: int,
    config: Config = Config(),
    spark_bam_first: bool = False,
    iterations: int = 1,
    reference=None,
    sharded: bool = False,
) -> None:
    def timed_loop(count_fn):
        """The no-competitor output shape shared by every standalone mode
        (sharded / CRAM): N timed counts, no hadoop-bam leg.
        The named Timer feeds the ``timer.count_reads.spark_bam``
        histogram when a registry is live; output format is unchanged."""
        for _ in range(max(iterations, 1)):
            with Timer("count_reads.spark_bam") as t:
                count = count_fn()
            p.echo(f"spark-bam read-count time: {int(t.ms)}")
            p.echo(f"Read count: {count}", "")

    is_cram = str(path).endswith(".cram")
    if sharded:
        # Mesh-scale streaming count across every device (no hadoop-bam
        # leg: this is the scale mode; the comparison mode is the default).
        if is_cram:
            raise UsageError(
                "--sharded supports BAM only: CRAM has no BGZF block "
                "structure to window (use the default count-reads path)"
            )
        from spark_bam_tpu.parallel.stream_mesh import count_reads_sharded
        from spark_bam_tpu.utils.timer import heartbeat_progress

        def sharded_once():
            # One pass, as ``load.tpu_load.count_reads_tpu``'s is: a trace
            # of its own in the ``--metrics-out`` file.
            with heartbeat_progress(
                f"count-reads --sharded {path}"
            ) as progress, obs.pass_span("load.count", path=str(path)):
                return count_reads_sharded(path, config, progress=progress)

        timed_loop(sharded_once)
        return
    if is_cram:
        # No hadoop-bam leg for CRAM (the reference delegates CRAM entirely;
        # there is no competitor count to diff against). ``reference`` (-F)
        # enables RR=true files with external references.
        timed_loop(
            lambda: load_reads(
                path, split_size, config, reference=reference
            ).count()
        )
        return

    def run_once():
        with Timer("count_reads.spark_bam") as t:
            spark_count = load_bam(path, split_size, config).count()
        spark_ms = int(t.ms)
        try:
            with Timer("count_reads.hadoop_bam") as t:
                hadoop_count = hadoop_bam_count(path, split_size, config)
            return spark_ms, spark_count, int(t.ms), hadoop_count, None
        except Exception as e:
            return spark_ms, spark_count, None, None, e

    results = [run_once() for _ in range(max(iterations, 1))]
    for spark_ms, spark_count, hadoop_ms, hadoop_count, error in results:
        p.echo(f"spark-bam read-count time: {spark_ms}")
        if error is None:
            p.echo(f"hadoop-bam read-count time: {hadoop_ms}", "")
            if spark_count == hadoop_count:
                p.echo(f"Read counts matched: {spark_count}", "")
            else:
                p.echo(
                    f"Read counts mismatched: {spark_count} via spark-bam,"
                    f" {hadoop_count} via hadoop-bam",
                    "",
                )
        else:
            p.echo(
                "",
                f"spark-bam found {spark_count} reads, hadoop-bam threw exception:",
                f"{type(error).__module__}.{type(error).__name__}: {error}",
            )
