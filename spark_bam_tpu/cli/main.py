"""spark-bam-tpu CLI: the reference's 10 subcommands
(cli/.../bam/Main.scala:21-41), same names and comparable output formats.

    spark-bam-tpu check-bam [-s|-u] [-m SIZE] [-l LIMIT] [-o OUT] PATH
    spark-bam-tpu check-blocks ...
    spark-bam-tpu full-check ...
    spark-bam-tpu compute-splits [-s|-u] [-m SIZE] PATH
    spark-bam-tpu compare-splits [-m SIZE] BAMS-FILE
    spark-bam-tpu count-reads [-m SIZE] [-n N] [-s] PATH
    spark-bam-tpu time-load [-m SIZE] PATH
    spark-bam-tpu export [-i LOCI] [--format F] [--columns C] -o OUT PATH
        (beyond the 10: columnar analytics export, docs/analytics.md)
    spark-bam-tpu index [-m SIZE] [--record-starts] PATH   (beyond the 10:
        ahead-of-time .sbi split-index cache builder, docs/caching.md)
    spark-bam-tpu index-blocks PATH
    spark-bam-tpu index-records PATH
    spark-bam-tpu htsjdk-rewrite [--durable] [--disk-chaos SEED:SPEC] IN OUT
    spark-bam-tpu scrub [--source BAM] [--quarantine] PATHS...
        (beyond the 10: end-to-end integrity scrubber, docs/robustness.md)
"""

from __future__ import annotations

import argparse
import sys

from spark_bam_tpu.cli.output import UsageError
from spark_bam_tpu.core.config import Config, parse_bytes


def _positive_int(s: str) -> int:
    v = int(s)
    if v <= 0:
        raise argparse.ArgumentTypeError(f"must be a positive integer: {s}")
    return v


def _add_metrics(sub):
    sub.add_argument(
        "--metrics-out", default=None, metavar="PATH",
        help="enable observability for this run and write the JSONL "
             "metrics trace here on exit — a directory or a {pid} "
             "placeholder gives each fabric worker its own file "
             "(SPARK_BAM_METRICS_OUT env var works too; render with the "
             "metrics-report subcommand)",
    )
    sub.add_argument(
        "--profile", default=None, metavar="DIR",
        help="capture ONE steady inflate/count window (the first whose "
             "shape has run before, so it does not compile) with "
             "jax.profiler.trace into this directory (TensorBoard format; "
             "SPARK_BAM_PROFILE env var works too — fabric workers "
             "inherit it)",
    )


def _add_faults(sub):
    sub.add_argument(
        "--faults", default=None, metavar="SPEC",
        help="fault-tolerance policy for partition execution, e.g. "
             "'retries=3,backoff=0.05,deadline=60,hedge=2,mode=tolerant' "
             "(SPARK_BAM_FAULTS env var works too; docs/robustness.md)",
    )
    sub.add_argument(
        "--chaos", default=None, metavar="SEED:SPEC",
        help="deterministic fault injection on every opened channel, e.g. "
             "'7:io=0.1,latency=0.05x10,short=0.02,corrupt=1e-6' — same "
             "seed replays the same faults (docs/robustness.md)",
    )


def _add_disk_chaos(sub):
    sub.add_argument(
        "--disk-chaos", default=None, metavar="SEED:SPEC",
        help="deterministic filesystem-fault injection on every guarded "
             "write, e.g. '7:enospc=0.02+eio=0.01+short=0.01+torn=0.01+"
             "rename=0.05' — same seed replays the same faults; fabric "
             "workers inherit it via SPARK_BAM_DISK_CHAOS "
             "(docs/robustness.md)",
    )


def _add_durable(sub):
    sub.add_argument(
        "--durable", action="store_true",
        help="run through the journaled job runner: checkpoints to a "
             "write-ahead log, a re-run after a crash resumes from the "
             "last durable checkpoint and produces a byte-identical "
             "artifact (SPARK_BAM_JOBS tunes the job dir/cadence; "
             "docs/robustness.md)",
    )
    sub.add_argument(
        "--checkpoint", type=_positive_int, default=None, metavar="N",
        help="with --durable: checkpoint cadence (records for rewrite, "
             "frames for export; default from SPARK_BAM_JOBS)",
    )
    _add_jobs(sub)


def _add_jobs(sub):
    sub.add_argument(
        "--jobs", default=None, metavar="SPEC",
        help="durable-job plane knobs, e.g. 'dir=/var/jobs,checkpoint="
             "5000,frames=8,mem=0.92,max=2' (SPARK_BAM_JOBS env var "
             "works too; docs/robustness.md)",
    )


def _add_cache(sub):
    sub.add_argument(
        "--cache", default=None, metavar="MODE",
        help="split-index (.sbi) cache mode: off|read|write|readwrite, "
             "optional ',strict' suffix raises on stale sidecars "
             "(SPARK_BAM_CACHE env var works too; docs/caching.md)",
    )


def _add_limits(sub):
    sub.add_argument(
        "--limits", default=None, metavar="SPEC",
        help="decode resource limits for untrusted input, e.g. "
             "'record=32MB,refs=1000,cigar=65536,alloc=1GB' "
             "(SPARK_BAM_LIMITS env var works too; docs/robustness.md)",
    )


def _add_remote(sub):
    sub.add_argument(
        "--remote", default=None, metavar="SPEC",
        help="remote data-plane tuning, e.g. "
             "'mode=plan,depth=8,gap=128KB,request=512KB,hedge=3,pool=64' "
             "(mode=legacy restores cursor read-ahead; depth=0 adapts; "
             "SPARK_BAM_REMOTE env var works too; docs/remote.md)",
    )


def _add_funnel(sub):
    sub.add_argument(
        "--funnel", default=None, choices=("on", "off", "auto"),
        help="two-stage checker candidate funnel: cheap prefilter over "
             "every position, deep checks on survivors only. auto "
             "(default) funnels verdict paths and keeps the exact "
             "single-pass kernel for full flag-mask output "
             "(SPARK_BAM_FUNNEL env var works too; docs/design.md)",
    )


def _add_columnar(sub):
    sub.add_argument(
        "--columnar", default=None, metavar="SPEC",
        help="columnar-plane knobs, e.g. 'rows=8192,codec=zlib,level=6,"
             "columns=flag+pos+name' (SPARK_BAM_COLUMNAR env var works "
             "too; docs/analytics.md)",
    )


def _add_slo(sub):
    sub.add_argument(
        "--slo", default=None, metavar="SPEC",
        help="SLO objectives + burn-rate alerting, e.g. "
             "'serve.latency:p99<1500ms@5m;serve.errors:ratio<0.1%%@1h;"
             "sample=0.1' (SPARK_BAM_SLO env var works too; "
             "docs/observability.md)",
    )
    sub.add_argument(
        "--dashboard", default=None, metavar="ADDR",
        help="serve the zero-dependency live dashboard on host:port — "
             "HTML sparklines at /, Prometheus text at /metrics, SLO "
             "burn rates + accounting at /slo (docs/observability.md)",
    )


def _add_deflate(sub):
    sub.add_argument(
        "--deflate", default=None, metavar="SPEC",
        help="write-path codec knobs, e.g. 'mode=fixed,lanes=16,"
             "device=auto' — stored/fixed members batch-compressed on "
             "device, host zlib when off (SPARK_BAM_DEFLATE env var "
             "works too; docs/design.md)",
    )


def _add_common(sub, split_default=None):
    _add_metrics(sub)
    _add_faults(sub)
    _add_cache(sub)
    _add_limits(sub)
    _add_remote(sub)
    _add_funnel(sub)
    sub.add_argument("-m", "--max-split-size", default=split_default,
                     help="split size (byte shorthand like 2MB ok)")
    sub.add_argument("-l", "--print-limit", type=int, default=10)
    sub.add_argument("-o", "--out", default=None, help="write output to file")
    sub.add_argument("-w", "--warn", action="store_true", help="root log level WARN")
    sub.add_argument(
        "-i", "--intervals", default=None,
        help="comma-separated compressed byte-ranges (start-end|start+len|point,"
             " byte shorthand ok); only blocks starting inside are checked",
    )
    # Reference FindBlockArgs (-z) / FindReadArgs knobs.
    sub.add_argument("-z", "--bgzf-blocks-to-check", type=int, default=None)
    sub.add_argument("--reads-to-check", type=int, default=None)
    sub.add_argument("--max-read-size", type=int, default=None)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="spark-bam-tpu", description="TPU-native parallel BAM toolkit"
    )
    sp = ap.add_subparsers(dest="command", required=True)

    for name in ("check-bam", "check-blocks"):
        sub = sp.add_parser(name)
        _add_common(sub)
        sub.add_argument("-s", "--spark-bam", action="store_true",
                         help="score the eager checker against the .records index")
        sub.add_argument("-u", "--upstream", action="store_true",
                         help="score the seqdoop checker against the .records index")
        if name == "check-bam":
            sub.add_argument(
                "--sharded", action="store_true",
                help="mesh-scale streaming check vs .records truth across"
                     " all devices (compact summary output)",
            )
        sub.add_argument("path")

    sub = sp.add_parser("full-check")
    _add_common(sub)
    sub.add_argument(
        "--streaming", action="store_true",
        help="WGS-scale O(window)-memory scan; mask-derived sections match"
             " the default report, position lists print unannotated",
    )
    sub.add_argument(
        "--sharded", action="store_true",
        help="with --streaming: run the scan across every device on the "
             "mesh (flag totals psum'd over ICI)",
    )
    sub.add_argument("path")

    sub = sp.add_parser("compute-splits")
    _add_common(sub)
    sub.add_argument("-s", "--spark-bam", action="store_true")
    sub.add_argument("-u", "--upstream", action="store_true")
    sub.add_argument(
        "--plan-hosts", type=_positive_int, default=0, metavar="N",
        help="also print the N-host sharded-run IO plan (per-host "
             "compressed byte ranges — the preferredLocations analog)",
    )
    sub.add_argument(
        "--devices-per-host", type=_positive_int, default=8, metavar="D",
        help="devices per host for --plan-hosts (default 8)",
    )
    sub.add_argument("path")

    sub = sp.add_parser("compare-splits")
    _add_common(sub)
    sub.add_argument("bams", help="file containing one BAM path per line")

    sub = sp.add_parser("count-reads")
    _add_common(sub)
    sub.add_argument("-s", "--spark-bam-first", action="store_true")
    sub.add_argument("-n", "--num-iterations", type=int, default=1)
    sub.add_argument("-F", "--reference", default=None,
                     help="FASTA for reference-based (RR=true) CRAM decode")
    sub.add_argument(
        "--sharded", action="store_true",
        help="mesh-scale streaming count across all devices (no hadoop leg)",
    )
    sub.add_argument("path")

    sub = sp.add_parser("time-load")
    _add_common(sub)
    sub.add_argument("path")

    # Columnar analytics export: record batches to a native container /
    # Arrow IPC / Parquet file (docs/analytics.md).
    sub = sp.add_parser("export")
    _add_metrics(sub)
    _add_faults(sub)
    _add_disk_chaos(sub)
    _add_durable(sub)
    _add_cache(sub)
    _add_limits(sub)
    _add_remote(sub)
    _add_columnar(sub)
    sub.add_argument("-m", "--max-split-size", default=None,
                     help="split size (byte shorthand like 2MB ok)")
    sub.add_argument(
        "-i", "--intervals", default=None, metavar="LOCI",
        help="genomic loci to restrict to, e.g. 'chr1:5k-10k,chr2' "
             "(decimal k/m suffixes; whole contig when no range)",
    )
    sub.add_argument(
        "--format", default="native", choices=("native", "arrow", "parquet"),
        help="output format (arrow/parquet need the pyarrow extra; "
             "default native)",
    )
    sub.add_argument(
        "--columns", default=None, metavar="COLS",
        help="comma-separated column projection (default: all columns)",
    )
    sub.add_argument("-F", "--reference", default=None,
                     help="FASTA for reference-based (RR=true) CRAM decode")
    sub.add_argument("-w", "--warn", action="store_true",
                     help="root log level WARN")
    sub.add_argument("-o", "--out", dest="export_out", required=True,
                     help="output file path")
    sub.add_argument("path")

    # On-device aggregation: reduce a query to kilobytes of statistics
    # without materializing records (docs/analytics.md "Aggregation").
    sub = sp.add_parser("aggregate")
    _add_metrics(sub)
    _add_faults(sub)
    _add_cache(sub)
    _add_limits(sub)
    _add_remote(sub)
    sub.add_argument("-m", "--max-split-size", default=None,
                     help="split size (byte shorthand like 2MB ok)")
    sub.add_argument(
        "-a", "--agg", default=None, metavar="SPEC",
        help="';'-separated metric[:k=v,...] spec — count, flagstat, "
             "mapq, tlen[:max=N], coverage[:bin=N,bins=N,cap=N] "
             "(default: every metric at defaults, or SPARK_BAM_AGG)",
    )
    sub.add_argument(
        "-i", "--intervals", default=None, metavar="LOCI",
        help="genomic loci to restrict to, e.g. 'chr1:5k-10k,chr2' "
             "(decimal k/m suffixes; whole contig when no range)",
    )
    sub.add_argument("--flags-required", type=int, default=0,
                     help="only records with ALL these SAM flag bits")
    sub.add_argument("--flags-forbidden", type=int, default=0,
                     help="only records with NONE of these SAM flag bits")
    sub.add_argument(
        "-t", "--tag", action="append", default=None, metavar="TG",
        help="only records carrying this two-char tag (repeatable; "
             "all must be present)",
    )
    sub.add_argument("--format", default="tsv", choices=("tsv", "json"),
                     help="report format (default tsv)")
    sub.add_argument("-F", "--reference", default=None,
                     help="FASTA for reference-based (RR=true) CRAM decode")
    sub.add_argument("-w", "--warn", action="store_true",
                     help="root log level WARN")
    sub.add_argument("-o", "--out", default=None,
                     help="write the report here instead of stdout")
    sub.add_argument("path")

    sub = sp.add_parser("index-blocks")
    _add_metrics(sub)
    sub.add_argument("-o", "--out", default=None)
    sub.add_argument("path")

    # Ahead-of-time .sbi builder: warm the split-index cache so the first
    # load is already served from the sidecar (docs/caching.md).
    sub = sp.add_parser("index")
    _add_metrics(sub)
    _add_faults(sub)
    sub.add_argument("-m", "--max-split-size", default=None,
                     help="split size to plan for (byte shorthand like 2MB ok)")
    sub.add_argument("-o", "--out", default=None,
                     help="write the .sbi here instead of the resolved "
                          "cache location")
    sub.add_argument("-w", "--warn", action="store_true",
                     help="root log level WARN")
    sub.add_argument(
        "--record-starts", action="store_true",
        help="also index every record-start virtual position (runs the "
             "vectorized checker once over the file)",
    )
    sub.add_argument("-z", "--bgzf-blocks-to-check", type=int, default=None)
    sub.add_argument("--reads-to-check", type=int, default=None)
    sub.add_argument("--max-read-size", type=int, default=None)
    sub.add_argument("path")

    sub = sp.add_parser("index-records")
    _add_metrics(sub)
    sub.add_argument("-o", "--out", default=None)
    sub.add_argument("-t", "--throw-on-truncation", action="store_true")
    sub.add_argument("path")

    # Beyond the reference's 10 commands: the samtools-index role for the
    # built-in .bai writer (the reference consumes .bai but can't produce
    # one; ours can, so indexed interval loads work on any sorted BAM).
    sub = sp.add_parser("index-bam")
    _add_metrics(sub)
    sub.add_argument("-o", "--out", default=None)
    sub.add_argument("path")

    sub = sp.add_parser("htsjdk-rewrite", aliases=["rewrite"])
    _add_metrics(sub)
    _add_cache(sub)
    _add_deflate(sub)
    _add_disk_chaos(sub)
    _add_durable(sub)
    sub.add_argument("-o", "--out", default=None, help="write output to file")
    sub.add_argument("-b", "--block-payload", default="65280")
    sub.add_argument("--level", type=int, default=6,
                     help="zlib level for the host codec path (default 6)")
    sub.add_argument("-i", "--index", action="store_true",
                     help="also write .blocks/.records/.sbi sidecars for "
                          "the output, built from the packing metadata "
                          "(no re-read)")
    sub.add_argument("in_path")
    sub.add_argument("out_path")

    # Structure-aware mutation fuzzing of the decode boundary
    # (tools/fuzz_decode.py; docs/robustness.md "Malformed inputs").
    sub = sp.add_parser("fuzz-decode")
    _add_limits(sub)
    sub.add_argument("--seed", type=int, default=0,
                     help="base seed; the same seed replays the same mutants")
    sub.add_argument("--mutants", type=int, default=200,
                     help="mutants per corpus format (default 200)")
    sub.add_argument(
        "--formats", default="bam,bgzf,cram,sbi",
        help="comma-separated corpus formats to fuzz (default all)",
    )
    sub.add_argument("-o", "--out", default=None,
                     help="write the JSON summary here instead of stdout")

    # End-to-end integrity scrubber over rewritten artifacts: BGZF frame
    # CRCs, sidecar cross-checks, native-container validation, spot
    # record-parity against the source (docs/robustness.md).
    sub = sp.add_parser("scrub")
    _add_metrics(sub)
    _add_limits(sub)
    sub.add_argument(
        "--source", default=None, metavar="BAM",
        help="original BAM the artifacts were rewritten from — enables "
             "spot record-parity (every --stride'th record compared "
             "byte-for-byte)",
    )
    sub.add_argument(
        "--quarantine", action="store_true",
        help="rename artifacts with findings to <path>.quarantined so "
             "downstream pipelines cannot consume them",
    )
    sub.add_argument(
        "--stride", type=_positive_int, default=16, metavar="N",
        help="record-parity sampling stride (default 16; 1 = compare "
             "every record)",
    )
    sub.add_argument("-o", "--out", default=None,
                     help="write the JSON report here instead of stdout")
    sub.add_argument("-w", "--warn", action="store_true",
                     help="root log level WARN")
    sub.add_argument(
        "paths", nargs="+",
        help="artifacts to scrub (BAM pulls its .blocks/.records/.sbi "
             "sidecars in automatically; native containers stand alone)",
    )

    # Long-running split/record daemon over the device mesh: warm steps,
    # warm flat views, warm .sbi tier; newline-JSON protocol
    # (docs/serving.md).
    sub = sp.add_parser("serve")
    _add_metrics(sub)
    _add_faults(sub)
    _add_disk_chaos(sub)
    _add_cache(sub)
    _add_limits(sub)
    _add_remote(sub)
    _add_funnel(sub)
    _add_columnar(sub)
    _add_deflate(sub)
    _add_slo(sub)
    _add_jobs(sub)
    sub.add_argument(
        "--serve", default=None, metavar="SPEC",
        help="serving knobs, e.g. 'batch=16,tick=2,plan_queue=64,"
             "scan_queue=128,workers=2,window=1MB,halo=64KB,cache=256MB' "
             "(SPARK_BAM_SERVE env var works too; docs/serving.md)",
    )
    sub.add_argument(
        "--listen", default="tcp:127.0.0.1:8765", metavar="ADDR",
        help="unix:<path> or tcp:<host>:<port> (default tcp:127.0.0.1:8765)",
    )
    sub.add_argument("--reads-to-check", type=int, default=None)
    sub.add_argument("-w", "--warn", action="store_true",
                     help="root log level WARN")

    # Serve fabric control plane: launch (or attach to) N serve workers
    # and front them with the affinity router + health prober + SLO
    # autoscaler (docs/fabric.md). Same wire protocol as `serve`.
    sub = sp.add_parser("fabric")
    _add_metrics(sub)
    _add_faults(sub)
    _add_disk_chaos(sub)
    _add_slo(sub)
    sub.add_argument(
        "--fabric", default=None, metavar="SPEC",
        help="fabric knobs, e.g. 'workers=3,slo=200,probe=500,spill=8,"
             "batch_ceil=32' (SPARK_BAM_FABRIC env var works too; "
             "docs/fabric.md). Resilience: budget/budget_rate, flap_k/"
             "flap_window/holddown, brownout[_frac], stream=1 for "
             "resumable streaming relay. Seeded fleet chaos: "
             "'chaos=SEED:drop=0.05+trunc=0.02+delay=0.1x20' "
             "(docs/robustness.md)",
    )
    sub.add_argument(
        "--serve", default=None, metavar="SPEC",
        help="per-worker serving knobs, forwarded to every launched "
             "worker (docs/serving.md)",
    )
    sub.add_argument(
        "--listen", default="tcp:127.0.0.1:8765", metavar="ADDR",
        help="router address: unix:<path> or tcp:<host>:<port> "
             "(default tcp:127.0.0.1:8765)",
    )
    sub.add_argument(
        "--attach", action="append", default=None, metavar="ADDR",
        help="attach to an already-running worker instead of launching "
             "(repeatable — point one at every host's `multihost --serve` "
             "address for the multi-host fabric)",
    )
    sub.add_argument(
        "--worker-devices", type=int, default=0, metavar="N",
        help="virtual CPU devices per LAUNCHED worker (dev boxes; "
             "0 = each worker's real local devices)",
    )
    sub.add_argument("-w", "--warn", action="store_true",
                     help="root log level WARN")

    # Render --metrics-out JSONL trace(s) as the reference stats format.
    # Several files (e.g. a fabric run's per-worker trace directory) are
    # merged by trace_id into one cross-process report.
    sub = sp.add_parser("metrics-report")
    sub.add_argument("-o", "--out", default=None, help="write output to file")
    sub.add_argument("-l", "--print-limit", type=int, default=10)
    sub.add_argument(
        "trace", nargs="+",
        help="JSONL trace(s) --metrics-out runs wrote; pass every "
             "per-process file of one fleet run to merge spans by "
             "trace_id",
    )

    # One-shot fleet telemetry view: per-worker health, queue depth,
    # per-op p50/p99, host/H2D/device ms split (docs/observability.md).
    sub = sp.add_parser("top")
    sub.add_argument("-o", "--out", default=None, help="write output to file")
    sub.add_argument(
        "--prometheus", action="store_true",
        help="print the (fleet-merged) Prometheus exposition text "
             "instead of the human view",
    )
    sub.add_argument(
        "--watch", action="store_true",
        help="live mode: clear and re-render every --interval seconds "
             "(Ctrl-C to stop)",
    )
    sub.add_argument(
        "--interval", type=float, default=2.0, metavar="S",
        help="--watch refresh cadence in seconds (default 2)",
    )
    sub.add_argument(
        "address",
        help="serve worker or fabric router address "
             "(tcp:host:port or unix:path)",
    )

    # Project-native static analysis: AST rules guarding the jit,
    # asyncio, and untrusted-byte seams (docs/static-analysis.md).
    sub = sp.add_parser("lint")
    sub.add_argument("-o", "--out", default=None, help="write output to file")
    sub.add_argument(
        "paths", nargs="*",
        help="files or directories to lint (default: the installed "
             "spark_bam_tpu package)",
    )
    sub.add_argument(
        "--rules", default=None,
        help="comma-separated rule ids to run (default: all registered)",
    )
    sub.add_argument(
        "--baseline", default=None,
        help="baseline suppression file (default: lint-baseline.json "
             "next to the package; missing file = empty baseline)",
    )
    sub.add_argument(
        "--no-baseline", action="store_true",
        help="ignore the baseline file — report every finding",
    )
    sub.add_argument(
        "--json", dest="json_out", default=None, metavar="PATH",
        help="also write the full findings report as JSON (the CI "
             "artifact format)",
    )
    sub.add_argument(
        "--write-baseline", default=None, metavar="REASON",
        help="write the current live findings to the baseline file with "
             "REASON as the justification stub, then exit 0 (edit "
             "per-entry justifications before committing)",
    )
    sub.add_argument(
        "-v", "--verbose", action="store_true",
        help="also list suppressed findings with their justifications",
    )

    return ap


def _service_dashboard(service, listen: str):
    """Start a :class:`~spark_bam_tpu.obs.dashboard.DashboardServer`
    reading one worker's local registry/engine/accountant."""
    from spark_bam_tpu import obs
    from spark_bam_tpu.obs import flight
    from spark_bam_tpu.obs.dashboard import DashboardServer

    def provider():
        reg = obs.registry()
        return {
            "snapshot": reg.snapshot() if reg is not None else {},
            "series": service.rings.snapshot() if service.rings else None,
            "slo": (service.slo_engine.status()
                    if service.slo_engine is not None
                    else {"enabled": False, "objectives": []}),
            "accounting": service.accountant.snapshot(),
            "flight": flight.recorder().events(),
        }

    return DashboardServer(listen, provider).start()


def _router_dashboard(router, listen: str):
    """Start a dashboard over a fabric router: each request crosses into
    the router's event loop (``run_coroutine_threadsafe``) and reads the
    same ``telemetry``/``alerts`` fan-outs clients get. Before the loop
    runs (no request yet), render the router-local flight ring only."""
    import asyncio

    from spark_bam_tpu.obs import flight
    from spark_bam_tpu.obs.dashboard import DashboardServer

    def provider():
        loop = router._loop
        if loop is None or not loop.is_running():
            return {"snapshot": {}, "flight": flight.recorder().events()}
        tel = asyncio.run_coroutine_threadsafe(
            router.submit({"op": "telemetry"}), loop
        ).result(timeout=10)
        al = asyncio.run_coroutine_threadsafe(
            router.submit({"op": "alerts"}), loop
        ).result(timeout=10)
        # Fleet SLO view: per objective, the worst worker's status.
        objs: dict = {}
        for r in (al.get("workers") or {}).values():
            for st in (r.get("slo") or {}).get("objectives", ()):
                cur = objs.get(st.get("objective"))
                if cur is None or (st.get("burn_fast") or 0) > (
                        cur.get("burn_fast") or 0):
                    objs[st.get("objective")] = st
        return {
            "snapshot": tel.get("fleet") or {},
            "series": tel.get("series"),
            "slo": {
                "enabled": bool(objs),
                "objectives": sorted(
                    objs.values(), key=lambda s: s.get("objective") or ""
                ),
                "firing": al.get("firing") or [],
                "ledger": al.get("ledger") or [],
                "moves": al.get("moves") or [],
            },
            "accounting": tel.get("accounting"),
            "flight": tel.get("flight"),
        }

    return DashboardServer(listen, provider).start()


def _compiles_device_programs(args, config) -> bool:
    """Whether this invocation will build jax programs (so the compile
    cache is placed first). The host-only commands never import jax, and
    this must not make them: it costs seconds of start-up."""
    return (
        args.command in ("serve", "aggregate", "export")
        or any(getattr(args, flag, False)
               for flag in ("sharded", "streaming"))
        or config.backend == "tpu"
    )


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    import logging
    import os

    # --warn: root log level to WARN (reference args/LogArgs.scala:30-33).
    logging.basicConfig(
        level=logging.WARNING if getattr(args, "warn", False) else logging.INFO
    )
    from spark_bam_tpu import obs
    from spark_bam_tpu.cli.output import Printer

    out = open(args.out, "w") if getattr(args, "out", None) else None
    p = Printer(out=out, limit=getattr(args, "print_limit", 10))
    config = Config.from_env()
    split = getattr(args, "max_split_size", None)
    if split is not None:
        config = config.replace(split_size=parse_bytes(split))
    for knob in ("bgzf_blocks_to_check", "reads_to_check", "max_read_size"):
        value = getattr(args, knob, None)
        if value is not None:
            config = config.replace(**{knob: value})

    from spark_bam_tpu.core.faults import (
        FaultPolicy, install_chaos, install_disk_chaos, uninstall_chaos,
        uninstall_disk_chaos,
    )
    from spark_bam_tpu.parallel.executor import last_report, reset_last_report

    chaos_state = None
    disk_state = None
    try:
        if getattr(args, "faults", None):
            FaultPolicy.parse(args.faults)  # fail before any work starts
            config = config.replace(faults=args.faults)
        if getattr(args, "cache", None) is not None:
            from spark_bam_tpu.sbi.store import CacheMode

            CacheMode.parse(args.cache)  # fail before any work starts
            config = config.replace(cache=args.cache)
        if getattr(args, "limits", None) is not None:
            from spark_bam_tpu.core.guard import DecodeLimits, set_limits

            # Fail before any work starts, then install process-wide so
            # every parser this invocation touches decodes under them.
            set_limits(DecodeLimits.parse(args.limits))
            config = config.replace(limits=args.limits)
        if getattr(args, "remote", None) is not None:
            from spark_bam_tpu.core.remote_plan import (
                RemoteConfig, set_remote_config,
            )

            # Fail before any work starts, then install process-wide so
            # every channel this invocation opens rides the tuned plane.
            set_remote_config(RemoteConfig.parse(args.remote))
            config = config.replace(remote=args.remote)
        if getattr(args, "funnel", None) is not None:
            config = config.replace(funnel=args.funnel)
        config.funnel_enabled()  # fail early on a bad SPARK_BAM_FUNNEL
        if getattr(args, "columnar", None) is not None:
            from spark_bam_tpu.columnar import ColumnarConfig

            ColumnarConfig.parse(args.columnar)  # fail before any work starts
            config = config.replace(columnar=args.columnar)
        if getattr(args, "deflate", None) is not None:
            from spark_bam_tpu.compress.config import DeflateConfig

            DeflateConfig.parse(args.deflate)  # fail before any work starts
            config = config.replace(deflate=args.deflate)
        if getattr(args, "serve", None) is not None:
            from spark_bam_tpu.serve import ServeConfig

            ServeConfig.parse(args.serve)  # fail before any work starts
            config = config.replace(serve=args.serve)
        if getattr(args, "fabric", None) is not None:
            from spark_bam_tpu.fabric import FabricConfig

            FabricConfig.parse(args.fabric)  # fail before any work starts
            config = config.replace(fabric=args.fabric)
        if getattr(args, "slo", None) is not None:
            from spark_bam_tpu.obs.slo import SloConfig

            SloConfig.parse(args.slo)  # fail before any work starts
            config = config.replace(slo=args.slo)
        if getattr(args, "jobs", None) is not None:
            from spark_bam_tpu.jobs.manager import JobsConfig

            JobsConfig.parse(args.jobs)  # fail before any work starts
            config = config.replace(jobs=args.jobs)
        if getattr(args, "dashboard", None):
            from spark_bam_tpu.obs.dashboard import parse_listen

            parse_listen(args.dashboard)  # fail before any work starts
        if getattr(args, "listen", None) is not None:
            from spark_bam_tpu.serve import ServeAddress

            ServeAddress(args.listen)  # fail before any work starts
        if getattr(args, "chaos", None):
            chaos_state = install_chaos(args.chaos)
        if getattr(args, "disk_chaos", None):
            # In-process seam for rewrite/export/serve; the fabric branch
            # additionally exports SPARK_BAM_DISK_CHAOS so every launched
            # worker installs the same seeded schedule.
            disk_state = install_disk_chaos(args.disk_chaos)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        if chaos_state is not None:
            uninstall_chaos()
        return 2
    reset_last_report()
    # Cache-status events are per-run (module-global): clear leftovers so
    # the status line describes THIS invocation only.
    from spark_bam_tpu.sbi.store import reset_cache_events

    reset_cache_events()
    if _compiles_device_programs(args, config):
        from spark_bam_tpu.core.platform import enable_compile_cache

        enable_compile_cache()

    # --metrics-out (or the env var) turns the process-wide registry on
    # for this run; everything below the root ``cli.<command>`` span
    # records into it and the trace is written on the way out.
    metrics_out = (
        getattr(args, "metrics_out", None)
        or os.environ.get("SPARK_BAM_METRICS_OUT")
    )
    if metrics_out:
        obs.configure()
        metrics_out = obs.resolve_metrics_path(metrics_out)
    elif config.slo or getattr(args, "dashboard", None):
        # The SLO engine evaluates against the live registry's ring and
        # the dashboard scrapes it — both need metrics on even without a
        # trace file to write.
        obs.configure()
    # --profile rides the env var so the inflate pipeline (and any
    # fabric worker subprocess inheriting the environment) sees it.
    profile_set = getattr(args, "profile", None)
    if profile_set:
        os.environ["SPARK_BAM_PROFILE"] = profile_set
    cmd = args.command
    # lint: allow[obs-contract] cmd bounded by the subparser set; every
    # cli.<subcommand> span is enumerated in obs/names.py
    root_span = obs.span(f"cli.{cmd}")
    root_span.__enter__()
    try:
        if cmd in ("check-bam", "check-blocks", "full-check", "compute-splits",
                   "time-load"):
            from spark_bam_tpu.cli.app import CheckerContext
            from spark_bam_tpu.core.ranges import parse_ranges

            ctx = CheckerContext(
                args.path, config, p,
                ranges=parse_ranges(getattr(args, "intervals", None)),
            )
            if cmd == "check-bam":
                from spark_bam_tpu.cli import check_bam

                check_bam.run(
                    ctx, args.spark_bam, args.upstream, sharded=args.sharded
                )
            elif cmd == "check-blocks":
                from spark_bam_tpu.cli import check_blocks

                check_blocks.run(ctx, args.spark_bam, args.upstream)
            elif cmd == "full-check":
                from spark_bam_tpu.cli import full_check

                if args.sharded and not args.streaming:
                    raise UsageError(
                        "full-check --sharded requires --streaming (the "
                        "in-memory report has no mesh mode)"
                    )
                if args.streaming:
                    full_check.run_streaming(ctx, sharded=args.sharded)
                else:
                    full_check.run(ctx)
            elif cmd == "compute-splits":
                from spark_bam_tpu.cli import compute_splits

                compute_splits.run(
                    ctx,
                    config.split_size_or(Config.LOAD_SPLIT_SIZE_DEFAULT),
                    args.spark_bam,
                    args.upstream,
                )
                if args.plan_hosts:
                    compute_splits.print_host_plan(
                        ctx, args.plan_hosts, args.devices_per_host
                    )
            elif cmd == "time-load":
                from spark_bam_tpu.cli import time_load

                time_load.run(ctx, config.split_size_or(Config.LOAD_SPLIT_SIZE_DEFAULT))
        elif cmd == "compare-splits":
            from spark_bam_tpu.cli import compare_splits

            compare_splits.run(
                args.bams, p, config.split_size_or(Config.LOAD_SPLIT_SIZE_DEFAULT),
                config,
            )
        elif cmd == "count-reads":
            from spark_bam_tpu.cli import count_reads

            count_reads.run(
                args.path, p, config.split_size_or(Config.LOAD_SPLIT_SIZE_DEFAULT),
                config, args.spark_bam_first, args.num_iterations,
                reference=args.reference, sharded=args.sharded,
            )
        elif cmd == "export":
            from spark_bam_tpu.cli import export as export_cmd
            from spark_bam_tpu.load.intervals import BadLociError, LociSet

            loci = getattr(args, "intervals", None)
            if loci:
                try:
                    LociSet.parse(loci)  # fail before any work starts
                except BadLociError as e:
                    raise UsageError(str(e)) from e
            if args.columns:
                from spark_bam_tpu.columnar import normalize_columns

                try:
                    normalize_columns(args.columns)
                except ValueError as e:
                    raise UsageError(str(e)) from e
            if args.durable:
                # Journaled export: checkpoints at container-frame
                # boundaries, crash-resumable (docs/robustness.md). The
                # runner streams whole-file native frames, so the knobs
                # that change the frame list are out of scope here.
                if args.format != "native":
                    raise UsageError(
                        "--durable export supports --format native only"
                    )
                if loci or args.reference:
                    raise UsageError(
                        "--durable export does not take -i/--reference"
                    )
                import json as _json

                from spark_bam_tpu.jobs.manager import job_id_of
                from spark_bam_tpu.jobs.runner import run_export_job

                spec = {"op": "export", "path": args.path,
                        "out": args.export_out, "columns": args.columns}
                spec = {k: v for k, v in spec.items() if v is not None}
                jcfg = config.jobs_config
                res = run_export_job(
                    spec, os.path.join(jcfg.root(), job_id_of(spec)),
                    config=config,
                    checkpoint=args.checkpoint or jcfg.frames,
                )
                p.echo(_json.dumps(res, indent=2, sort_keys=True))
            else:
                export_cmd.run(
                    args.path, p, config, args.export_out, fmt=args.format,
                    loci=loci, columns=args.columns, reference=args.reference,
                )
        elif cmd == "aggregate":
            from spark_bam_tpu.agg.plan import AggConfig
            from spark_bam_tpu.cli import aggregate as aggregate_cmd
            from spark_bam_tpu.load.intervals import BadLociError, LociSet

            loci = getattr(args, "intervals", None)
            if loci:
                try:
                    LociSet.parse(loci)  # fail before any work starts
                except BadLociError as e:
                    raise UsageError(str(e)) from e
            try:
                AggConfig.parse(args.agg or config.agg)
                for t in args.tag or ():
                    if len(t) != 2:
                        raise ValueError(
                            f"tag names are exactly two chars: {t!r}"
                        )
            except ValueError as e:
                raise UsageError(str(e)) from e
            aggregate_cmd.run(
                args.path, p, config, agg=args.agg, loci=loci,
                flags_required=args.flags_required,
                flags_forbidden=args.flags_forbidden,
                tags_required=tuple(args.tag or ()),
                fmt=args.format, reference=args.reference,
            )
        elif cmd == "index-blocks":
            from spark_bam_tpu.bgzf.index_blocks import index_blocks

            out_path, count = index_blocks(args.path, args.out)
            print(f"Wrote {count} blocks to {out_path}", file=sys.stderr)
        elif cmd == "index":
            from spark_bam_tpu.cli import index_sbi

            index_sbi.run(
                args.path, p,
                config.split_size_or(Config.LOAD_SPLIT_SIZE_DEFAULT),
                config, out=args.out, record_starts=args.record_starts,
            )
        elif cmd == "index-records":
            from spark_bam_tpu.bam.index_records import index_records

            out_path, count = index_records(
                args.path, args.out, strict=args.throw_on_truncation
            )
            print(f"Wrote {count} records to {out_path}", file=sys.stderr)
        elif cmd == "index-bam":
            from spark_bam_tpu.bam.bai import index_bam

            out_path, idx = index_bam(args.path, args.out)
            n_chunks = sum(
                len(cs) for ref in idx.references for cs in ref.bins.values()
            )
            print(
                f"Wrote {out_path}: {len(idx.references)} references, "
                f"{n_chunks} chunks, {idx.n_no_coor} unplaced reads",
                file=sys.stderr,
            )
        elif cmd in ("htsjdk-rewrite", "rewrite"):
            if args.durable:
                # Journaled rewrite: the WAL + segment files live under
                # the job dir keyed by the spec hash, so re-running the
                # same command after a crash resumes from the last
                # checkpoint and emits a byte-identical artifact.
                import json as _json

                from spark_bam_tpu.jobs.manager import job_id_of
                from spark_bam_tpu.jobs.runner import run_rewrite_job

                spec = {"op": "rewrite", "path": args.in_path,
                        "out": args.out_path,
                        "block_payload": parse_bytes(args.block_payload),
                        "level": args.level,
                        "index": True if args.index else None}
                spec = {k: v for k, v in spec.items() if v is not None}
                jcfg = config.jobs_config
                res = run_rewrite_job(
                    spec, os.path.join(jcfg.root(), job_id_of(spec)),
                    config=config,
                    checkpoint=args.checkpoint or jcfg.checkpoint,
                )
                p.echo(_json.dumps(res, indent=2, sort_keys=True))
            else:
                from spark_bam_tpu.cli import rewrite

                rewrite.run(
                    args.in_path, args.out_path, p,
                    block_payload=parse_bytes(args.block_payload),
                    reindex=args.index,
                    level=args.level,
                    deflate=config.deflate,
                    config=config,
                )
        elif cmd == "fuzz-decode":
            from spark_bam_tpu.tools.fuzz_decode import run_fuzz

            summary = run_fuzz(
                seed=args.seed,
                mutants_per_format=args.mutants,
                formats=tuple(
                    f for f in args.formats.split(",") if f.strip()
                ),
            )
            import json

            p.echo(json.dumps(summary, indent=2, sort_keys=True))
            if summary["violations"]:
                return 1
        elif cmd == "scrub":
            from spark_bam_tpu.cli import scrub as scrub_cmd

            rc = scrub_cmd.run(
                args.paths, p, source=args.source,
                quarantine=args.quarantine, stride=args.stride,
            )
            if rc:
                return rc
        elif cmd == "serve":
            from spark_bam_tpu.serve import ServeAddress, SplitService, serve_forever

            service = SplitService(config)
            addr = ServeAddress(args.listen)
            where = addr.path if addr.kind == "unix" else f"{addr.host}:{addr.port}"
            print(
                f"serving on {args.listen} ({where}; "
                f"{service.mesh.devices.size} devices) — Ctrl-C to stop",
                file=sys.stderr,
            )
            dash = None
            if args.dashboard:
                dash = _service_dashboard(service, args.dashboard)
                print(f"dashboard on http://{dash.address}/ "
                      "(/metrics, /slo, /series)", file=sys.stderr)
            try:
                serve_forever(service, args.listen)
            except KeyboardInterrupt:
                pass
            finally:
                if dash is not None:
                    dash.stop()
                service.close()
        elif cmd == "fabric":
            import os
            import signal as _signal

            from spark_bam_tpu.fabric import Router, WorkerPool
            from spark_bam_tpu.obs import flight
            from spark_bam_tpu.serve import serve_forever

            fcfg = config.fabric_config
            # Workers inherit the fabric spec via env so a chaos run's
            # seed lands in THEIR flight dumps too (fabric/worker.py).
            worker_env = None
            if config.fabric or getattr(args, "disk_chaos", None):
                worker_env = dict(os.environ)
                if config.fabric:
                    worker_env["SPARK_BAM_FABRIC"] = config.fabric
                if getattr(args, "disk_chaos", None):
                    # Disk faults ride the env into every launched
                    # worker (fabric/worker.py installs from it).
                    worker_env["SPARK_BAM_DISK_CHAOS"] = args.disk_chaos
            pool = WorkerPool(
                workers=fcfg.workers, devices=args.worker_devices,
                serve=config.serve, columnar=config.columnar,
                slo=config.slo, attach=args.attach, env=worker_env,
            )
            addresses = pool.start()
            router = Router(addresses, config=config, pool=pool)

            def _graceful(signum, frame):
                # Drain: stop routing new work; workers get SIGTERM in
                # the finally and finish their in-flight ticks unshed.
                flight.record("sigterm", signum=int(signum), who="router")
                router.draining = True
                raise KeyboardInterrupt

            # Handler installed BEFORE the announce: a supervisor that
            # SIGTERMs on seeing the line must still get a clean drain.
            _signal.signal(_signal.SIGTERM, _graceful)
            dash = None
            try:
                chaos_note = (
                    f" [chaos {router.chaos.describe()}]"
                    if router.chaos is not None else ""
                )
                print(
                    f"fabric: routing on {args.listen} over "
                    f"{len(addresses)} workers "
                    f"({'attached' if args.attach else 'launched'}: "
                    f"{', '.join(addresses)}){chaos_note} — Ctrl-C to stop",
                    file=sys.stderr,
                )
                if args.dashboard:
                    dash = _router_dashboard(router, args.dashboard)
                    print(f"dashboard on http://{dash.address}/ "
                          "(/metrics, /slo, /series)", file=sys.stderr)
                serve_forever(router, args.listen)
            except KeyboardInterrupt:
                pass
            except BaseException as exc:
                # The router's own postmortem (satellite of the worker
                # dumps from PR 11): narrate the crash before unwinding —
                # a dead router otherwise leaves no artifact naming what
                # was in flight at the fleet edge.
                flight.dump_auto("crash", who="router",
                                 extra={"error": repr(exc),
                                        "workers": addresses})
                raise
            finally:
                if dash is not None:
                    dash.stop()
                pool.terminate()
                # Graceful-path artifact: the drain dump records the
                # router's routing counters + move ledger tail.
                flight.dump_auto(
                    "drain", who="router",
                    extra={"counters": dict(router.counters),
                           "moves": list(router.moves)[-32:]},
                )
        elif cmd == "metrics-report":
            from spark_bam_tpu.cli import metrics_report

            metrics_report.run(args.trace, p)
        elif cmd == "top":
            from spark_bam_tpu.cli import top

            top.run(args.address, p, prometheus=args.prometheus,
                    watch=args.watch, interval_s=args.interval)
        elif cmd == "lint":
            import spark_bam_tpu as _pkg
            from spark_bam_tpu.analysis import Baseline, render_report, run_lint
            from spark_bam_tpu.analysis.runner import write_json

            pkg_dir = os.path.dirname(os.path.abspath(_pkg.__file__))
            baseline_path = args.baseline or os.path.join(
                os.path.dirname(pkg_dir), "lint-baseline.json"
            )
            rule_ids = ([r.strip() for r in args.rules.split(",") if r.strip()]
                        if args.rules else None)
            try:
                if args.write_baseline is not None:
                    rep = run_lint(paths=args.paths or None,
                                   rule_ids=rule_ids)
                    n = Baseline.write(baseline_path, rep.findings,
                                       args.write_baseline)
                    p.echo(f"wrote {n} entries to {baseline_path} — edit "
                           "per-entry justifications before committing")
                    return 0
                rep = run_lint(
                    paths=args.paths or None, rule_ids=rule_ids,
                    baseline=None if args.no_baseline else baseline_path,
                )
            except ValueError as e:
                raise UsageError(str(e)) from e
            if args.json_out:
                write_json(rep, args.json_out)
            p.echo(render_report(rep, verbose=args.verbose))
            return 0 if rep.ok else 1
        # Fault-tolerance postscript: whenever partition execution had to
        # retry/hedge/quarantine, say so (the quarantine list is the
        # operator's cue that the output is a degraded-but-complete run).
        rep = last_report()
        if rep is not None and (rep.retries or rep.hedges or rep.quarantined
                                or rep.lost_records or rep.lost_blocks):
            p.echo(rep.summary())
        if chaos_state is not None:
            injected = ", ".join(
                f"{k}={v}" for k, v in chaos_state.injected.items() if v
            )
            p.echo(f"chaos(seed={chaos_state.seed}): injected "
                   f"{injected or 'nothing'}")
        if disk_state is not None:
            injected = ", ".join(
                f"{k}={v}" for k, v in disk_state.injected.items() if v
            )
            p.echo(f"disk-chaos(seed={disk_state.seed}): injected "
                   f"{injected or 'nothing'}")
        return 0
    except UsageError as e:
        # Flag-combination errors (e.g. --sharded with -u or CRAM) present
        # as one-line usage errors; library failures keep their tracebacks.
        print(f"error: {e}", file=sys.stderr)
        return 2
    finally:
        if profile_set:
            os.environ.pop("SPARK_BAM_PROFILE", None)
        if chaos_state is not None:
            uninstall_chaos()
        if disk_state is not None:
            uninstall_disk_chaos()
        if getattr(args, "remote", None) is not None:
            from spark_bam_tpu.core.remote_plan import set_remote_config

            set_remote_config(None)  # in-process callers (tests) reset clean
        root_span.__exit__(None, None, None)
        if metrics_out:
            # Export after the root span closes so it lands in the trace;
            # shutdown so in-process callers (tests) start the next run
            # from a clean disabled state.
            obs.export_jsonl(metrics_out)
            obs.shutdown()
        if out:
            out.close()


if __name__ == "__main__":
    sys.exit(main())
