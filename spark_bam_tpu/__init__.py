"""spark-bam-tpu: TPU-native parallel BAM loading.

A from-scratch reimplementation of the capabilities of fnothaft/spark-bam
(Scala/Spark) as a TPU-first framework:

- ``core``     — virtual positions, config surface, byte ranges, channels
- ``bgzf``     — BGZF block layer: header parse, block streams, block-start search
- ``bam``      — BAM structure: header/contigs, record codec, .bai index, iterators
- ``check``    — record-boundary checkers (eager / full / indexed / seqdoop-semantics)
                 plus the vectorized host (NumPy) checker
- ``tpu``      — JAX/XLA vectorized checker + batched record parser (the hot path)
- ``parallel`` — host orchestration, device meshes, sharded multi-chip check step
- ``load``     — user-facing load API (load_reads / load_bam / intervals / splits)
- ``cli``      — the 10 operator commands (check-bam, compute-splits, ...)

The reference's Spark substrate (driver/executors, RDDs, broadcast, accumulators)
is replaced by a host-side orchestrator plus fixed-shape batched kernels that XLA
compiles for TPU; see SURVEY.md §7 in the repo root.
"""

from spark_bam_tpu.core.pos import Pos
from spark_bam_tpu.core.config import Config, default_config

__version__ = "0.1.0"

__all__ = [
    "Pos",
    "Config",
    "default_config",
    "load_bam",
    "load_reads",
    "load_sam",
    "load_bam_intervals",
    "load_splits_and_reads",
    "load_reads_and_positions",
    "export",
    "aggregate",
    "count_reads_tpu",
    "check_bam_tpu",
    "load_reads_columnar",
    "record_starts_streaming",
    "stream_read_batches",
    "full_check_summary_streaming",
    "count_reads_sharded",
    "check_bam_sharded",
]

# Lazy exports: the load API pulls in numpy/jax; keep `import spark_bam_tpu`
# cheap. One name → providing-module table serves every lazily-bound symbol.
_LAZY = {
    **{
        name: "spark_bam_tpu.load.api"
        for name in (
            "load_bam", "load_reads", "load_sam", "load_bam_intervals",
            "load_splits_and_reads", "load_reads_and_positions", "export",
            "aggregate",
        )
    },
    **{
        name: "spark_bam_tpu.load.tpu_load"
        for name in (
            "count_reads_tpu", "check_bam_tpu", "load_reads_columnar",
            "record_starts",
            "record_starts_streaming", "stream_read_batches",
        )
    },
    "full_check_summary_streaming": "spark_bam_tpu.tpu.stream_check",
    "count_reads_sharded": "spark_bam_tpu.parallel.stream_mesh",
    "check_bam_sharded": "spark_bam_tpu.parallel.stream_mesh",
}


def __getattr__(name):
    module = _LAZY.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(module), name)
