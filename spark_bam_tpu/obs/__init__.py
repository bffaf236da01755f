"""Unified observability: metrics registry, span tracing, exporters.

See ``spark_bam_tpu.obs.registry`` for the design and
``docs/observability.md`` for usage. Import the package and use the
module-level entry points::

    from spark_bam_tpu import obs

    with obs.span("inflate.window", blocks=len(metas)):
        ...
    obs.count("bgzf.blocks_read", len(metas))

Everything is a shared no-op until ``obs.configure()`` runs (the CLI's
``--metrics-out`` / the ``SPARK_BAM_METRICS_OUT`` env var does this).
"""

from spark_bam_tpu.obs import account, flight, sampler, slo, timeseries, trace
from spark_bam_tpu.obs.registry import (
    NOOP,
    Counter,
    Gauge,
    Histogram,
    PassSpan,
    Registry,
    Span,
    annotate,
    configure,
    count,
    counter,
    dispatched,
    enabled,
    export_jsonl,
    gauge,
    histogram,
    observe,
    pass_span,
    read_jsonl,
    registry,
    resolve_metrics_path,
    shutdown,
    span,
)

__all__ = [
    "NOOP",
    "Counter",
    "Gauge",
    "Histogram",
    "PassSpan",
    "Registry",
    "Span",
    "account",
    "annotate",
    "configure",
    "count",
    "counter",
    "dispatched",
    "enabled",
    "export_jsonl",
    "flight",
    "gauge",
    "histogram",
    "observe",
    "pass_span",
    "read_jsonl",
    "registry",
    "resolve_metrics_path",
    "sampler",
    "shutdown",
    "slo",
    "span",
    "timeseries",
    "trace",
]
