"""Typed configuration surface.

The reference exposes value-class knobs with implicit defaults
(bgzf/.../block/package.scala:20-22, check/.../package.scala:36-58,
bgzf/.../EstimatedCompressionRatio.scala:5-14) plus a ``spark.bam.*``-style
config namespace. Here the same knobs live on one explicit dataclass; every
API/CLI entry point threads a ``Config`` instead of Scala implicits.

Keys may also be supplied as a flat ``{"spark.bam.<knob>": value}`` mapping
(``Config.from_dict``) for parity with the reference's config-surface contract
(BASELINE.json: "gated behind the existing Checker plugin and spark.bam.*
config surface").
"""

from __future__ import annotations

import dataclasses
import os
import re
from dataclasses import dataclass

_SIZE_RE = re.compile(r"^\s*(\d+(?:\.\d+)?)\s*([kKmMgGtTpP]?)i?[bB]?\s*$")

_SIZE_FACTORS = {
    "": 1,
    "k": 1 << 10,
    "m": 1 << 20,
    "g": 1 << 30,
    "t": 1 << 40,
    "p": 1 << 50,
}


def parse_bytes(s) -> int:
    """Parse byte-size shorthand: ``"2MB"``, ``"32m"``, ``"100KB"``, ``1024``.

    Mirrors the reference's ``hammerlab.bytes`` shorthand accepted by
    ``SplitSize.Args`` (check/.../args/SplitSize.scala:9-32).
    """
    if isinstance(s, int):
        return s
    m = _SIZE_RE.match(str(s))
    if not m:
        raise ValueError(f"Bad byte-size: {s!r}")
    value, unit = m.groups()
    return int(float(value) * _SIZE_FACTORS[unit.lower()])


def format_bytes(n: int) -> str:
    for unit, shift in (("PB", 50), ("TB", 40), ("GB", 30), ("MB", 20), ("KB", 10)):
        if n >= (1 << shift) and n % (1 << shift) == 0:
            return f"{n >> shift}{unit}"
    for unit, shift in (("PB", 50), ("TB", 40), ("GB", 30), ("MB", 20), ("KB", 10)):
        if n >= (1 << shift):
            return f"{n / (1 << shift):.1f}{unit}"
    return f"{n}B"


@dataclass(frozen=True)
class Config:
    # --- BGZF block search (bgzf/.../block/package.scala:20-22) ---
    bgzf_blocks_to_check: int = 5       # consecutive headers a block-start must chain
    # --- record checking (check/.../package.scala:36-58) ---
    reads_to_check: int = 10            # consecutive records a boundary must chain
    max_read_size: int = 10_000_000     # byte budget for a boundary scan
    # --- split planning ---
    split_size: int | None = None       # bytes; None → context default (2MB check path)
    estimated_compression_ratio: float = 3.0
    # --- backend selection: the Checker plugin surface ---
    checker: str = "eager"              # eager | full | indexed | seqdoop
    backend: str = "auto"               # one of BACKENDS; anything else is refused
    # --- TPU execution shape ---
    # Uncompressed bytes checked per device window. The streaming path
    # rounds (window + carry) up to a power of two for the kernel shape, so
    # 24 MB + the 4 MB halo stays within a 32 MB kernel — the largest that
    # fits a 16 GB-HBM chip (64 MB windows OOM at compile time).
    window_size: int = 24 << 20
    halo_size: int = 4 << 20            # extra trailing bytes so chains can complete
    # --- fault tolerance (core/faults.py; docs/robustness.md) ---
    # Compact FaultPolicy spec ("retries=3,deadline=60,mode=tolerant"; "" =
    # defaults). Kept as the string form so the frozen dataclass stays
    # hashable/env-roundtrippable; ``fault_policy`` parses it (cached).
    faults: str = ""
    # --- split-index cache (sbi/; docs/caching.md) ---
    # "off | read | write | readwrite" with optional ",strict" suffix
    # ("" = off). Same string-spec pattern as ``faults``; ``cache_mode``
    # parses it. Sidecar location/budget come from SPARK_BAM_CACHE_DIR /
    # SPARK_BAM_CACHE_BUDGET (store-level, not Config knobs).
    cache: str = ""
    # --- decode limits (core/guard.py; docs/robustness.md) ---
    # Compact DecodeLimits spec ("record=32MB,refs=1000"; "" = defaults).
    # Same string-spec pattern; ``decode_limits`` parses it (cached).
    limits: str = ""
    # --- remote data plane (core/remote_plan.py; docs/remote.md) ---
    # Compact RemoteConfig spec ("mode=plan,depth=8,gap=128KB,hedge=3";
    # "" = defaults: plan-driven, adaptive depth). Same string-spec
    # pattern; ``remote_config`` parses it (cached).
    remote: str = ""
    # --- serving daemon (serve/; docs/serving.md) ---
    # Compact ServeConfig spec ("batch=16,tick=2,scan_queue=128,window=1MB";
    # "" = defaults). Same string-spec pattern; ``serve_config`` parses it
    # (cached). Governs the long-running split/record service's batching,
    # admission limits, and resident-cache budgets.
    serve: str = ""
    # --- columnar analytics plane (columnar/; docs/analytics.md) ---
    # Compact ColumnarConfig spec ("rows=8192,codec=zlib,level=6,
    # columns=flag+pos+name"; "" = defaults). Same string-spec pattern;
    # ``columnar_config`` parses it (cached). Governs record-batch row
    # counts, native-container compression, and the default projection
    # for the export sinks and the serve ``batch`` op.
    columnar: str = ""
    # --- write-path compression (compress/; docs/design.md) ---
    # Compact DeflateConfig spec ("mode=fixed,level=6,lanes=16,
    # device=auto"; "" = defaults: host zlib). Same string-spec pattern;
    # ``deflate_config`` parses it (cached). Governs the block codec
    # behind write_bam/htsjdk-rewrite/the serve ``rewrite`` op: stored /
    # fixed-Huffman members batch-compressed on device with per-window
    # demote-to-host, or the seed host-zlib path when off.
    deflate: str = ""
    # --- serve fabric control plane (fabric/; docs/fabric.md) ---
    # Compact FabricConfig spec ("workers=3,slo=200,probe=500,spill=8";
    # "" = defaults). Same string-spec pattern; ``fabric_config`` parses
    # it (cached). Governs the router's worker pool, affinity spillover,
    # health probe/eject pacing, and the SLO autoscaler's target and
    # actuation floors/ceilings.
    fabric: str = ""
    # --- durable job plane (jobs/; docs/robustness.md) ---
    # Compact JobsConfig spec ("dir=/var/jobs,checkpoint=5000,frames=8,
    # mem=0.92,max=2"; "" = defaults). Same string-spec pattern;
    # ``jobs_config`` parses it. Governs the WAL job directory, the
    # checkpoint cadence for journaled rewrite/export/transcode, and the
    # manager's admission watermarks (max concurrent jobs, host-memory
    # fraction above which submits defer).
    jobs: str = ""
    # --- disk-fault chaos seam (core/faults.py; docs/robustness.md) ---
    # "SEED:SPEC" (e.g. "9:enospc=0.05+torn=0.01"; "" = off). Carried as
    # a Config knob so SPARK_BAM_DISK_CHAOS round-trips through
    # ``Config.from_env`` into pool workers; installation itself happens
    # at process entry (``maybe_install_disk_chaos_from_env`` /
    # ``--disk-chaos``), not lazily — a seam that appears mid-run would
    # make the seeded fault schedule depend on call order.
    disk_chaos: str = ""
    # --- on-device aggregation plane (agg/; docs/analytics.md) ---
    # Compact AggConfig spec ("coverage:bin=1000,bins=512;flagstat;mapq;
    # tlen:max=2000;count"; "" = every metric at defaults). Same
    # string-spec pattern; ``agg_config`` parses it (cached). Governs
    # the default metric plan behind the serve ``aggregate`` op, the
    # ``aggregate`` CLI subcommand and ``load.api.aggregate``; requests
    # may override it per call.
    agg: str = ""
    # --- SLO objectives + burn-rate alerting (obs/slo.py) ---
    # Compact SloConfig spec ("serve.latency:p99<1500ms@5m;
    # serve.errors:ratio<0.1%@1h;sample=0.1"; "" = disabled). Same
    # string-spec pattern; ``slo_config`` parses it (cached). Governs the
    # serve-side SLO engine's objectives, alerting windows/threshold, and
    # the tail sampler's keep fraction/seed (docs/observability.md).
    slo: str = ""
    # --- candidate funnel (tpu/checker.py; docs/design.md) ---
    # Two-stage checker hot path: cheap fixed-block prefilter over every
    # position, full 19-flag pass only on survivors. "auto" (default)
    # funnels verdict projections (spans/count/check-bam) and keeps the
    # single-pass kernel wherever full per-position flag masks are the
    # product (full-check forensics) — the funnel's masks are only
    # verdict-faithful. "on" behaves like auto (mask projections always
    # take the exact path); "off" disables it everywhere.
    funnel: str = "auto"                # on | off | auto
    # --- device pacing (tpu/stream_check.py) ---
    # Device→host flush interval of the count's windows
    # (StreamChecker.count_reads), in windows. None → auto: ≤ 2^30
    # positions between flushes so the on-device int32 accumulators cannot
    # overflow (the auto cap still bounds explicit values).
    flush_every: int | None = None
    # Windows whose device scalars may remain un-synced in the count's
    # ring: how far the feeding thread runs ahead of the device.
    ring_depth: int = 2
    # --- misc ---
    warn: bool = False                  # root log-level toggle (args/LogArgs.scala:30-33)
    # Accepted for config-surface parity (PostPartitionArgs -p, default
    # 100000, args/PostPartitionArgs.scala:38-43) but intentionally inert:
    # the reference repartitions its filtered-calls RDD so annotation work
    # balances across executors; here disagreement positions are a host
    # array and annotation is vectorized, so there is no partition count to
    # tune. Kept so reference invocations parse unchanged.
    post_partition_size: int = 100_000

    CHECK_SPLIT_SIZE_DEFAULT = 2 << 20  # Blocks.scala:64
    LOAD_SPLIT_SIZE_DEFAULT = 32 << 20  # hadoop FileSplits default in the load path

    BACKENDS = ("auto", "tpu", "numpy", "python", "native")

    def __post_init__(self):
        # Outside input (spark.bam.backend, SPARK_BAM_BACKEND): a name no
        # engine answers to must not run the NumPy engine unasked.
        if self.backend not in self.BACKENDS:
            raise ValueError(
                f"Bad backend: {self.backend!r} "
                f"(expected {' | '.join(self.BACKENDS)})"
            )

    @property
    def fault_policy(self):
        """The parsed ``FaultPolicy`` for this config's ``faults`` spec."""
        from spark_bam_tpu.core.faults import FaultPolicy

        return FaultPolicy.parse(self.faults)

    @property
    def cache_mode(self):
        """The parsed ``CacheMode`` for this config's ``cache`` spec."""
        from spark_bam_tpu.sbi.store import CacheMode

        return CacheMode.parse(self.cache)

    @property
    def decode_limits(self):
        """The parsed ``DecodeLimits`` for this config's ``limits`` spec."""
        from spark_bam_tpu.core.guard import DecodeLimits

        return DecodeLimits.parse(self.limits)

    @property
    def remote_config(self):
        """The parsed ``RemoteConfig`` for this config's ``remote`` spec."""
        from spark_bam_tpu.core.remote_plan import RemoteConfig

        return RemoteConfig.parse(self.remote)

    @property
    def serve_config(self):
        """The parsed ``ServeConfig`` for this config's ``serve`` spec."""
        from spark_bam_tpu.serve.config import ServeConfig

        return ServeConfig.parse(self.serve)

    @property
    def columnar_config(self):
        """The parsed ``ColumnarConfig`` for this config's ``columnar`` spec."""
        from spark_bam_tpu.columnar.config import ColumnarConfig

        return ColumnarConfig.parse(self.columnar)

    @property
    def deflate_config(self):
        """The parsed ``DeflateConfig`` for this config's ``deflate`` spec."""
        from spark_bam_tpu.compress.config import DeflateConfig

        return DeflateConfig.parse(self.deflate)

    @property
    def fabric_config(self):
        """The parsed ``FabricConfig`` for this config's ``fabric`` spec."""
        from spark_bam_tpu.fabric.config import FabricConfig

        return FabricConfig.parse(self.fabric)

    @property
    def jobs_config(self):
        """The parsed ``JobsConfig`` for this config's ``jobs`` spec."""
        from spark_bam_tpu.jobs.manager import JobsConfig

        return JobsConfig.parse(self.jobs)

    @property
    def disk_chaos_config(self):
        """The parsed ``(seed, DiskChaosSpec)`` for this config's
        ``disk_chaos`` spec, or ``None`` when off."""
        from spark_bam_tpu.core.faults import parse_disk_chaos

        return parse_disk_chaos(self.disk_chaos) if self.disk_chaos else None

    @property
    def agg_config(self):
        """The parsed ``AggConfig`` for this config's ``agg`` spec."""
        from spark_bam_tpu.agg.plan import AggConfig

        return AggConfig.parse(self.agg)

    @property
    def slo_config(self):
        """The parsed ``SloConfig`` for this config's ``slo`` spec."""
        from spark_bam_tpu.obs.slo import SloConfig

        return SloConfig.parse(self.slo)

    def funnel_enabled(self, full_masks: bool = False) -> bool:
        """Whether a projection should run the two-stage candidate funnel.

        ``full_masks=True`` marks projections whose *product* is the
        per-position flag mask (full-check forensics): those always take
        the exact single-pass kernel — the funnel's masks carry only
        prefilter bits at rejected positions, so they are verdict-faithful
        but not mask-faithful.
        """
        mode = self.funnel
        if mode not in ("on", "off", "auto"):
            raise ValueError(
                f"Bad funnel mode: {mode!r} (expected on | off | auto)"
            )
        return mode != "off" and not full_masks

    def flush_every_for(self, kernel_window: int) -> int:
        """Count-path flush interval for this kernel window: the explicit
        knob when set, else the int32-overflow-safe auto value; either way
        capped so ≤ 2^30 positions accumulate between flushes."""
        auto = max(1, (1 << 30) // max(kernel_window, 1))
        if self.flush_every is None:
            return auto
        return max(1, min(self.flush_every, auto))

    def split_size_or(self, default: int) -> int:
        return self.split_size if self.split_size is not None else default

    def replace(self, **kw) -> "Config":
        if "split_size" in kw and kw["split_size"] is not None:
            kw["split_size"] = parse_bytes(kw["split_size"])
        return dataclasses.replace(self, **kw)

    _PREFIX = "spark.bam."

    @classmethod
    def from_dict(cls, d: dict, base: "Config | None" = None) -> "Config":
        """Build from a flat ``spark.bam.*`` (or bare-key) mapping."""
        base = base or cls()
        fields = {f.name: f for f in dataclasses.fields(cls)}
        kw = {}
        for key, value in d.items():
            name = key[len(cls._PREFIX):] if key.startswith(cls._PREFIX) else key
            name = name.replace(".", "_").replace("-", "_")
            if name not in fields:
                raise KeyError(f"Unknown config key: {key}")
            f = fields[name]
            if f.type in ("int", int):
                value = parse_bytes(value) if isinstance(value, str) else int(value)
            elif f.type == "int | None":
                if value is None or str(value).lower() in ("auto", "none", ""):
                    value = None
                else:
                    value = parse_bytes(value)
            elif f.type in ("float", float):
                value = float(value)
            elif f.type in ("bool", bool):
                if not isinstance(value, bool):
                    value = str(value).lower() in ("1", "true", "yes")
            kw[name] = value
        return base.replace(**kw)

    # SPARK_BAM_* sub-namespaces that are NOT Config knobs (cloud backend
    # endpoints/tokens in core/cloud.py; cache-store location/budget in
    # sbi/store.py; telemetry artifact paths in obs/) — from_env must not
    # trip on them. Note the bare SPARK_BAM_CACHE still maps to the
    # ``cache`` knob.
    _ENV_NON_CONFIG = ("gs_", "s3_", "profile", "cache_",
                       "metrics_out", "flight_dir")

    @classmethod
    def from_env(cls, env=os.environ, base: "Config | None" = None) -> "Config":
        """Read ``SPARK_BAM_<KNOB>`` environment overrides."""
        d = {}
        for key, value in env.items():
            if key.startswith("SPARK_BAM_"):
                name = key[len("SPARK_BAM_"):].lower()
                if name.startswith(cls._ENV_NON_CONFIG):
                    continue
                d[name] = value
        return cls.from_dict(d, base=base) if d else (base or cls())


def default_config() -> Config:
    return Config.from_env()
