"""Registered metric/span name catalog — the obs naming contract.

Every metric and span name the package emits lives here, grouped by
hot-path layer (the dotted ``layer.stage`` convention from
obs/registry.py). The ``obs-contract`` lint pass
(analysis/rules/obs_contract.py) enforces it: a literal name passed to
``obs.count``/``observe``/``span``/``counter``/``gauge``/``histogram``
that is not in :data:`NAMES` fails the gate, and dynamic (f-string)
names are flagged unless their literal prefix is a registered layer AND
the call site carries an inline allow justifying bounded cardinality.

Why a registry: the PR 11 telemetry plane merges snapshots across
processes by name (obs/exporters.py ``merge_snapshots``) and renders
fleet dashboards from them — an ad-hoc name in one worker silently
forks a series the merge can't join, and an unbounded name (one series
per request id) OOMs the registry. Adding a metric = adding one line
here; the whole-repo lint test fails until you do.
"""

from __future__ import annotations

#: Layer prefixes (the segment before the first dot). A new layer means
#: a new subsystem — add it here alongside its names.
LAYERS = frozenset({
    "account", "agg", "bgzf", "cache", "chaos", "check", "checkbam", "cli",
    "columnar", "compress", "deflate", "fabric", "faults", "funnel",
    "guard", "host", "inflate", "jobs", "load", "mesh", "progress", "remote",
    "sampler", "scrub", "serve", "slo", "timer", "transport", "ts",
})

NAMES = frozenset({
    # account — per-request cost accounting (obs/account.py)
    "account.requests", "account.tenants",
    # agg — fused on-device aggregation plane (docs/analytics.md)
    "agg.bytes_out", "agg.encode", "agg.host_fallbacks", "agg.reduce",
    "agg.requests", "agg.rows",
    # bgzf — block streaming (docs/design.md)
    "bgzf.blocks_read", "bgzf.blocks_scanned", "bgzf.blocks_scanned_native",
    "bgzf.bytes_inflated", "bgzf.bytes_read", "bgzf.read",
    # cache — .sbi split-index sidecars (docs/caching.md)
    "cache.bytes", "cache.evictions", "cache.hits", "cache.invalidations",
    "cache.misses", "cache.read_ms", "cache.write_errors", "cache.write_ms",
    # chaos — deterministic fault injection (docs/robustness.md);
    # chaos.disk_* are the filesystem-seam kinds (core/faults.py)
    "chaos.corrupted_bytes", "chaos.io_errors", "chaos.latency_spikes",
    "chaos.short_reads",
    "chaos.disk_enospc", "chaos.disk_eio", "chaos.disk_short_writes",
    "chaos.disk_torn_writes", "chaos.disk_rename_fails",
    # check — record-boundary checker
    "check.accepted", "check.candidates", "check.count_escape_retries",
    "check.defer_resolved", "check.defer_retries", "check.deferred",
    "check.escape_candidates", "check.escape_overflows",
    "check.escape_resolve", "check.escape_resolved",
    "check.escaped", "check.find_record_start", "check.flush",
    "check.fused_demotions", "check.pace",
    "check.window", "check.windows",
    # checkbam — check-bam against the .records truth on the mesh
    # (load/tpu_load.check_bam_tpu, parallel/stream_mesh.check_bam_sharded)
    "checkbam.list_overflows", "checkbam.mismatches", "checkbam.passes",
    "checkbam.truth_load", "checkbam.truth_restarts", "checkbam.truth_wait",
    # cli — root spans, one per subcommand (cli/main.py)
    "cli.aggregate", "cli.check-bam", "cli.check-blocks",
    "cli.compare-splits", "cli.compute-splits", "cli.count-reads",
    "cli.export", "cli.fabric",
    "cli.full-check", "cli.fuzz-decode", "cli.htsjdk-rewrite",
    "cli.index", "cli.index-bam", "cli.index-blocks", "cli.index-records",
    "cli.lint", "cli.metrics-report", "cli.rewrite", "cli.scrub",
    "cli.serve", "cli.time-load", "cli.top",
    # columnar — record-batch analytics plane (docs/analytics.md)
    "columnar.build_ms", "columnar.bytes_out", "columnar.encode_ms",
    "columnar.export", "columnar.rows",
    # compress — write-path member/batch ledger (docs/design.md)
    "compress.batches", "compress.bytes_in", "compress.bytes_out",
    "compress.fixed", "compress.members", "compress.stored",
    # deflate — device-side BGZF compression (docs/design.md, write path)
    "deflate.d2h_ms", "deflate.demotions", "deflate.device_ms",
    "deflate.device_windows", "deflate.dispatch", "deflate.host_ms",
    "deflate.pack_ms",
    # fabric — control plane (docs/fabric.md); fabric.<counter> names are
    # emitted through Router._count's bounded literal set
    "fabric.relay", "fabric.autoscale_moves", "fabric.drained",
    "fabric.ejected", "fabric.failovers", "fabric.lost",
    "fabric.reinstated", "fabric.relayed_overload", "fabric.routed",
    "fabric.spilled",
    # fabric.breaker — per-link circuit breakers (docs/robustness.md)
    "fabric.breaker.opened", "fabric.breaker.half_open",
    "fabric.breaker.closed", "fabric.breaker.holddowns",
    # fabric resilience: retry budget, brownout, streaming failover,
    # durable-job orphan rescue (docs/robustness.md)
    "fabric.budget_spent", "fabric.budget_exhausted",
    "fabric.brownout_shed", "fabric.streamed", "fabric.stream_frames",
    "fabric.resumed", "fabric.job_rescues",
    # fabric.chaos — fleet-seam fault injection (fabric/chaos.py)
    "fabric.chaos.drops", "fabric.chaos.delays", "fabric.chaos.dups",
    "fabric.chaos.truncs", "fabric.chaos.slowed",
    "fabric.chaos.accept_delays", "fabric.chaos.kills",
    "fabric.chaos.wedges",
    # fabric.chaos shm seam — rolled per frame record by the serve
    # accept loop (docs/serving.md "Transport")
    "fabric.chaos.shm_crcs", "fabric.chaos.shm_truncs",
    "fabric.chaos.shm_unlinks",
    # faults — retry/hedge/quarantine ledger (docs/robustness.md)
    "faults.attempt_ms", "faults.hedges", "faults.quarantined",
    "faults.quarantined_blocks", "faults.retries",
    # funnel — two-stage checker candidate funnel (docs/design.md).
    # survivors / lanes: stage 0's survivors and the lanes the lane stage
    # ran for them (whole blocks, each window's or row's own), from every
    # path that runs it: the count's stream and mesh steps, the served
    # tick (also serve.tick_lanes, one observation a tick) and check-bam's
    # steps (also mesh.step_lanes, one a step). positions: stream only.
    "funnel.lanes", "funnel.positions", "funnel.survivors",
    # guard — untrusted-byte decode boundary (core/guard.py)
    "guard.quarantined_blocks", "guard.quarantined_records",
    # host — the registry's witness of the host (obs/witness.py;
    # docs/observability.md "The host"): host.sleep (annotation: each wait
    # of the witness thread, a line of its own in a capture), host.stop
    # (span event: a wake 40 ms late or more, the collector's hold taken
    # out, in the trace of every open pass; histogram once a stop), host.gc
    # (span event: a full collection, or any of 1 ms or more; attr
    # generation), host.overshoot_ms (every wake's lateness, less the
    # collector's hold), host.pace_us (a CRC of 64 KiB timed at every wake:
    # the host's speed), host.stops (counter)
    "host.gc", "host.overshoot_ms", "host.pace_us", "host.sleep",
    "host.stop", "host.stops",
    # inflate — host BGZF inflate feeding the device (docs/design.md)
    "inflate.block", "inflate.blocks", "inflate.bytes",
    "inflate.device_kernel", "inflate.device_ms",
    "inflate.h2d", "inflate.h2d_bytes", "inflate.h2d_ms",
    "inflate.stall_ms", "inflate.window", "inflate.windows",
    # jobs — durable job plane: WAL + crash-resumable runners
    # (docs/robustness.md "Durable jobs & scrubbing")
    "jobs.cancelled", "jobs.checkpoint_bytes", "jobs.checkpoints",
    "jobs.completed", "jobs.deferred", "jobs.export", "jobs.failed",
    "jobs.journal_appends", "jobs.journal_skipped",
    "jobs.journal_truncated", "jobs.paused", "jobs.preflight_rejects",
    "jobs.redone_bytes", "jobs.resumed", "jobs.rewrite", "jobs.scrub",
    "jobs.submitted",
    # load — partition execution, and the whole-file pass: the roots
    # load.count / load.check_bam (obs.pass_span: one trace a pass), its
    # phases on the feeding thread load.open (header, contig lengths and
    # their put, the program's lookup) and load.drain (what follows the last
    # dispatch and no other span holds), and its own account at the root's
    # exit, load.head_ms / load.drain_ms, and of the host load.stop_ms
    # (the machine's stops inside the pass) / load.gc_ms (the collector's
    # pauses) / load.cpu_ms (the process's CPU time over it), one
    # observation a pass each (docs/observability.md "A pass", "The host")
    "load.check_bam", "load.count", "load.cpu_ms", "load.drain",
    "load.drain_ms", "load.fleet_files", "load.gc_ms", "load.head_ms",
    "load.open", "load.parse", "load.partition",
    "load.partitions", "load.record_starts", "load.records",
    "load.split_resolutions", "load.stop_ms",
    # load — the streaming load (load/tpu_load.stream_read_batches,
    # tpu/stream_check.read_batches): the root load.reads (a pass as the
    # two above), load.batch (feeding thread: a window's rows read back
    # and wrapped), load.device_ms (a window of load_window on the device,
    # as inflate.device_ms is the count's), and a pass's account:
    # records_parsed (every record, as the count counts them), rows_out
    # (what passed loci and flags), d2h_bytes (all that came back),
    # cigar_host_fixups (rows whose CIGAR outran the device's scan),
    # spilled_records (decoded from the seekable stream)
    "load.batch", "load.cigar_host_fixups", "load.d2h_bytes",
    "load.device_ms", "load.passes", "load.reads", "load.records_parsed",
    "load.rows_out", "load.spilled_records",
    # mesh — compiled-step registry + shard_map dispatch
    "mesh.assemble", "mesh.block_reuse", "mesh.dirty_steps", "mesh.dispatch",
    "mesh.escapes",
    "mesh.h2d", "mesh.h2d_bytes",
    "mesh.patch_chunk_positions", "mesh.patch_chunks", "mesh.patch_rows",
    "mesh.plan", "mesh.row_inflate", "mesh.rows", "mesh.stall", "mesh.step",
    "mesh.step_device_ms", "mesh.step_lanes", "mesh.steps",
    "mesh.truth_fill",
    # progress — long-run heartbeats
    "progress.beats",
    # remote — plan-driven data plane (docs/remote.md)
    "remote.bucket_wait_ms", "remote.bytes", "remote.depth",
    "remote.evictions", "remote.get_ms", "remote.gets", "remote.hedge_wins",
    "remote.hedges", "remote.plan_segments", "remote.quota_wait_ms",
    "remote.stalls", "remote.unplanned_gets",
    # sampler — tail-based trace sampling (obs/sampler.py)
    "sampler.dropped", "sampler.exemplars", "sampler.kept",
    # scrub — end-to-end integrity scrubber (jobs/scrub.py)
    "scrub.artifacts", "scrub.findings", "scrub.quarantined",
    "scrub.records_checked",
    # serve — split-service daemon (docs/serving.md)
    "serve.batch_encode", "serve.batch_pack", "serve.batch_rows",
    "serve.batch_wait", "serve.batches",
    "serve.connections", "serve.cycle", "serve.d2h",
    "serve.device_dispatch",
    "serve.errors", "serve.file_open", "serve.flat_resident_mib",
    "serve.h2d",
    "serve.h2d_bytes", "serve.latency_ms", "serve.overloaded",
    "serve.parse", "serve.queue_depth", "serve.queue_ms", "serve.request",
    "serve.requests", "serve.rewrite", "serve.scatter",
    "serve.segment_evictions", "serve.segment_hits",
    "serve.segment_inflate", "serve.segment_misses", "serve.segment_waits",
    "serve.shed",
    "serve.step", "serve.stream_aborts",
    "serve.tick", "serve.tick_lanes", "serve.ticks_overlapped",
    "serve.tuned", "serve.worker_wait_ms",
    # serve shm — segment lifecycle + encoded-frame cache
    # (docs/serving.md "Transport")
    "serve.frame_cache_hits", "serve.frame_cache_misses",
    "serve.shm_crc_errors", "serve.shm_orphans_cleaned",
    "serve.shm_segments",
    # slo — burn-rate objective engine (obs/slo.py)
    "slo.alerts", "slo.burn_rate", "slo.evals", "slo.firing",
    # transport — zero-copy data plane: shm rings, descriptor relay,
    # handshake downgrades (docs/serving.md "Transport")
    "transport.downgrades", "transport.inline_frames",
    "transport.relay_descriptors", "transport.ring_full_waits",
    "transport.segment_announces", "transport.shm_bytes",
    "transport.shm_connections", "transport.shm_frames",
    # ts — time-series ring scraper (obs/timeseries.py)
    "ts.scrapes", "ts.series",
})

#: ``jax.named_scope`` names inside the jitted programs, as they appear in
#: an operation's name path in a device trace
#: (``jit(count_window)/.../check/flags/...``). A reduction finds a stage
#: by these, so they are a contract like the span names above. The window
#: program (tpu/checker.count_window) and the steps of parallel/mesh.py
#: have ``check`` with its children ``flags``, ``funnel`` and
#: ``chain_walk``, and ``reduce`` (the count sums, a step's psum, the
#: confusion step's sums and mismatch list); ``check_window`` (the served
#: step, check-bam) has ``check/scatter`` besides, the lanes' verdicts
#: scattered back over every position; the confusion step's cross-chip
#: tail (the ``psum`` of its sums, the mismatch lists gathered over the
#: mesh) is ``collect``; agg/kernels.py has ``agg_reduce``.
#: Under the funnel the lane stage's three (``flags``, ``funnel``,
#: ``chain_walk``) sit inside its block loops (``check/while/body/...``),
#: in ``check_window`` as in the count; ``scatter`` runs once, after them.
#: The load's window program (tpu/checker.load_window) has ``check`` as the
#: count has it and, beside it in the walk's block loop, ``parse`` (the
#: accepted lanes' fixed blocks and CIGAR spans out of the word view) and
#: ``filter`` (loci and flags, and the rows that passed moved into the
#: window's table); its ``reduce`` is the seven integers it reports.
SCOPES = frozenset({
    "agg_reduce", "chain_walk", "check", "collect", "filter", "flags",
    "funnel", "parse", "reduce", "scatter",
})

#: Names of the jitted programs the scopes live in: ``jit_<name>`` is the
#: XLA module's name, which a device trace shows for each execution.
PROGRAMS = frozenset({
    "agg_step", "agg_update", "check_step", "check_window",
    "confusion_step", "count_step", "count_window", "full_step",
    "load_window", "serve_step", "sharded_check_step",
})


def is_registered(name: str) -> bool:
    return name in NAMES


def layer_of(name: str) -> str:
    return name.split(".", 1)[0]
