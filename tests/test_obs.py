"""Observability subsystem: registry semantics, span nesting, JSONL
round-trip, exporter formats, the disabled no-op fast path, trace
propagation, the flight recorder, and the ``--metrics-out`` /
``metrics-report`` CLI surface."""

import threading

import pytest

from spark_bam_tpu import obs
from spark_bam_tpu.obs import flight
from spark_bam_tpu.obs import trace as obs_trace
from spark_bam_tpu.obs.exporters import (
    merge_snapshots,
    parse_prom_labels,
    prometheus_text,
    stage_totals,
    stats_summary,
)
from spark_bam_tpu.obs.registry import _HIST_SAMPLE_CAP, NOOP, Registry


@pytest.fixture
def reg():
    obs.shutdown()
    r = obs.configure()
    yield r
    obs.shutdown()


# ---------------------------------------------------------------- registry


def test_disabled_is_shared_noop_singleton():
    obs.shutdown()
    assert not obs.enabled()
    assert obs.registry() is None
    # Every entry point hands back the SAME object: zero allocation on
    # instrumented hot loops when observability is off.
    assert obs.span("x") is obs.span("y") is NOOP
    assert obs.counter("c") is obs.gauge("g") is obs.histogram("h") is NOOP
    obs.count("c", 5)
    obs.observe("h", 1.0, unit="ms")
    with obs.span("x", k=1) as s:
        s.set(device_ms=3)  # attrs on the noop are swallowed too
    assert obs.registry() is None


def test_counter_gauge_histogram_semantics(reg):
    c = obs.counter("bgzf.blocks_read")
    c.inc()
    c.inc(4)
    assert obs.counter("bgzf.blocks_read") is c  # same series, same object
    assert c.value == 5

    g = obs.gauge("mem.peak")
    g.set(10)
    g.set(3)
    assert g.value == 3 and g.max == 10  # last-write value, running peak

    h = obs.histogram("lat", unit="ms")
    for v in (2.0, 8.0, 5.0):
        h.observe(v)
    assert (h.count, h.sum, h.min, h.max) == (3, 15.0, 2.0, 8.0)
    assert h.values == [2.0, 8.0, 5.0]


def test_labeled_series_are_distinct(reg):
    a = obs.counter("check.windows", kind="whole_file")
    b = obs.counter("check.windows", kind="streaming")
    a.inc()
    assert a is not b and (a.value, b.value) == (1, 0)
    # Label order does not split a series.
    h1 = obs.histogram("x", unit="ms", stage="h2d")
    h2 = obs.histogram("x", stage="h2d", unit="ms")
    assert h1 is h2


def test_count_observe_shorthand(reg):
    obs.count("load.records", 7)
    obs.observe("inflate.stall_ms", 2.5, unit="ms")
    snap = reg.snapshot()
    counters = {c["name"]: c["value"] for c in snap["counters"]}
    assert counters["load.records"] == 7
    hists = {h["name"]: h for h in snap["hists"]}
    assert hists["inflate.stall_ms"]["count"] == 1


# ------------------------------------------------------------------- spans


def test_span_nesting_parent_depth_and_histogram(reg):
    with obs.span("outer"):
        with obs.span("inner", blocks=3):
            pass
        with obs.span("inner"):
            pass
    events = reg.events()
    by_name = {}
    for ev in events:
        by_name.setdefault(ev["name"], []).append(ev)
    # Children close before the parent: completion order in the trace.
    assert [ev["name"] for ev in events] == ["inner", "inner", "outer"]
    assert by_name["outer"][0]["depth"] == 0
    assert "parent" not in by_name["outer"][0]
    for ev in by_name["inner"]:
        assert ev["depth"] == 1 and ev["parent"] == "outer"
    assert by_name["inner"][0]["attrs"] == {"blocks": 3}
    # Every span also feeds its per-name ms histogram.
    hists = {h["name"]: h for h in reg.snapshot()["hists"]}
    assert hists["inner"]["count"] == 2
    assert hists["outer"]["count"] == 1


def test_span_attrs_coerced_to_json_safe(reg):
    class Opaque:
        def __str__(self):
            return "opaque!"

    with obs.span("s", path=Opaque(), n=2, ok=True):
        pass
    attrs = reg.events()[-1]["attrs"]
    assert attrs == {"path": "opaque!", "n": 2, "ok": True}


def test_trace_event_cap_counts_drops(tmp_path):
    r = Registry(max_events=2)
    for _ in range(5):
        with r.span("s"):
            pass
    assert len(r.events()) == 2
    snap = r.snapshot()
    assert snap["dropped_events"] == 3
    # Dropped events still feed the duration histogram (aggregate survives).
    hists = {h["name"]: h for h in snap["hists"]}
    assert hists["s"]["count"] == 5


# -------------------------------------------------------- JSONL round-trip


def test_export_jsonl_round_trip(tmp_path, reg):
    with obs.span("bgzf.read", kind="metadata_scan"):
        with obs.span("inflate.block"):
            pass
    obs.count("bgzf.blocks_read", 3)
    obs.gauge("mem.peak").set(9)
    path = tmp_path / "trace.jsonl"
    obs.export_jsonl(path)

    events = list(obs.read_jsonl(path))
    meta = events[0]
    assert meta["e"] == "meta" and meta["version"] == 1 and meta["enabled"]
    spans = [ev for ev in events if ev["e"] == "span"]
    assert [s["name"] for s in spans] == ["inflate.block", "bgzf.read"]
    assert spans[0]["parent"] == "bgzf.read"
    counters = {ev["name"]: ev for ev in events if ev["e"] == "counter"}
    assert counters["bgzf.blocks_read"]["value"] == 3
    gauges = {ev["name"]: ev for ev in events if ev["e"] == "gauge"}
    assert gauges["mem.peak"]["max"] == 9
    # Span durations also arrive as hist snapshot lines.
    hists = {ev["name"]: ev for ev in events if ev["e"] == "hist"}
    assert hists["bgzf.read"]["count"] == 1


def test_export_jsonl_disabled_writes_empty_run(tmp_path):
    obs.shutdown()
    path = tmp_path / "empty.jsonl"
    obs.export_jsonl(path)
    events = list(obs.read_jsonl(path))
    assert len(events) == 1
    assert events[0]["e"] == "meta" and events[0]["enabled"] is False


# --------------------------------------------------------------- exporters


def test_prometheus_text_format(reg):
    obs.counter("bgzf.blocks_read").inc(2)
    obs.gauge("mem.peak").set(7)
    h = obs.histogram("inflate.window", unit="ms")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    text = prometheus_text(reg.snapshot())
    assert "# TYPE bgzf_blocks_read counter" in text
    assert "bgzf_blocks_read 2" in text
    assert "# TYPE mem_peak gauge" in text
    assert "# TYPE inflate_window summary" in text
    assert 'inflate_window{quantile="0.5",unit="ms"} 2.0' in text
    assert 'inflate_window_sum{unit="ms"} 6.0' in text
    assert 'inflate_window_count{unit="ms"} 3' in text


def test_stats_summary_and_stage_totals(reg):
    obs.counter("load.records").inc(42)
    h = obs.histogram("load.partition", unit="ms")
    h.observe(5.0)
    h.observe(7.0)
    obs.histogram("mesh.patch_chunk_positions").observe(100.0)  # not ms
    snap = reg.snapshot()
    text = stats_summary(snap)
    assert "load.partition[unit=ms]:" in text
    assert "load.records: 42" in text
    # stage_totals keeps only ms-unit series (per-stage bench breakdown).
    totals = stage_totals(snap)
    assert totals == {"load.partition": {"count": 2, "total_ms": 12.0}}


# ------------------------------------------------------------- CLI surface


def _small_bam(tmp_path):
    from tests.bam_factories import random_bam

    path = tmp_path / "smoke.bam"
    random_bam(path, seed=11, n_records=(120, 121))
    return path


def test_cli_count_reads_metrics_out_smoke(tmp_path, capsys, monkeypatch):
    """ISSUE acceptance: ``count-reads --metrics-out`` emits a valid JSONL
    trace whose spans cover the bgzf/inflate/check/load stages, and
    ``metrics-report`` renders it."""
    from spark_bam_tpu.cli.main import main

    monkeypatch.delenv("SPARK_BAM_METRICS_OUT", raising=False)
    bam = _small_bam(tmp_path)
    trace = tmp_path / "m.jsonl"
    # A small split size forces several partitions through the
    # find-block-start → find-record-start resolution path.
    rc = main(
        ["count-reads", "-m", "16k", "--metrics-out", str(trace), str(bam)]
    )
    assert rc == 0
    assert not obs.enabled(), "CLI must shut the registry down on exit"

    events = list(obs.read_jsonl(trace))
    assert events[0]["e"] == "meta" and events[0]["enabled"]
    names = {ev["name"] for ev in events if ev["e"] == "span"}
    assert {
        "cli.count-reads",
        "load.count",
        "load.partition",
        "bgzf.read",
        "check.find_record_start",
        "inflate.block",
    } <= names
    roots = [
        ev for ev in events
        if ev["e"] == "span" and ev["name"] == "cli.count-reads"
    ]
    assert len(roots) == 1 and roots[0]["depth"] == 0
    counters = {
        ev["name"]: ev["value"] for ev in events if ev["e"] == "counter"
    }
    assert counters["bgzf.blocks_read"] > 0
    assert counters["load.partitions"] > 0

    capsys.readouterr()
    rc = main(["metrics-report", str(trace)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "cli.count-reads" in out
    assert "load.partition" in out
    assert "bgzf.blocks_read" in out


def test_cli_disabled_by_default(tmp_path, capsys, monkeypatch):
    from spark_bam_tpu.cli.main import main

    monkeypatch.delenv("SPARK_BAM_METRICS_OUT", raising=False)
    bam = _small_bam(tmp_path)
    rc = main(["count-reads", str(bam)])
    assert rc == 0
    assert not obs.enabled()
    capsys.readouterr()


# ---------------------------------------------------- prometheus escaping


def test_prom_label_escape_round_trip(reg):
    """Satellite: label values holding quotes, backslashes, and newlines
    must render as valid exposition text and parse back verbatim —
    including the nasty literal backslash-n that a sequential unescape
    would corrupt."""
    values = {
        "plain": "worker-0",
        "quote": 'say "hi"',
        "newline": "line1\nline2",
        "backslash": "C:\\temp\\x",
        "literal_bs_n": "a\\nb",          # backslash + 'n', NOT a newline
        "mixed": 'q"\\\n"end',
    }
    for i, (k, v) in enumerate(values.items()):
        obs.counter("esc.test", kind=k, path=v).inc(i + 1)
    text = prometheus_text(reg.snapshot())
    assert "\n\n" not in text  # newlines in values never split a sample line
    seen = {}
    for line in text.splitlines():
        if not line.startswith("esc_test{"):
            continue
        labels = parse_prom_labels(line[line.index("{"):line.rindex("}") + 1])
        seen[labels["kind"]] = labels["path"]
    assert seen == values


def test_parse_prom_labels_single_pass_unescape():
    # "\\n" (escaped backslash, then 'n') must NOT become a newline.
    assert parse_prom_labels(r'{a="x\\ny"}') == {"a": "x\\ny"}
    assert parse_prom_labels(r'{a="x\ny"}') == {"a": "x\ny".replace(
        r"\n", "\n")}


# --------------------------------------------------- histogram reservoir


def test_histogram_reservoir_bounded_with_exact_aggregates(reg):
    """Satellite: a long-running serve histogram stays bounded at the
    reservoir cap while count/sum/min/max remain exact and p50/p99 stay
    representative of the full stream."""
    h = obs.histogram("serve.request", unit="ms")
    n = 50_000
    # Deterministic stream with known quantiles: 0..n-1 shuffled.
    import random as _random

    stream = list(range(n))
    _random.Random(7).shuffle(stream)
    for v in stream:
        h.observe(float(v))
    assert len(h.values) == _HIST_SAMPLE_CAP       # bounded
    assert h.count == n                             # exact
    assert h.sum == float(sum(range(n)))            # exact
    assert (h.min, h.max) == (0.0, float(n - 1))    # exact
    values = sorted(h.values)
    p50 = values[len(values) // 2]
    p99 = values[int(len(values) * 0.99)]
    # A uniform reservoir over U[0, n) keeps quantiles near truth.
    assert abs(p50 - n * 0.50) < n * 0.05
    assert abs(p99 - n * 0.99) < n * 0.05


def test_histogram_reservoir_deterministic_per_series():
    a, b = Registry(), Registry()
    for r in (a, b):
        h = r.histogram("x", unit="ms")
        for v in range(20_000):
            h.observe(float(v))
    assert a.histogram("x", unit="ms").values == \
        b.histogram("x", unit="ms").values  # crc32-seeded RNG, not hash()


# ------------------------------------------------------- trace propagation


def test_trace_carrier_round_trip_and_lenient_parse():
    ctx = obs_trace.mint()
    assert len(ctx.trace_id) == 16 and ctx.span_id is None
    c = obs_trace.carrier(ctx)
    back = obs_trace.from_carrier(c)
    assert back.trace_id == ctx.trace_id and back.span_id is None
    child = obs_trace.TraceContext(ctx.trace_id, obs_trace.new_id())
    c2 = obs_trace.from_carrier(obs_trace.carrier(child))
    assert (c2.trace_id, c2.span_id) == (child.trace_id, child.span_id)
    # Lenient: malformed carriers never fail a request.
    for bad in (None, "x", 7, [], {}, {"id": ""}, {"id": 3},
                {"span": "only"}):
        assert obs_trace.from_carrier(bad) is None
    assert obs_trace.carrier(None) is None  # nothing bound → no field


def test_span_joins_bound_trace_and_parents(reg):
    ctx = obs_trace.TraceContext("f" * 16, "a" * 16)
    with obs_trace.bind(ctx):
        with obs.span("serve.request", op="count"):
            with obs.span("load.partition"):
                pass
    events = {ev["name"]: ev for ev in reg.events()}
    req, part = events["serve.request"], events["load.partition"]
    assert req["trace"] == part["trace"] == "f" * 16
    assert req["pspan"] == "a" * 16          # parents under the carrier span
    assert part["pspan"] == req["span"]      # local nesting keeps the chain
    # Outside the bind, spans stay trace-less (existing local behavior).
    with obs.span("bare"):
        pass
    assert "trace" not in reg.events()[-1]


def test_emit_span_event_feeds_histogram_and_tree(reg):
    sid = reg.emit_span_event(
        "serve.device_dispatch", 4.5, trace_id="t" * 16,
        parent_span_id="p" * 16, rows=8,
    )
    ev = reg.events()[-1]
    assert ev["trace"] == "t" * 16 and ev["span"] == sid
    assert ev["pspan"] == "p" * 16 and ev["attrs"]["rows"] == 8
    hists = {h["name"]: h for h in reg.snapshot()["hists"]}
    assert hists["serve.device_dispatch"]["count"] == 1


def test_concurrent_span_nesting_across_threads(reg):
    """Satellite: span stacks are per-thread and trace binds are
    per-context — concurrent nested spans from many threads never
    corrupt each other's parentage."""
    n_threads, per_thread = 8, 25
    errors: list = []

    def worker(i):
        ctx = obs_trace.TraceContext(f"{i:016x}")
        token = obs_trace.set_current(ctx)
        try:
            for _ in range(per_thread):
                with obs.span("outer", thread=i) as outer:
                    with obs.span("inner") as inner:
                        if inner.trace_id != f"{i:016x}":
                            errors.append((i, "trace", inner.trace_id))
                        if inner.parent_span_id != outer.span_id:
                            errors.append((i, "parent"))
                        if inner.depth != 1:
                            errors.append((i, "depth", inner.depth))
        finally:
            obs_trace.reset(token)

    threads = [
        threading.Thread(target=worker, args=(i,))
        for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    events = reg.events()
    assert len(events) == n_threads * per_thread * 2
    by_span = {ev["span"]: ev for ev in events}
    for ev in events:
        if ev["name"] != "inner":
            continue
        parent = by_span[ev["pspan"]]
        # Every inner's parent is an outer of the SAME thread's trace.
        assert parent["name"] == "outer"
        assert parent["trace"] == ev["trace"]
        assert int(ev["trace"], 16) == parent["attrs"]["thread"]


def test_concurrent_span_nesting_across_asyncio_tasks(reg):
    """Interleaved asyncio tasks share ONE thread: the span stack must
    ride the execution context, not the thread. A thread-local stack
    parents task B's span under whatever span task A still holds open —
    grafting B onto A's trace — and once interleaved exits leak an
    entry, every later span on the loop inherits a stale trace (the
    fabric router's relay spans all collapsed onto one trace id under
    storm load before this was contextvar-backed)."""
    import asyncio

    n_tasks, per_task = 8, 10
    errors: list = []

    async def task(i):
        ctx = obs_trace.TraceContext(f"{i:016x}")
        with obs_trace.bind(ctx):
            for _ in range(per_task):
                with obs.span("relay", task=i) as outer:
                    await asyncio.sleep(0)     # interleave mid-span
                    with obs.span("inner") as inner:
                        await asyncio.sleep(0)
                        if inner.trace_id != f"{i:016x}":
                            errors.append((i, "trace", inner.trace_id))
                        if inner.parent_span_id != outer.span_id:
                            errors.append((i, "parent"))
                        if inner.depth != 1:
                            errors.append((i, "depth", inner.depth))

    async def main():
        await asyncio.gather(*(task(i) for i in range(n_tasks)))
        # The loop thread's stack must be EMPTY afterwards: a serial
        # span opened next joins only its own bound trace.
        with obs_trace.bind(obs_trace.TraceContext("e" * 16)):
            with obs.span("after") as sp:
                assert sp.depth == 0 and sp.trace_id == "e" * 16

    asyncio.run(main())
    assert errors == []
    events = [ev for ev in reg.events() if ev["name"] != "after"]
    assert len(events) == n_tasks * per_task * 2
    by_span = {ev["span"]: ev for ev in events}
    for ev in events:
        if ev["name"] != "inner":
            continue
        parent = by_span[ev["pspan"]]
        assert parent["name"] == "relay"
        assert parent["trace"] == ev["trace"]
        assert int(ev["trace"], 16) == parent["attrs"]["task"]


def test_executor_threads_rebind_trace(reg):
    from spark_bam_tpu.parallel.executor import ParallelConfig, run_partitions

    def fn(i):
        with obs.span("load.partition", i=i):
            pass
        return i

    ctx = obs_trace.TraceContext("c" * 16, "d" * 16)
    with obs_trace.bind(ctx):
        results, _ = run_partitions(
            fn, list(range(6)), ParallelConfig(mode="threads", workers=3)
        )
    assert results == list(range(6))
    parts = [ev for ev in reg.events() if ev["name"] == "load.partition"]
    assert len(parts) == 6
    # Pool threads don't inherit contextvars; the executor rebinds at the
    # seam so every partition span lands in the request's trace.
    assert all(ev["trace"] == "c" * 16 for ev in parts)
    assert all(ev["pspan"] == "d" * 16 for ev in parts)


# --------------------------------------------------------- flight recorder


def test_flight_recorder_ring_bounds_and_dump(tmp_path, monkeypatch):
    rec = flight.FlightRecorder(cap=4)
    for i in range(7):
        rec.record("request", op="count", id=i)
    evs = rec.events()
    assert len(evs) == 4 and [e["id"] for e in evs] == [3, 4, 5, 6]
    path = tmp_path / "post.jsonl"
    rec.dump(path, "crash", extra={"worker": "w0"})
    dumped = flight.read_dump(path)
    assert dumped[0]["e"] == "flight_meta"
    assert dumped[0]["reason"] == "crash" and dumped[0]["worker"] == "w0"
    assert [e["id"] for e in dumped[1:]] == [3, 4, 5, 6]


def test_flight_dump_auto_gated_on_env(tmp_path, monkeypatch):
    monkeypatch.delenv(flight.FLIGHT_DIR_ENV, raising=False)
    assert flight.dump_auto("drain") is None     # no env → no files
    monkeypatch.setenv(flight.FLIGHT_DIR_ENV, str(tmp_path / "fl"))
    flight.record("sigterm", signum=15)
    path = flight.dump_auto("drain", who="w1", extra={"address": "tcp:x:1"})
    assert path is not None and "w1" in path and "drain" in path
    dumped = flight.read_dump(path)
    assert dumped[0]["address"] == "tcp:x:1"
    assert any(e.get("e") == "sigterm" for e in dumped)


# ----------------------------------------------- multi-process trace merge


def test_resolve_metrics_path(tmp_path):
    import os

    assert obs.resolve_metrics_path(None) is None
    assert obs.resolve_metrics_path("") is None
    plain = str(tmp_path / "t.jsonl")
    assert obs.resolve_metrics_path(plain) == plain
    pid = os.getpid()
    assert obs.resolve_metrics_path(
        str(tmp_path / "t-{pid}.jsonl")
    ) == str(tmp_path / f"t-{pid}.jsonl")
    assert obs.resolve_metrics_path(str(tmp_path)) == str(
        tmp_path / f"trace-{pid}.jsonl"
    )


def test_merge_snapshots_fleet_view():
    a, b = Registry(), Registry()
    a.counter("serve.requests").inc(3)
    b.counter("serve.requests").inc(4)
    a.gauge("queue.depth").set(2)
    b.gauge("queue.depth").set(5)
    a.histogram("serve.request", unit="ms").observe(1.0)
    b.histogram("serve.request", unit="ms").observe(9.0)
    m = merge_snapshots([a.snapshot(), b.snapshot()])
    counters = {c["name"]: c["value"] for c in m["counters"]}
    assert counters["serve.requests"] == 7
    g = next(g for g in m["gauges"] if g["name"] == "queue.depth")
    assert g["value"] == 7 and g["max"] == 5
    h = next(h for h in m["hists"] if h["name"] == "serve.request")
    assert (h["count"], h["sum"], h["min"], h["max"]) == (2, 10.0, 1.0, 9.0)
    assert sorted(h["values"]) == [1.0, 9.0]


def _simulated_process_trace(tmp_path, name, trace_id, spans):
    """One registry's worth of spans, exported as its own JSONL file —
    a stand-in for a separate fabric process (same pid, distinct file)."""
    r = Registry()
    for sname, span_id, pspan, ms in spans:
        r.emit_span_event(
            sname, ms, trace_id=trace_id, span_id=span_id,
            parent_span_id=pspan,
        )
    path = tmp_path / name
    obs.export_jsonl(path, reg=r)
    return str(path)


def test_merge_traces_joins_by_trace_id_across_files(tmp_path):
    from spark_bam_tpu.obs.report import merge_traces, render_merged_report

    tid = "ab" * 8
    router = _simulated_process_trace(
        tmp_path, "router.jsonl", tid,
        [("fabric.relay", "r" * 16, None, 30.0)],
    )
    worker = _simulated_process_trace(
        tmp_path, "worker.jsonl", tid,
        [("serve.request", "w" * 16, "r" * 16, 25.0),
         ("serve.device_dispatch", "e" * 16, "w" * 16, 5.0)],
    )
    merged = merge_traces([router, worker])
    assert set(merged["traces"]) == {tid}
    events = merged["traces"][tid]
    assert [e["name"] for e in events] == [
        "fabric.relay", "serve.request", "serve.device_dispatch",
    ]  # sorted by start time, across files
    text = render_merged_report([router, worker])
    assert f"trace {tid} (3 spans):" in text
    tree = [l for l in text.splitlines() if "fabric.relay" in l
            or "serve." in l and "ms" in l]
    # Indentation encodes the cross-process parent chain.
    assert any(l.startswith("fabric.relay") for l in tree)
    assert any(l.startswith("  serve.request") for l in tree)
    assert any(l.startswith("    serve.device_dispatch") for l in tree)


def test_cli_metrics_report_merges_multiple_traces(tmp_path, capsys):
    from spark_bam_tpu.cli.main import main

    tid = "cd" * 8
    a = _simulated_process_trace(
        tmp_path, "a.jsonl", tid, [("fabric.relay", "1" * 16, None, 2.0)]
    )
    b = _simulated_process_trace(
        tmp_path, "b.jsonl", tid,
        [("serve.request", "2" * 16, "1" * 16, 1.5)],
    )
    rc = main(["metrics-report", a, b])
    assert rc == 0
    out = capsys.readouterr().out
    assert "processes: 2" in out
    assert f"trace {tid} (2 spans):" in out
    assert "  serve.request" in out
