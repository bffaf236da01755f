"""The sharded split service: batching, admission, deadlines, warm tiers.

Everything runs on the conftest 8-device virtual CPU mesh. The serve
step is compiled once per process through the ``mesh_steps`` registry,
so per-test service instances are cheap after the first test warms it.
"""

import threading
import time

import numpy as np
import pytest

from spark_bam_tpu import obs
from spark_bam_tpu.benchmarks.synth import synthetic_fixture
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.serve import (
    Overloaded,
    ProtocolError,
    ServeAddress,
    ServeClient,
    ServeClientError,
    ServeConfig,
    ServerThread,
    SplitService,
    decode_request,
    encode,
    error_response,
    ok_response,
)

pytestmark = pytest.mark.serve

#: Small windows so the 2500-read fixture spans many rows per request —
#: the coalescing tests need multiple rows in flight.
SERVE_SPEC = "window=64KB,halo=8KB,batch=8,tick=5,workers=4"


@pytest.fixture(scope="module")
def bam_path(tmp_path_factory):
    return str(synthetic_fixture(tmp_path_factory.mktemp("serve_fixture")))


@pytest.fixture()
def service(bam_path):
    svc = SplitService(Config(serve=SERVE_SPEC))
    yield svc
    svc.close()


def _payload(resp: dict) -> dict:
    return {k: v for k, v in resp.items() if k != "id"}


# ---------------------------------------------------------------- config


def test_serve_config_parse_knobs():
    cfg = ServeConfig.parse("window=128KB,halo=16KB,batch=16,tick=1.5,"
                            "planq=8,scanq=4,workers=3,cache=64MB")
    assert cfg.window == 128 << 10
    assert cfg.halo == 16 << 10
    assert cfg.batch_rows == 16
    assert cfg.tick_ms == 1.5
    assert cfg.plan_queue == 8
    assert cfg.scan_queue == 4
    assert cfg.workers == 3
    assert cfg.flat_cache == 64 << 20


def test_serve_config_rejects_bad_specs():
    with pytest.raises(ValueError):
        ServeConfig.parse("nope=1")
    with pytest.raises(ValueError):
        ServeConfig.parse("batch=0")
    with pytest.raises(ValueError):
        ServeConfig.parse("window=8KB,halo=8KB")  # halo must be < window


def test_config_carries_serve_spec():
    cfg = Config(serve="batch=32")
    assert cfg.serve_config.batch_rows == 32
    assert Config().serve_config == ServeConfig()


# -------------------------------------------------------------- protocol


def test_protocol_roundtrip():
    req = decode_request(b'{"op": "ping", "id": 7}\n')
    assert req["op"] == "ping"
    ok = ok_response(req, pong=True)
    assert ok["ok"] and ok["id"] == 7
    err = error_response(req, "Overloaded", "full", retry_after_ms=12.5)
    assert not err["ok"] and err["retry_after_ms"] == 12.5
    assert encode(ok).endswith(b"\n")


def test_protocol_rejects_garbage():
    with pytest.raises(ProtocolError):
        decode_request(b"not json\n")
    with pytest.raises(ProtocolError):
        decode_request(b'["not", "a", "dict"]\n')
    with pytest.raises(ProtocolError):
        decode_request(b'{"op": "unknown"}\n')


def test_serve_address_parsing():
    a = ServeAddress("unix:/tmp/x.sock")
    assert a.kind == "unix" and a.path == "/tmp/x.sock"
    t = ServeAddress("tcp:0.0.0.0:9000")
    assert (t.kind, t.host, t.port) == ("tcp", "0.0.0.0", 9000)
    bare = ServeAddress("127.0.0.1:0")
    assert (bare.host, bare.port) == ("127.0.0.1", 0)
    with pytest.raises(ValueError):
        ServeAddress("unix:")
    with pytest.raises(ValueError):
        ServeAddress("tcp:nowhere")


# ------------------------------------------------------------- coalescing


def test_batched_counts_byte_identical_to_sequential(service, bam_path):
    """Concurrent requests coalesced into shared device ticks must return
    byte-for-byte the responses the same requests get one at a time."""
    from spark_bam_tpu.tpu.stream_check import StreamChecker

    truth = StreamChecker(bam_path, Config()).count_reads()

    # Sequential: one request fully served before the next is submitted.
    seq = [
        service.submit({"op": "count", "path": bam_path}).result(timeout=120)
        for _ in range(3)
    ]

    # Batched: hold the batcher so every row from every request queues,
    # then release — rows from different requests share dispatch ticks.
    service.batcher.pause()
    futs = [
        service.submit({"op": "count", "path": bam_path}) for _ in range(6)
    ]
    time.sleep(0.3)  # let the worker pool expand rows into the queue
    service.batcher.resume()
    batched = [f.result(timeout=120) for f in futs]

    assert seq[0]["ok"] and seq[0]["count"] == truth
    for resp in seq[1:] + batched:
        assert encode(_payload(resp)) == encode(_payload(seq[0]))
    # The coalescer actually batched: some dispatch carried >1 row.
    assert any(size > 1 for size in service.batcher.batch_sizes)


def test_a_tick_reports_its_rows_survivors_and_lanes(tmp_path):
    """The served step's two new columns reach the funnel's counters and one
    ``serve.tick_lanes`` observation a tick: on a generated short-read file
    (the benchmark's kind) a row runs whole blocks of ``lane_block`` that
    hold its stage-0 survivors, far fewer than its capacity."""
    import json
    from pathlib import Path

    from bench.generators import shortread
    from spark_bam_tpu.tpu.checker import lane_block, lane_capacity

    root = Path(__file__).resolve().parents[1]
    params = json.loads(
        (root / "bench" / "configs" / "wgs-short.json").read_text())["params"]
    path = tmp_path / "short.bam"
    index = shortread.generate(params, 7, 600_000, path)
    obs.configure()
    try:
        svc = SplitService(Config(serve=SERVE_SPEC))
        try:
            resp = svc.submit(
                {"op": "count", "path": str(path)}).result(timeout=120)
        finally:
            svc.close()
        snap = obs.registry().snapshot()
    finally:
        obs.shutdown()
    assert resp["ok"] and resp["count"] == len(index["record_starts"])
    counters = {c["name"]: c["value"] for c in snap["counters"]}
    hists = {h["name"]: h for h in snap["hists"]}
    window = ServeConfig.parse(SERVE_SPEC).window
    rows = hists["serve.batch_rows"]["sum"]
    survivors, lanes = counters["funnel.survivors"], counters["funnel.lanes"]
    # Every owned record start is a stage-0 survivor of its row (the halo's
    # are counted again in the next row).
    assert resp["count"] <= survivors <= lanes
    assert lanes % lane_block(window) == 0
    assert lanes < rows * lane_capacity(window)
    assert hists["serve.tick_lanes"]["count"] == counters["serve.batches"]
    assert hists["serve.tick_lanes"]["sum"] == lanes


def test_fleet_coalesces_across_files(service, bam_path, tmp_path):
    """Rows from different files batch in one tick (per-row contig
    dictionaries); the fleet verdict equals per-file counts."""
    second = str(synthetic_fixture(tmp_path, reads=700))
    single = {
        p: service.submit({"op": "count", "path": p}).result(timeout=120)
        for p in (bam_path, second)
    }
    fleet = service.submit(
        {"op": "fleet", "paths": [bam_path, second]}
    ).result(timeout=120)
    assert fleet["ok"]
    assert fleet["paths"] == {p: r["count"] for p, r in single.items()}
    assert fleet["total"] == sum(r["count"] for r in single.values())


# -------------------------------------------------------------- admission


def test_admission_rejects_over_limit_with_overloaded(bam_path):
    svc = SplitService(Config(serve=SERVE_SPEC + ",scanq=1"))
    try:
        svc.batcher.pause()
        first = svc.submit({"op": "count", "path": bam_path})
        time.sleep(0.1)  # the one scan slot is held by ``first``
        with pytest.raises(Overloaded) as exc:
            svc.submit({"op": "count", "path": bam_path})
        assert exc.value.klass == "scan"
        assert exc.value.retry_after_ms >= 0
        # ping/stats bypass admission even at the limit.
        assert svc.submit({"op": "ping"}).result(timeout=10)["pong"]
        svc.batcher.resume()
        assert first.result(timeout=120)["ok"]
        # The slot freed: the same request is admitted now.
        again = svc.submit({"op": "count", "path": bam_path})
        assert again.result(timeout=120)["ok"]
    finally:
        svc.close()


@pytest.mark.slow
def test_deadline_expiry_sheds_queued_work(bam_path):
    reg = obs.configure()
    svc = SplitService(Config(serve=SERVE_SPEC))
    try:
        svc.batcher.pause()
        fut = svc.submit(
            {"op": "count", "path": bam_path, "deadline_ms": 30}
        )
        time.sleep(0.3)  # deadline passes while rows sit in the queue
        svc.batcher.resume()
        resp = fut.result(timeout=120)
        assert not resp["ok"]
        assert resp["error"] == "DeadlineExceeded"
        shed = {
            c["name"]: c["value"]
            for c in reg.snapshot()["counters"] if not c["labels"]
        }.get("serve.shed", 0)
        assert shed >= 1
        # The service survives shedding: a deadline-free retry succeeds.
        assert svc.submit(
            {"op": "count", "path": bam_path}
        ).result(timeout=120)["ok"]
    finally:
        svc.close()
        obs.shutdown()


# -------------------------------------------------------------- warm tiers


def test_warm_plan_request_does_zero_split_resolutions(
    bam_path, tmp_path, monkeypatch
):
    """Second plan for the same file must come entirely from the shared
    ``.sbi`` index tier — zero ``load.split_resolutions``."""
    from spark_bam_tpu.sbi.store import reset_shared_store

    monkeypatch.setenv("SPARK_BAM_CACHE_DIR", str(tmp_path))
    reset_shared_store()
    svc = SplitService(Config(serve=SERVE_SPEC, cache="readwrite"))
    try:
        req = {"op": "plan", "path": bam_path, "split_size": 256 << 10}
        cold = svc.submit(dict(req)).result(timeout=120)
        assert cold["ok"] and len(cold["splits"]) >= 2

        reg = obs.configure()
        try:
            warm = svc.submit(dict(req)).result(timeout=120)
            counters = {
                c["name"]: c["value"]
                for c in reg.snapshot()["counters"] if not c["labels"]
            }
        finally:
            obs.shutdown()
        assert _payload(warm) == _payload(cold)
        assert counters.get("load.split_resolutions", 0) == 0
    finally:
        svc.close()
        reset_shared_store()


def test_file_state_is_resident_and_stat_fresh(service, bam_path):
    first = service.file_state(bam_path)
    assert service.file_state(bam_path) is first  # warm hit, no rebuild
    assert service.stats()["files_resident"] == 1
    starts = first.starts(service.config)
    assert len(starts) == service.submit(
        {"op": "record_starts", "path": bam_path}
    ).result(timeout=120)["count"]
    assert np.all(np.diff(starts) > 0)


# ----------------------------------------------------------------- server


def test_tcp_server_roundtrip(service, bam_path):
    with ServerThread(service) as srv:
        with ServeClient(srv.address) as c:
            assert c.request("ping")["devices"] == 8
            count = c.request("count", path=bam_path)["count"]
            assert count == c.request("count", path=bam_path)["count"]
            stats = c.request("stats")
            assert stats["batch_rows"] == 8 and stats["served"] >= 2
            starts = c.request("record_starts", path=bam_path, limit=5)
            assert starts["count"] == count and len(starts["vpos"]) == 5
            with pytest.raises(ServeClientError) as exc:
                c.request("count", path=bam_path + ".missing")
            assert exc.value.error == "NotFound"
            with pytest.raises(ServeClientError) as exc:
                c.request("bogus-op")
            assert exc.value.error == "ProtocolError"


def test_unix_server_roundtrip(service, bam_path, tmp_path):
    with ServerThread(service, f"unix:{tmp_path}/serve.sock") as srv:
        with ServeClient(srv.address) as c:
            assert c.request("count", path=bam_path)["count"] > 0


# ----------------------------------------------------- admin ops (fabric)


def test_stats_reports_percentiles_and_knobs(service, bam_path):
    for _ in range(3):
        assert service.submit(
            {"op": "count", "path": bam_path}
        ).result(timeout=120)["ok"]
    stats = service.stats()
    assert stats["latency_p50_ms"] is not None
    assert stats["latency_p99_ms"] >= stats["latency_p50_ms"]
    per_op = stats["ops"]["count"]
    assert per_op["p50_ms"] is not None
    assert per_op["p99_ms"] >= per_op["p50_ms"]
    assert stats["draining"] is False
    assert stats["queue_depth"] == 0
    assert stats["limits"] == {"plan": 64, "scan": 64, "control": 8}
    assert stats["tick_ms"] == pytest.approx(5.0)


def test_tune_op_applies_rounds_and_rejects(service):
    r = service.submit(
        {"op": "tune", "batch_rows": 3, "tick_ms": 2.5, "scan_queue": 16}
    ).result(timeout=10)
    # batch_rows rounds UP to the 8-device mesh multiple: the dispatch
    # shape set stays bounded.
    assert r["applied"]["batch_rows"] == 8
    assert r["applied"]["tick_ms"] == 2.5
    assert r["applied"]["scan_queue"] == 16
    assert service.batcher.batch_rows == 8
    assert service.gate.limits["scan"] == 16
    empty = service.submit({"op": "tune"}).result(timeout=10)
    assert not empty["ok"] and empty["error"] == "ProtocolError"
    bad = service.submit({"op": "tune", "scan_queue": 0}).result(timeout=10)
    assert not bad["ok"] and bad["error"] == "ProtocolError"


def test_drain_refuses_new_work_keeps_inflight(bam_path):
    svc = SplitService(Config(serve=SERVE_SPEC))
    try:
        warm = svc.submit({"op": "count", "path": bam_path})
        expected = warm.result(timeout=120)["count"]
        svc.batcher.pause()
        held = svc.submit({"op": "count", "path": bam_path})
        time.sleep(0.1)
        drained = svc.submit({"op": "drain"}).result(timeout=10)
        assert drained["draining"] is True
        assert drained["inflight"]["scan"] == 1
        refused = svc.submit({"op": "count", "path": bam_path})
        assert refused.result(timeout=10)["error"] == "Draining"
        # ping/stats stay answerable on a draining worker.
        assert svc.submit({"op": "ping"}).result(timeout=10)["pong"]
        assert svc.submit({"op": "stats"}).result(timeout=10)["draining"]
        svc.batcher.resume()
        # The queued request finishes unshed — drain sheds nothing.
        assert held.result(timeout=120)["count"] == expected
    finally:
        svc.close()


def test_client_retries_overloaded_until_slot_frees(bam_path):
    """Satellite regression for the client retry loop: with ``scanq=1``
    a held slot must surface Overloaded (+hint) to a policy-less client
    and read as latency, not failure, to a client with a policy."""
    from spark_bam_tpu.core.faults import FaultPolicy

    svc = SplitService(Config(serve=SERVE_SPEC + ",scanq=1"))
    try:
        with ServerThread(svc) as srv:
            with ServeClient(srv.address) as c:   # warm: compile + small hint
                expected = c.request("count", path=bam_path)["count"]
            svc.batcher.pause()
            held = svc.submit({"op": "count", "path": bam_path})
            time.sleep(0.1)
            with ServeClient(srv.address, policy=None) as c:
                with pytest.raises(ServeClientError) as exc:
                    c.request("count", path=bam_path)
            assert exc.value.error == "Overloaded"
            assert exc.value.retry_after_ms >= 0
            timer = threading.Timer(0.3, svc.batcher.resume)
            timer.start()
            try:
                pol = FaultPolicy(max_retries=8, backoff_base=0.05,
                                  backoff_max=0.25, jitter=0.5)
                with ServeClient(srv.address, policy=pol) as c:
                    assert c.request("count", path=bam_path)["count"] == expected
            finally:
                timer.join()
            assert held.result(timeout=120)["count"] == expected
    finally:
        svc.close()
