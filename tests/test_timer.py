"""Observability helpers (SURVEY §5 tracing/heartbeat subsystem)."""

import logging
import time

from spark_bam_tpu.utils.timer import Timer, heartbeat


def test_timer_measures_and_echoes():
    lines = []
    with Timer("stage", echo=lines.append) as t:
        time.sleep(0.02)
    assert isinstance(t.seconds, float) and isinstance(t.ms, float)
    assert t.ms >= 15
    assert lines == [f"stage: {t.ms:.3f}ms"]

    # No name ⇒ silent even with an echo sink.
    lines.clear()
    with Timer(echo=lines.append):
        pass
    assert lines == []


def test_timer_sub_millisecond_not_truncated():
    # The old int(ms) truncation erased sub-ms stages; float ms keeps them.
    with Timer("quick") as t:
        time.sleep(0.001)
    assert 0 < t.ms < 1000
    assert t.ms == t.seconds * 1e3


def test_named_timer_feeds_registry():
    from spark_bam_tpu import obs

    obs.shutdown()
    reg = obs.configure()
    try:
        with Timer("stagex"):
            pass
        hists = {h["name"]: h for h in reg.snapshot()["hists"]}
        assert hists["timer.stagex"]["count"] == 1
    finally:
        obs.shutdown()


def test_heartbeat_rate_limits(caplog):
    with caplog.at_level(logging.INFO, logger="spark_bam_tpu.utils.timer"):
        with heartbeat("indexing", interval_seconds=0.05) as beat:
            beat("p0")          # within the first interval: suppressed
            time.sleep(0.06)
            beat("p1")          # logged
            beat("p2")          # suppressed again
    messages = [r.getMessage() for r in caplog.records]
    assert messages == ["indexing: p1"]


def test_heartbeat_progress_shape_and_rate(caplog):
    import logging

    from spark_bam_tpu.utils.timer import heartbeat_progress

    with caplog.at_level(logging.INFO):
        with heartbeat_progress("t", unit="window", interval_seconds=0) as p:
            p(3, 100, 200)
    assert "t: window 3, 100/200 positions" in caplog.text

    # Rate limit: a long interval suppresses the very first beat too.
    with caplog.at_level(logging.INFO):
        caplog.clear()
        with heartbeat_progress("u", interval_seconds=3600) as p:
            p(1, 1, 2)
    assert "u:" not in caplog.text
