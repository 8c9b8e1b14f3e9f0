"""Aux subsystems: metrics server, threshold monitor, structured logging,
entropy (SURVEY.md §5.1/§5.3/§5.5)."""

import io
import json
import time
import urllib.request

from drand_tpu import log as dlog
from drand_tpu.entropy import ScriptReader, get_random
from drand_tpu.metrics import (MetricsServer, ThresholdMonitor,
                               beacon_discrepancy_latency, last_beacon_round,
                               scrape, scrape_all)


def test_metrics_registries_and_series():
    last_beacon_round.labels("auxtest").set(42)
    beacon_discrepancy_latency.labels("auxtest").set(12.5)
    text = scrape("group").decode()
    assert 'last_beacon_round{beacon_id="auxtest"} 42.0' in text
    assert "beacon_discrepancy_latency" in text
    assert scrape_all()          # all four registries concatenate


def test_metrics_server_routes():
    srv = MetricsServer(0)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "last_beacon_round" in body
        body = urllib.request.urlopen(f"{base}/metrics/group").read().decode()
        assert "group_size" in body
        assert b"GC run" in urllib.request.urlopen(f"{base}/debug/gc").read()
        # pprof-equivalent stack dump names this very thread
        dump = urllib.request.urlopen(f"{base}/debug/pprof").read().decode()
        assert "Thread" in dump
    finally:
        srv.stop()


def test_threshold_monitor_escalation():
    stream = io.StringIO()
    dlog.configure(level="debug", json_output=True, stream=stream)
    try:
        log = dlog.Logger("thr-test")
        mon = ThresholdMonitor("auxtest", log, threshold=2, period=0.1)
        mon.start()
        mon.report_failure("10.0.0.1:1")
        mon.report_failure("10.0.0.2:1")
        time.sleep(0.4)
        mon.stop()
        events = [json.loads(line) for line in
                  stream.getvalue().splitlines() if line.strip()]
        errors = [e for e in events if e["level"] == "ERROR"]
        assert errors and errors[0]["failures"] == 2
    finally:
        dlog.configure()


def test_structured_logger_named_fields():
    stream = io.StringIO()
    dlog.configure(level="info", json_output=True, stream=stream)
    try:
        log = dlog.Logger("daemon").named("default").with_fields(index=3)
        log.info("beacon stored", round=7)
        rec = json.loads(stream.getvalue())
        assert rec["logger"] == "daemon.default"
        assert rec["index"] == 3 and rec["round"] == 7
        assert rec["msg"] == "beacon stored"
    finally:
        dlog.configure()


def test_rate_limited_info():
    stream = io.StringIO()
    dlog.configure(level="info", json_output=True, stream=stream)
    try:
        log = dlog.Logger("bulk")
        for _ in range(dlog.LOGS_TO_SKIP * 2):
            log.rate_limited_info("syncing")
        lines = [l for l in stream.getvalue().splitlines() if l.strip()]
        assert len(lines) == 2       # one per LOGS_TO_SKIP window
    finally:
        dlog.configure()


def test_entropy_sources(tmp_path):
    assert len(get_random(None, 32)) == 32
    script = tmp_path / "entropy.sh"
    script.write_text("#!/bin/sh\nprintf 'abcdefgh'\n")
    script.chmod(0o755)
    reader = ScriptReader(str(script))
    out = reader.read(20)
    assert out == (b"abcdefgh" * 3)[:20]
    # failing script falls back to the CSPRNG without raising
    bad = tmp_path / "bad.sh"
    bad.write_text("#!/bin/sh\nexit 1\n")
    bad.chmod(0o755)
    assert len(get_random(ScriptReader(str(bad)), 16)) == 16
