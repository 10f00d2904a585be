"""Telemetry subsystem tests: registry semantics, the disabled no-op
contract, export validity (Prometheus text + JSON), cross-rank merging,
the eager timeline writer, and the launcher end-to-end collection path.
"""

import json
import os
import re
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

from horovod_tpu import telemetry
from horovod_tpu.telemetry import aggregate, exporter
from horovod_tpu.telemetry.eager_timeline import (EagerTimelineWriter,
                                                  per_rank_path)
from horovod_tpu.telemetry.registry import MetricsRegistry


@pytest.fixture()
def enabled_telemetry():
    """Collection on, registry clean; restores the disabled default."""
    telemetry.registry().clear()
    telemetry.configure(enabled_flag=True)
    yield telemetry
    telemetry.configure(enabled_flag=False)
    telemetry.registry().clear()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counter_and_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help", {"op": "x"})
    c.inc()
    c.inc(4)
    assert c.value == 5.0
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g", "help")
    g.set(7)
    g.dec(2)
    assert reg.snapshot()["g"]["values"][0]["value"] == 5.0
    # get-or-create returns the same child for the same labels
    assert reg.counter("c_total", "help", {"op": "x"}) is c


def test_histogram_bucket_edges():
    reg = MetricsRegistry()
    h = reg.histogram("h", "help", bounds=(1.0, 10.0))
    # Prometheus le semantics: a value equal to a bound lands IN it.
    h.observe(1.0)     # le=1.0
    h.observe(1.0001)  # le=10.0
    h.observe(10.0)    # le=10.0
    h.observe(11.0)    # +Inf
    b = h.buckets()
    assert b["1.0"] == 1 and b["10.0"] == 2 and b["+Inf"] == 1
    assert h.count == 4
    assert h.sum == pytest.approx(23.0001)
    snap = reg.snapshot()["h"]["values"][0]
    assert snap["count"] == 4


def test_histogram_rejects_unsorted_bounds():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("bad", "help", bounds=(5.0, 1.0))


def test_thread_safety_under_concurrent_increments():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help")
    h = reg.histogram("h", "help", bounds=(0.5,))
    n_threads, n_iters = 8, 2000

    def work():
        for _ in range(n_iters):
            c.inc()
            h.observe(0.25)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_iters
    assert h.count == n_threads * n_iters
    assert h.buckets()["0.5"] == n_threads * n_iters


# ---------------------------------------------------------------------------
# no-op contract
# ---------------------------------------------------------------------------

def test_disabled_path_is_noop():
    telemetry.configure(enabled_flag=False)
    telemetry.registry().clear()
    c = telemetry.counter("never_total", "help")
    assert c is telemetry.NOOP
    assert telemetry.gauge("never_g") is telemetry.NOOP
    assert telemetry.histogram("never_h") is telemetry.NOOP
    # mutators are accepted and record nothing
    c.inc()
    telemetry.NOOP.observe(1.0)
    telemetry.NOOP.set(3.0)
    telemetry.observe_op("allreduce", 0.001, 64)
    assert telemetry.metrics_snapshot() == {}
    assert telemetry.timeline() is None


def test_collective_records_nothing_when_disabled(hvd):
    telemetry.configure(enabled_flag=False)
    telemetry.registry().clear()
    out = hvd.allreduce(np.ones(8, np.float32), average=False,
                        name="telemetry.off")
    assert np.asarray(out).tolist() == [1.0] * 8
    assert telemetry.metrics_snapshot() == {}


# ---------------------------------------------------------------------------
# instrumentation through the public API
# ---------------------------------------------------------------------------

def test_metrics_snapshot_after_local_allreduce(hvd, enabled_telemetry):
    out = hvd.allreduce(np.ones(8, np.float32), average=False,
                        name="telemetry.on")
    assert np.asarray(out).tolist() == [1.0] * 8
    snap = hvd.metrics_snapshot()
    assert aggregate.counter_total(
        snap, "hvd_eager_ops_total", {"op": "allreduce"}) == 1
    assert aggregate.counter_total(
        snap, "hvd_eager_bytes_total", {"op": "allreduce"}) == 32
    lat = snap["hvd_eager_op_seconds"]["values"][0]
    assert lat["count"] == 1 and lat["sum"] > 0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [0-9.eE+-]+(?:inf)?$")


def test_prometheus_render_is_valid(enabled_telemetry):
    telemetry.counter("req_total", "requests", op="allreduce").inc(3)
    telemetry.histogram("lat_seconds", "latency",
                        bounds=(0.001, 1.0)).observe(0.5)
    text = telemetry.render_prometheus()
    lines = text.strip().splitlines()
    for line in lines:
        if line.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*", line)
        else:
            assert _PROM_SAMPLE.match(line), f"bad sample line: {line!r}"
    # histogram buckets are cumulative and end at +Inf == count
    buckets = [l for l in lines if l.startswith("lat_seconds_bucket")]
    counts = [int(l.rsplit(" ", 1)[1]) for l in buckets]
    assert counts == sorted(counts)
    assert 'le="+Inf"' in buckets[-1]
    assert counts[-1] == 1
    assert any(l.startswith("lat_seconds_count 1") for l in lines)


def test_http_server_serves_prometheus_and_json(enabled_telemetry):
    telemetry.counter("served_total", "help").inc()
    server = exporter.start_http_server(
        0, telemetry.render_prometheus, telemetry.metrics_snapshot,
        bind="127.0.0.1")
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "served_total 1" in body
        js = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=5).read())
        assert js["schema"] == "horovod_tpu.metrics.v1"
        assert js["metrics"]["served_total"]["values"][0]["value"] == 1.0
    finally:
        server.shutdown()


def test_write_json_document(tmp_path, enabled_telemetry):
    telemetry.counter("dumped_total", "help").inc(2)
    path = str(tmp_path / "m.json")
    exporter.write_json(path, telemetry.metrics_snapshot)
    doc = json.loads(open(path).read())
    assert doc["schema"] == "horovod_tpu.metrics.v1"
    assert doc["metrics"]["dumped_total"]["values"][0]["value"] == 2.0
    assert not [p for p in os.listdir(tmp_path) if ".tmp." in p]


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------

def _snap_with(counter_val, hist_obs, gauge_val):
    reg = MetricsRegistry()
    reg.counter("ops_total", "h", {"op": "allreduce"}).inc(counter_val)
    h = reg.histogram("lat", "h", bounds=(1.0, 10.0))
    for v in hist_obs:
        h.observe(v)
    reg.gauge("depth", "h").set(gauge_val)
    return reg.snapshot()


def test_merge_snapshots_counters_histograms_gauges():
    merged = aggregate.merge_snapshots({
        "0": _snap_with(3, [0.5, 20.0], 2.0),
        "1": _snap_with(4, [5.0], 6.0),
    })
    assert aggregate.counter_total(merged, "ops_total") == 7
    lat = merged["lat"]["values"][0]
    assert lat["count"] == 3
    assert lat["buckets"]["1.0"] == 1
    assert lat["buckets"]["10.0"] == 1
    assert lat["buckets"]["+Inf"] == 1
    depth = merged["depth"]["values"][0]
    assert depth["min"] == 2.0 and depth["max"] == 6.0
    assert depth["mean"] == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# eager timeline
# ---------------------------------------------------------------------------

def test_eager_timeline_writer_emits_chrome_trace(tmp_path):
    path = str(tmp_path / "tl.json")
    w = EagerTimelineWriter(path, rank=0)
    w.record_op("grad.0", "allreduce", 1.0, 1.1, 1.3, nbytes=64)
    w.record_op("grad.1", "allgather", 2.0, 2.0, 2.0, nbytes=16)
    w.close()
    events = json.loads(open(path).read())
    names = [e["name"] for e in events]
    assert "SUBMIT_ALLREDUCE" in names and "WAIT_ALLREDUCE" in names
    assert "SUBMIT_ALLGATHER" in names
    assert names.count("FINISH") == 2
    assert names[-1] == "SHUTDOWN"
    # per-tensor rows announced via thread_name metadata
    tids = {e["args"]["name"]: e["tid"] for e in events
            if e["name"] == "thread_name"}
    assert set(tids) == {"grad.0", "grad.1"}
    sub = next(e for e in events if e["name"] == "SUBMIT_ALLREDUCE")
    assert sub["ph"] == "X" and sub["dur"] > 0
    assert sub["tid"] == tids["grad.0"]
    assert sub["args"]["bytes"] == 64


def test_eager_timeline_truncated_file_still_parses(tmp_path):
    """A crashed rank never reaches close(); the viewer dialect (one
    event per line, trailing commas) must stay recoverable."""
    path = str(tmp_path / "tl.json")
    w = EagerTimelineWriter(path, rank=1)
    w.record_op("t", "broadcast", 0.0, 0.1, 0.2)
    w._file.flush()
    raw = open(path).read()
    body = raw.rstrip().rstrip(",")
    events = json.loads(body + "]")
    assert any(e["name"] == "SUBMIT_BROADCAST" for e in events)
    w.close()


def test_two_rank_timeline_merge_is_skew_corrected(tmp_path):
    """Two ranks' eager timelines merge onto the launcher clock: rank
    1's events shift by its measured offset, and a truncated file (the
    rank crashed before ``close()``) still contributes its events."""
    from horovod_tpu.telemetry import trace_merge
    p0 = str(tmp_path / "tl.rank0.json")
    p1 = str(tmp_path / "tl.rank1.json")
    w0 = EagerTimelineWriter(p0, rank=0)
    w0.record_op("g", "allreduce", w0._epoch + 1.0, w0._epoch + 1.1,
                 w0._epoch + 1.3, nbytes=64)
    w0.close()
    w1 = EagerTimelineWriter(p1, rank=1)
    w1.record_op("g", "allreduce", w1._epoch + 1.0, w1._epoch + 1.1,
                 w1._epoch + 1.3, nbytes=64)
    w1._file.flush()  # no close(): truncated tail, tolerant loader path
    merged = trace_merge.merge_chrome_traces(
        [p0, p1], offsets={1: 0.25})
    subs = [e for e in merged if e["name"] == "SUBMIT_ALLREDUCE"]
    assert {e["pid"] for e in subs} == {0, 1}  # pid stays the rank
    ts = {e["pid"]: e["ts"] for e in subs}
    assert ts[1] - ts[0] == 250000  # rank 1 moved onto the launcher clock
    body = [e for e in merged if e.get("ph") != "M"]
    assert body == sorted(body, key=lambda e: e["ts"])


def test_per_rank_path(monkeypatch):
    monkeypatch.setenv("HOROVOD_SIZE", "4")
    monkeypatch.setenv("HOROVOD_RANK", "2")
    assert per_rank_path("/tmp/tl.json") == "/tmp/tl.rank2.json"
    assert per_rank_path("/tmp/tl") == "/tmp/tl.rank2.json"
    # an explicit rank marker is left alone
    assert per_rank_path("/tmp/tl.rank2.json") == "/tmp/tl.rank2.json"
    monkeypatch.setenv("HOROVOD_SIZE", "1")
    assert per_rank_path("/tmp/tl.json") == "/tmp/tl.json"


def test_timeline_records_local_allreduce(hvd, tmp_path, monkeypatch):
    path = str(tmp_path / "tl.json")
    w = EagerTimelineWriter(path, rank=0)
    monkeypatch.setattr(telemetry, "_timeline", w)
    out = hvd.allreduce(np.ones(4, np.float32), average=False,
                        name="tl.grad")
    assert np.asarray(out).tolist() == [1.0] * 4
    w.close()
    events = json.loads(open(path).read())
    rows = [e for e in events if e.get("name") == "SUBMIT_ALLREDUCE"]
    assert len(rows) == 1
    assert rows[0]["args"]["bytes"] == 16


# ---------------------------------------------------------------------------
# satellites: TRACE level, print_profile guard
# ---------------------------------------------------------------------------

def test_trace_log_level():
    import logging as _logging

    from horovod_tpu.utils import logging as hvd_logging
    assert hvd_logging.TRACE == 5 < _logging.DEBUG
    assert _logging.getLevelName(hvd_logging.TRACE) == "TRACE"
    assert hvd_logging._LEVELS["trace"] == hvd_logging.TRACE
    log = hvd_logging.get_logger("test_trace")
    records = []

    class _Capture(_logging.Handler):
        def emit(self, record):
            records.append(record)

    handler = _Capture(level=hvd_logging.TRACE)
    log.addHandler(handler)
    old_level = log.level
    try:
        log.setLevel(hvd_logging.TRACE)
        log.trace("fire %d", 1)
        log.setLevel(_logging.DEBUG)
        log.trace("suppressed")
    finally:
        log.setLevel(old_level)
        log.removeHandler(handler)
    assert [r.getMessage() for r in records] == ["fire 1"]
    assert records[0].levelname == "TRACE"


# ---------------------------------------------------------------------------
# launcher end-to-end (the CI telemetry gate, as a test)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_launcher_collects_and_merges_metrics(tmp_path):
    summary = str(tmp_path / "metrics.json")
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "HOROVOD_METRICS_FILE": summary,
                "PYTHONPATH": os.getcwd()})
    env.pop("HOROVOD_EAGER_TIMELINE", None)
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         sys.executable, "tests/distributed/metrics_workload_np2.py"],
        env=env, capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert proc.stdout.count("METRICS_WORKLOAD_OK") == 2

    sys.path.insert(0, os.path.join(os.getcwd(), "tools"))
    try:
        import check_metrics
    finally:
        sys.path.pop(0)
    totals = check_metrics.check(summary, world_size=2)
    assert totals["allreduce_ops"] >= 10

    doc = json.load(open(summary))
    assert doc["schema"] == "horovod_tpu.metrics.summary.v1"
    assert set(doc["ranks"]) == {"0", "1"}
    # rank-attributed latency histograms survive the merge
    merged_lat = doc["merged"]["hvd_eager_op_seconds"]["values"]
    assert any(v["count"] > 0 for v in merged_lat)
