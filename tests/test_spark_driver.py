"""Spark driver-service protocol tests, pyspark-free.

Reference equivalent: test/test_spark.py (happy run, task timeout) — but
the reference needs a local Spark session; our coordination layer
(`horovod_tpu.spark.driver`) is deliberately pyspark-independent, so
threads stand in for Spark tasks and the full register → assign →
run-fn → report protocol is exercised for real, including the
HMAC-authenticated RPC (reference network.py:50-84).
"""

import os
import threading

import pytest

from horovod_tpu.runner import rpc
from horovod_tpu.spark.driver import JobDriver, run_task

KEY = b"k" * 32


@pytest.fixture(autouse=True)
def _restore_environ():
    """run_task sets the assigned HOROVOD_* env in os.environ — correct in
    a real Spark executor (its own process), but in this threaded
    simulation it would leak rank env into later tests in the same
    process."""
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


def test_rpc_roundtrip_and_auth():
    server = rpc.RpcServer(KEY, lambda req: {"echo": req["x"] * 2})
    try:
        out = rpc.rpc_call("127.0.0.1", server.port, {"x": 21}, KEY)
        assert out == {"echo": 42}
        # Wrong key: the server drops the request without a reply; the
        # client sees a closed connection, never a response.
        with pytest.raises((ConnectionError, OSError)):
            rpc.rpc_call("127.0.0.1", server.port, {"x": 1}, b"wrong" * 8,
                         timeout=5)
    finally:
        server.shutdown()


def test_driver_assigns_ranks_and_collects_results():
    num = 4
    driver = JobDriver(num, KEY, base_env={"EXTRA": "1"})
    try:
        results = [None] * num
        errors = []

        def fn():
            # Runs with the assigned env in place.
            return (int(os.environ["HOROVOD_RANK"]),
                    os.environ["HOROVOD_RENDEZVOUS_ADDR"],
                    os.environ["EXTRA"])

        def task(i):
            try:
                results[i] = run_task(i, "127.0.0.1", driver.port, KEY, fn)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        # NOTE: os.environ is process-global; tasks race on it in this
        # threaded simulation.  fn reads immediately after update, and the
        # asserts below only rely on per-task return order via the driver.
        threads = [threading.Thread(target=task, args=(i,))
                   for i in range(num)]
        for t in threads:
            t.start()
        ranked = driver.wait_for_results(timeout=60)
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors
        # Driver returns results in rank order; every rank present once.
        assert sorted(r[0] for r in ranked) == list(range(num))
        assert all(r[2] == "1" for r in ranked)
        # All tasks agree on the rendezvous address (rank 0's host).
        assert len({r[1] for r in ranked}) == 1
    finally:
        driver.shutdown()


def test_driver_surfaces_task_failure():
    driver = JobDriver(2, KEY)
    try:
        def ok():
            return "fine"

        def boom():
            raise ValueError("exploded")

        t0 = threading.Thread(
            target=lambda: run_task(0, "127.0.0.1", driver.port, KEY, ok))
        t0.start()

        def failing():
            with pytest.raises(ValueError):
                run_task(1, "127.0.0.1", driver.port, KEY, boom)

        t1 = threading.Thread(target=failing)
        t1.start()
        with pytest.raises(RuntimeError, match="exploded"):
            driver.wait_for_results(timeout=60)
        t0.join(timeout=30)
        t1.join(timeout=30)
    finally:
        driver.shutdown()


def test_driver_timeout_lists_missing_tasks():
    driver = JobDriver(2, KEY)
    try:
        def lone_task():
            try:
                run_task(0, "127.0.0.1", driver.port, KEY, lambda: None,
                         start_timeout=5)
            except Exception:  # noqa: BLE001 — expected: driver gone
                pass

        threading.Thread(target=lone_task).start()
        # Task 1 never arrives: registration stays incomplete, env never
        # assigned, so task 0 blocks in its env poll and the driver's
        # deadline fires with the missing tasks listed.
        with pytest.raises(TimeoutError, match=r"\[0, 1\]|did not report"):
            driver.wait_for_results(timeout=2)
    finally:
        driver.shutdown()


def test_keepalive_monitor():
    mon = rpc.KeepaliveMonitor(timeout=0.05)
    mon.ping("a")
    assert mon.dead_tasks() == []
    import time
    time.sleep(0.1)
    assert mon.dead_tasks() == ["a"]


def test_spark_run_requires_pyspark():
    pytest.importorskip  # keep flake quiet
    try:
        import pyspark  # noqa: F401
        pytest.skip("pyspark installed; gating not testable")
    except ImportError:
        pass
    import horovod_tpu.spark as hs
    with pytest.raises(ImportError, match="pyspark"):
        hs.run(lambda: None, num_proc=1)


def test_keepalive_monitor_injected_clock_and_forget():
    """Clock injection steps time instead of sleeping; forget() removes
    a finished task from liveness tracking entirely."""
    now = [0.0]
    mon = rpc.KeepaliveMonitor(timeout=5.0, clock=lambda: now[0])
    mon.ping("a")
    mon.ping("b")
    now[0] = 4.0
    assert mon.dead_tasks() == []
    mon.ping("b")
    now[0] = 7.0
    assert mon.dead_tasks() == ["a"]     # b pinged at t=4
    mon.forget("a")
    assert mon.dead_tasks() == []
    now[0] = 100.0
    mon.forget("b")                      # idempotent for unknown ids too
    mon.forget("never-seen")
    assert mon.dead_tasks() == []


def test_connect_with_retry_backoff_and_exhaustion():
    """Dial retries use jittered exponential backoff and surface a
    ConnectionError naming the attempt count after exhaustion."""
    import socket

    # A port guaranteed closed: bind-then-close.
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]

    sleeps = []
    with pytest.raises(ConnectionError, match="after 4 attempts"):
        rpc.connect_with_retry("127.0.0.1", dead_port, timeout=2,
                               retries=3, base_delay=0.2, max_delay=1.0,
                               sleep=sleeps.append, rng=lambda: 0.5)
    # 3 backoffs between 4 attempts: 0.2, 0.4, 0.8, all scaled by the
    # pinned jitter factor (0.5 + 0.5 = 1.0).
    assert sleeps == [0.2, 0.4, 0.8]

    # Success path: no sleeping, returns a connected socket.
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    try:
        sleeps.clear()
        sock = rpc.connect_with_retry("127.0.0.1", srv.getsockname()[1],
                                      sleep=sleeps.append)
        sock.close()
        assert sleeps == []
    finally:
        srv.close()


def test_driver_fails_fast_on_lost_task():
    """A task that registers and then falls silent (executor OOM-killed,
    node gone) must fail the job at the keepalive timeout, not after the
    full result timeout (dead_tasks is wired into the wait loop)."""
    driver = JobDriver(2, KEY, keepalive_timeout=0.2)
    try:
        for idx in (0, 1):
            rpc.rpc_call("127.0.0.1", driver.port,
                         {"kind": "register", "index": idx,
                          "host": "h", "port": 1}, KEY)
        with pytest.raises(RuntimeError, match="stopped sending keepalives"):
            driver.wait_for_results(timeout=60)
    finally:
        driver.shutdown()


def test_run_task_keepalive_pings_outlive_slow_fn():
    """run_task's background pinger keeps a long-running fn alive past
    the keepalive timeout, and the result forgets the task so it is not
    declared dead afterwards."""
    import time

    driver = JobDriver(1, KEY, keepalive_timeout=0.3)
    try:
        t = threading.Thread(
            target=lambda: run_task(0, "127.0.0.1", driver.port, KEY,
                                    lambda: time.sleep(1.0) or "done",
                                    ping_interval=0.05))
        t.start()
        assert driver.wait_for_results(timeout=60) == ["done"]
        t.join(timeout=30)
    finally:
        driver.shutdown()
