"""Minimal pyspark-API shim with local-mode execution semantics.

Purpose: the authoring host has no JVM/pyspark, but the Spark veneer
(``horovod_tpu/spark/__init__.py``) must be EXECUTED, not just imported.
Real pyspark's ``local[N]`` mode runs each task's
Python function in its own Python worker process, serialized with
cloudpickle; this shim reproduces exactly that contract for the four
API points the veneer touches:

* ``pyspark.sql.SparkSession.builder.getOrCreate()``
* ``session.sparkContext`` / ``sc.defaultParallelism``
* ``sc.parallelize(seq, n)``
* ``rdd.mapPartitionsWithIndex(f).collect()`` — each partition's ``f``
  runs in a SPAWNED subprocess (own interpreter, own ``os.environ``,
  cloudpickle-serialized closure), results collected in partition order.

What this does NOT cover (and the real-pyspark test in
``tests/distributed/test_spark_veneer.py`` does, in the Docker image):
py4j/JVM transport, Spark's own scheduler and serializer plumbing.
Everything on the horovod_tpu side — driver service, HMAC RPC, rank
assignment, env contract, per-process ``hvd.init`` — is the real code.
"""

import multiprocessing as mp
import sys
import types

import cloudpickle


def _worker(payload: bytes, index: int, q) -> None:
    """One Spark task: deserialize the partition fn and run it (spawned
    process = own os.environ, as a real pyspark Python worker has)."""
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # The task only needs the CPU/eager plane; the config update pins
    # the platform even where an inherited JAX_PLATFORMS names the TPU
    # (same recipe as __graft_entry__._force_virtual_cpu_mesh).
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
    except ImportError:
        pass
    try:
        f = cloudpickle.loads(payload)
        out = list(f(index, iter([index])))
        q.put((index, "ok", out))
    except BaseException as e:  # noqa: BLE001 — reported to the driver
        q.put((index, "err", f"{type(e).__name__}: {e}"))


class _Mapped:
    def __init__(self, n, f):
        self._n = n
        self._payload = cloudpickle.dumps(f)

    def collect(self):
        import queue as _queue
        import time
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        procs = [ctx.Process(target=_worker, args=(self._payload, i, q))
                 for i in range(self._n)]
        for p in procs:
            p.start()
        results = {}
        deadline = time.monotonic() + 600
        while len(results) < self._n:
            try:
                idx, kind, val = q.get(timeout=5)
            except _queue.Empty:
                # Fail fast with the real cause when a worker died
                # without reporting (spawn failure, OOM kill).  A clean
                # exit (code 0) right after its put() is NOT dead — the
                # result may still be in the pipe; loop and drain it.
                dead = [(i, p.exitcode) for i, p in enumerate(procs)
                        if not p.is_alive() and p.exitcode != 0
                        and i not in results]
                if dead or time.monotonic() > deadline:
                    for p in procs:
                        p.terminate()
                    raise RuntimeError(
                        f"tasks died without reporting: {dead}"
                        if dead else "timed out waiting for tasks")
                continue
            if kind == "err":
                for p in procs:
                    p.terminate()
                raise RuntimeError(f"task {idx} failed: {val}")
            results[idx] = val
        for p in procs:
            p.join(timeout=60)
        return [v for i in range(self._n) for v in results[i]]


class _RDD:
    def __init__(self, n):
        self._n = n

    def mapPartitionsWithIndex(self, f):
        return _Mapped(self._n, f)


class _SparkContext:
    defaultParallelism = 2

    def parallelize(self, seq, num_slices):
        return _RDD(num_slices)


class _Session:
    sparkContext = _SparkContext()


class _Builder:
    def getOrCreate(self):
        return _Session()


def install():
    """Install the shim as ``pyspark`` in ``sys.modules`` (only call when
    real pyspark is absent)."""
    pyspark = types.ModuleType("pyspark")
    sql = types.ModuleType("pyspark.sql")

    class SparkSession:
        builder = _Builder()

    sql.SparkSession = SparkSession
    pyspark.sql = sql
    sys.modules["pyspark"] = pyspark
    sys.modules["pyspark.sql"] = sql
    return pyspark
