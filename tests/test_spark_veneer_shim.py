"""Execute the pyspark veneer against the local-mode shim.

``horovod_tpu.spark.run`` runs end to end: driver service up, two
SPAWNED task processes (own interpreters, like pyspark local-mode
Python workers) register over HMAC RPC, receive their rank env, call
``hvd.init`` + a real eager-plane allreduce, and the driver returns
rank-ordered results.  Only the JVM/py4j transport is simulated (see
``tests/pyspark_local_shim.py``); the real-pyspark twin of this test is
``tests/distributed/test_spark_veneer.py`` (Docker image).

Prints a ``SPARK_VENEER_OK`` marker line so CI logs carry greppable
evidence that the veneer executed.
"""

import sys

import pytest


def _fn(scale):
    import horovod_tpu as hvd
    hvd.init()
    import numpy as np
    out = hvd.allreduce(np.ones(3) * (hvd.rank() + 1),
                        average=False, name="spark.veneer.shim")
    return float(out.sum()) * scale, hvd.rank(), hvd.size()


def test_spark_run_veneer_shim():
    try:
        import pyspark  # noqa: F401
        pytest.skip("real pyspark present; the distributed twin covers it")
    except ImportError:
        pass
    pytest.importorskip("cloudpickle")   # the shim's task serializer
    import pyspark_local_shim
    pyspark_local_shim.install()
    try:
        from horovod_tpu import spark as hvd_spark

        results = hvd_spark.run(_fn, args=(2.0,), num_proc=2, verbose=0)
        assert len(results) == 2
        # allreduce sum of (1+2) over 3 elements = 9; *2 scale = 18
        for r, (val, rank, size) in enumerate(results):
            assert size == 2 and rank == r
            assert val == pytest.approx(18.0)
        print("SPARK_VENEER_OK: horovod_tpu.spark.run executed a real fn "
              "in 2 spawned local-mode tasks with correct rank env",
              flush=True)
    finally:
        sys.modules.pop("pyspark", None)
        sys.modules.pop("pyspark.sql", None)
