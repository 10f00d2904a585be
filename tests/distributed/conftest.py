"""Fixtures for tests that run UNDER the launcher
(``hvdrun -np N python -m pytest tests/distributed``).

Unlike the parent conftest's per-test init/shutdown, the native runtime is
not shut down between tests: the rendezvous is a job-wide event (reference
tests likewise init once per process, test/test_torch.py), and
``basics`` shuts down at exit.  In the single-process suite a test of the
parent conftest may have shut down in between, so ``hvd`` initialises
whenever it finds the runtime down, and a distributed file passes wherever
it lands on an xdist worker.
"""

import pytest


@pytest.fixture()
def hvd():
    import horovod_tpu as hvd
    if not hvd.is_initialized():
        hvd.init()
    return hvd


@pytest.fixture()
def rank(hvd):
    return hvd.rank()


@pytest.fixture()
def size(hvd):
    return hvd.size()
