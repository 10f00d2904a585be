"""Test harness: simulate an 8-chip TPU mesh with CPU devices.

Mirrors the reference's "cluster without a cluster" strategy (SURVEY §4:
oversubscribed `-np 2` on localhost): here a single process gets 8 virtual
XLA CPU devices via ``--xla_force_host_platform_device_count``, so every
SPMD collective runs over a real 8-way mesh.  Multi-process (launcher) tests
spawn subprocesses with the same env.
"""

import os

# Must run before any JAX backend initialization.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Tests run on the virtual CPU mesh, on every machine: on a host with
# chips JAX would otherwise select the TPU (one device, no 8-way mesh, and
# a chip that belongs to one process at a time).  The config update pins
# the platform even where JAX_PLATFORMS names the TPU first.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture()
def hvd():
    import horovod_tpu as hvd
    hvd.init()
    yield hvd
    hvd.shutdown()


@pytest.fixture()
def mesh8(hvd):
    m = hvd.mesh()
    assert len(m.devices.ravel()) == 8
    return m
