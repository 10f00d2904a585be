"""Lifecycle/topology tests (reference: ``test/test_common.py`` introspection
tests and the rank/size plumbing exercised all over ``test/test_tensorflow.py``)."""

import numpy as np
import pytest


def test_not_initialized_raises():
    import horovod_tpu as hvd
    hvd.shutdown()
    with pytest.raises(ValueError, match="not been initialized"):
        hvd.rank()
    with pytest.raises(ValueError, match="not been initialized"):
        hvd.size()


def test_init_rank_size(hvd):
    assert hvd.is_initialized()
    assert hvd.rank() == 0
    assert hvd.size() == 1
    assert hvd.local_rank() == 0
    assert hvd.local_size() == 1
    assert hvd.cross_rank() == 0
    assert hvd.cross_size() == 1


def test_init_idempotent(hvd):
    hvd.init()
    assert hvd.rank() == 0


def test_env_topology(monkeypatch):
    import horovod_tpu as hvd
    hvd.shutdown()
    # Env contract set by the launcher (reference run/gloo_run.py:211-254)
    monkeypatch.setenv("HOROVOD_RANK", "3")
    monkeypatch.setenv("HOROVOD_SIZE", "1")   # keep 1 so no runtime needed
    monkeypatch.setenv("HOROVOD_LOCAL_RANK", "1")
    monkeypatch.setenv("HOROVOD_LOCAL_SIZE", "2")
    hvd.init()
    try:
        assert hvd.rank() == 3
        assert hvd.local_rank() == 1
        assert hvd.local_size() == 2
    finally:
        hvd.shutdown()


def test_rank_subset_inactive(monkeypatch):
    """hvd.init(ranks) with this process outside the subset → size-1 no-op
    member (reference basics.py:29-61, operations.cc:613-622)."""
    import horovod_tpu as hvd
    hvd.shutdown()
    monkeypatch.setenv("HOROVOD_RANK", "2")
    monkeypatch.setenv("HOROVOD_SIZE", "1")
    hvd.init(ranks=[0, 1])
    try:
        assert hvd.size() == 1 and hvd.rank() == 0
    finally:
        hvd.shutdown()


def test_num_devices(hvd):
    assert hvd.num_devices() == 8
    assert len(hvd.local_devices()) == 8


def test_capabilities(hvd):
    # Reference test_common.py:36-66 checks *_built consistency; this build
    # has exactly one backend: TPU/XLA.
    assert hvd.tpu_built() and hvd.tpu_enabled()
    assert not hvd.mpi_built() and not hvd.mpi_enabled()
    assert not hvd.gloo_built() and not hvd.nccl_built()
    assert not hvd.ddl_built() and not hvd.mlsl_built()
    assert hvd.mpi_threads_supported() is False


def test_mesh_default(hvd):
    m = hvd.mesh()
    assert m.axis_names == ("data",)
    assert m.shape["data"] == 8
    assert hvd.mesh() is m  # cached


def test_mesh_hierarchical(hvd):
    m = hvd.mesh(axes=("replica", "data"), shape=(2, 4))
    assert m.shape == {"replica": 2, "data": 4}


def test_mesh_bad_shape(hvd):
    with pytest.raises(ValueError, match="does not cover"):
        hvd.mesh(axes=("a", "b"), shape=(3, 4))


def test_exec_on_tpu_attribute_chain(hvd):
    """Pin the JAX-internal chain ``jax.typeof(x).sharding.mesh
    .abstract_device.device_kind`` that ``topology.exec_on_tpu`` routes
    on, against the jax version pyproject.toml pins: inside shard_map the
    whole chain resolves to the executing mesh's device kind, outside it
    ``abstract_device`` is None and the default backend answers.  There
    is no fallback — a JAX that renames a link fails here (and raises in
    ``exec_on_tpu``) instead of silently degrading kernel routing.
    """
    import importlib

    import jax
    from jax.sharding import PartitionSpec as P

    # The package exports basics.topology() under the same name; the
    # module itself must come from the module registry.
    topo = importlib.import_module("horovod_tpu.topology")
    seen = {}

    def body(x):
        seen["kind"] = jax.typeof(x).sharding.mesh.abstract_device.device_kind
        seen["exec_on_tpu"] = topo.exec_on_tpu(x)
        return x

    jax.shard_map(body, mesh=hvd.mesh(), in_specs=P("data"),
                  out_specs=P("data"))(np.zeros(8, np.float32))
    assert seen["kind"] == jax.devices()[0].device_kind
    assert seen["exec_on_tpu"] is False          # CPU mesh

    x = np.zeros(8, np.float32)
    assert jax.typeof(x).sharding.mesh.abstract_device is None
    assert topo.exec_on_tpu(x) is False
