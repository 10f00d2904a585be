"""TensorFlow binding tests (reference test/test_tensorflow.py:123-460
op matrix), rank-aware — run standalone (size 1) or under
``hvdrun -np N``."""

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")


@pytest.fixture()
def tfhvd(hvd):
    import horovod_tpu.tensorflow as tfhvd
    return tfhvd


def test_tf_allreduce_sum_avg(tfhvd, rank, size):
    x = tf.ones((4, 3)) * (rank + 1)
    out = tfhvd.allreduce(x, average=False, name="tf.sum")
    assert np.allclose(out.numpy(), sum(range(1, size + 1)))
    out = tfhvd.allreduce(x, average=True, name="tf.avg")
    assert np.allclose(out.numpy(), (size + 1) / 2)


def test_tf_allreduce_dtypes(tfhvd, rank, size):
    for dtype in (tf.float32, tf.float64, tf.int32, tf.int64):
        x = tf.cast(tf.fill([5], rank + 1), dtype)
        out = tfhvd.allreduce(x, average=False, name=f"tf.dt.{dtype.name}")
        assert out.dtype == dtype
        assert np.allclose(out.numpy(), sum(range(1, size + 1)))


def test_tf_allreduce_adasum(tfhvd, rank, size):
    """op=Adasum through the TF binding: the Adasum identity plus the
    2-rank parallel-vectors case (see test_torch_binding)."""
    x = tf.constant(np.linspace(1.0, 2.0, 8, dtype=np.float32))
    out = tfhvd.allreduce(x, op=tfhvd.Adasum, name="tf.adasum.ident")
    np.testing.assert_allclose(out.numpy(), x.numpy(), rtol=1e-5)
    if size == 2:
        y = x * (1.0 if rank == 0 else 3.0)
        out = tfhvd.allreduce(y, op=tfhvd.Adasum, name="tf.adasum.par")
        np.testing.assert_allclose(out.numpy(), 2.0 * x.numpy(),
                                   rtol=1e-4)


def test_tf_allreduce_fp16_compression(tfhvd, rank, size):
    x = tf.ones((8,)) * (rank + 1)
    out = tfhvd.allreduce(x, average=False, name="tf.fp16",
                          compression=tfhvd.Compression.fp16)
    assert out.dtype == tf.float32
    assert np.allclose(out.numpy(), sum(range(1, size + 1)))


def test_tf_allgather_variable_dim0(tfhvd, rank, size):
    """dim-0 may differ per rank (reference test_tensorflow.py:461-530)."""
    x = tf.ones((rank + 1, 2)) * rank
    out = tfhvd.allgather(x, name="tf.ag")
    assert out.shape == (size * (size + 1) // 2, 2)
    # rows from rank r hold value r
    rows = out.numpy()[:, 0]
    expect = np.concatenate([np.full(r + 1, r) for r in range(size)])
    assert np.allclose(rows, expect)


def test_tf_broadcast(tfhvd, rank, size):
    x = tf.range(6, dtype=tf.float32) * (rank + 1)
    out = tfhvd.broadcast(x, 0, name="tf.bc")
    assert np.allclose(out.numpy(), np.arange(6, dtype=np.float32))


def test_tf_broadcast_variables(tfhvd, rank, size):
    v = tf.Variable(tf.ones((3,)) * (rank + 7.0))
    tfhvd.broadcast_variables([v], root_rank=0)
    assert np.allclose(v.numpy(), 7.0)


def test_tf_allreduce_grad(tfhvd, rank, size):
    """Gradient of sum-allreduce is sum-allreduce of the gradient
    (reference test_tensorflow.py:385-420)."""
    v = tf.Variable(tf.ones((3,)) * (rank + 1))
    with tf.GradientTape() as t:
        y = tf.reduce_sum(tfhvd.allreduce(v, average=False, name="tf.g"))
    g = t.gradient(y, v)
    # upstream grad is ones; allreduce-sum of ones = size
    assert np.allclose(g.numpy(), size)


def test_tf_allgather_grad(tfhvd, rank, size):
    """Gradient slices this rank's rows out of the reduced upstream grad
    (reference mpi_ops.py:122-145)."""
    v = tf.Variable(tf.ones((rank + 1, 2)))
    with tf.GradientTape() as t:
        y = tf.reduce_sum(tfhvd.allgather(v, name="tf.agg") * 2.0)
    g = t.gradient(y, v)
    assert g.shape == (rank + 1, 2)
    assert np.allclose(g.numpy(), 2.0 * size)


def test_tf_distributed_gradient_tape(tfhvd, rank, size):
    """Averaged gradients are identical across ranks despite
    rank-dependent data (reference test_tensorflow.py grad tests)."""
    v = tf.Variable([1.0, 2.0])
    with tf.GradientTape() as tape:
        loss = tf.reduce_sum(v * float(rank + 1))
    tape = tfhvd.DistributedGradientTape(tape)
    (g,) = tape.gradient(loss, [v])
    expect = np.mean([r + 1 for r in range(size)])
    assert np.allclose(np.asarray(g), expect)


def test_tf_indexed_slices_allreduce(tfhvd, rank, size):
    """IndexedSlices ride the allgather path (reference
    tensorflow/__init__.py:63-76)."""
    slices = tf.IndexedSlices(values=tf.ones((2, 3)) * (rank + 1),
                              indices=tf.constant([0, rank + 1]),
                              dense_shape=tf.constant([size + 2, 3]))
    out = tfhvd.allreduce(slices, average=False)
    assert isinstance(out, tf.IndexedSlices)
    assert out.values.shape[0] == 2 * size


def test_tf_inside_tf_function(tfhvd, rank, size):
    """py_function collectives execute correctly inside a traced graph."""
    @tf.function
    def step(x):
        return tfhvd.allreduce(x, average=False, name="tf.fn")
    out = step(tf.ones((4,)) * (rank + 1))
    assert np.allclose(out.numpy(), sum(range(1, size + 1)))


def test_tf_alltoall(tfhvd, rank, size):
    x = tf.ones((size, 2)) * rank
    out = tfhvd.alltoall(x, name="tf.a2a")
    assert out.shape == (size, 2)
    assert np.allclose(out.numpy()[:, 0], np.arange(size))


def test_tf_broadcast_object(tfhvd, rank, size):
    obj = {"rank": 0, "data": [1, 2, 3]} if rank == 0 else None
    out = tfhvd.broadcast_object(obj, root_rank=0, name="tf.obj")
    assert out == {"rank": 0, "data": [1, 2, 3]}


def test_tf_shape_mismatch_error(tfhvd, rank, size):
    """Mismatched shapes must produce a coordinated error, not a hang
    (reference test_tensorflow.py:314-339)."""
    if size < 2:
        pytest.skip("needs >= 2 ranks")
    x = tf.ones((rank + 1,))   # different shape per rank
    with pytest.raises(Exception, match="[Mm]ismatch|shape"):
        tfhvd.allreduce(x, average=False, name="tf.err.shape")


def test_tf_alltoall_uneven_splits(tfhvd, rank, size):
    """alltoall with explicit splits returns (output, received_splits),
    both in eager and traced-graph mode (two-output py_function)."""
    splits = tf.constant(np.arange(1, size + 1, dtype=np.int64))
    rows = int(np.arange(1, size + 1).sum())
    x = tf.ones((rows, 2)) * rank
    out, received = tfhvd.alltoall(x, splits=np.arange(1, size + 1,
                                                      dtype=np.int64),
                                   name="tf.a2av")
    assert np.array_equal(received.numpy(), np.full(size, rank + 1))
    assert out.shape[0] == (rank + 1) * size

    @tf.function
    def step(v):
        return tfhvd.alltoall(v, splits=np.arange(1, size + 1,
                                                  dtype=np.int64),
                              name="tf.a2av.graph")
    out2, received2 = step(x)
    assert np.array_equal(received2.numpy(), np.full(size, rank + 1))
    assert out2.shape[0] == (rank + 1) * size
    del splits


def test_grouped_allreduce(tfhvd, rank, size):
    """grouped_allreduce averages every tensor in the group — the async
    enqueue + single sync-barrier path the gradient wrappers use."""
    hvd = tfhvd
    ts = [tf.constant(np.full((3, 2), float(rank + 1) * (i + 1),
                              np.float32)) for i in range(5)]
    outs = hvd.grouped_allreduce(ts, average=True, name="grp.eager")
    want_base = np.mean([r + 1 for r in range(size)])
    for i, o in enumerate(outs):
        np.testing.assert_allclose(o.numpy(), np.full((3, 2),
                                   want_base * (i + 1), np.float32),
                                   rtol=1e-6)


def test_grouped_allreduce_graph_and_grad(tfhvd, rank, size):
    """Graph-mode grouped allreduce: values AND gradients (the gradient
    of a group is a grouped sum-allreduce of the upstream gradients)."""
    hvd = tfhvd
    vs = [tf.Variable(np.full((2, 2), float(rank + 1) * (i + 1),
                              np.float32)) for i in range(4)]

    @tf.function
    def run():
        with tf.GradientTape() as tape:
            outs = hvd.grouped_allreduce([v * 1.0 for v in vs],
                                         average=True, name="grp.graph")
            loss = tf.add_n([tf.reduce_sum(o) for o in outs])
        return outs, tape.gradient(loss, vs)

    outs, grads = run()
    want_base = np.mean([r + 1 for r in range(size)])
    for i, o in enumerate(outs):
        np.testing.assert_allclose(o.numpy(), np.full((2, 2),
                                   want_base * (i + 1), np.float32),
                                   rtol=1e-6)
    # d(loss)/d(v) = allreduce-sum(ones)/size... average's local divide
    # makes each rank's grad = ones * size / size = ones.
    for g in grads:
        np.testing.assert_allclose(g.numpy(), np.ones((2, 2), np.float32),
                                   rtol=1e-6)
