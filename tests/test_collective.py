"""Collective op tests over the 8-device SPMD mesh plus single-process eager
semantics.  Modeled on reference ``test/test_tensorflow.py:123-649`` (op
matrix, dtype coverage, grad correctness) and ``test/test_torch.py:103-390``
(async handles, duplicate names)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd_mod
from horovod_tpu.ops import collective


def shard(f, mesh, in_specs, out_specs):
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


# ---------------------------------------------------------------------------
# SPMD plane
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_spmd_allreduce_sum(hvd, mesh8, dtype):
    x = jnp.arange(8 * 4, dtype=dtype).reshape(8, 4)
    f = shard(lambda t: hvd.allreduce(t, op=hvd.Sum), mesh8, P("data"), P())
    out = np.asarray(f(x), np.float64).reshape(-1)
    expected = np.sum(np.asarray(x, np.float64), axis=0)
    np.testing.assert_allclose(out, expected)


def test_spmd_allreduce_average(hvd, mesh8):
    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    f = shard(lambda t: hvd.allreduce(t), mesh8, P("data"), P())
    np.testing.assert_allclose(np.asarray(f(x)).reshape(-1),
                               np.mean(np.asarray(x), axis=0), rtol=1e-6)


def test_spmd_allreduce_adasum_raises(hvd, mesh8):
    """Adasum is an eager-plane op; the SPMD plane must fail loudly
    instead of silently substituting the mean (docs/api.md)."""
    x = jnp.ones((8, 4), jnp.float32)
    f = shard(lambda t: hvd.allreduce(t, op=hvd.Adasum), mesh8,
              P("data"), P())
    with pytest.raises(NotImplementedError, match="Adasum"):
        f(x)


def test_spmd_allreduce_min_max(hvd, mesh8):
    x = jnp.asarray(np.random.RandomState(0).randn(8, 5), jnp.float32)
    fmin = shard(lambda t: hvd.allreduce(t, op=hvd.Min), mesh8, P("data"), P())
    fmax = shard(lambda t: hvd.allreduce(t, op=hvd.Max), mesh8, P("data"), P())
    np.testing.assert_allclose(np.asarray(fmin(x)).reshape(-1),
                               np.min(np.asarray(x), 0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(fmax(x)).reshape(-1),
                               np.max(np.asarray(x), 0), rtol=1e-6)


def test_spmd_allreduce_prescale_postscale(hvd, mesh8):
    x = jnp.ones((8, 3), jnp.float32)
    f = shard(lambda t: hvd.allreduce(t, op=hvd.Sum, prescale_factor=0.5,
                                      postscale_factor=3.0),
              mesh8, P("data"), P())
    np.testing.assert_allclose(np.asarray(f(x)).reshape(-1),
                               np.full((3,), 8 * 0.5 * 3.0), rtol=1e-6)


def test_spmd_allgather(hvd, mesh8):
    # dim-0 concatenation semantics (reference tensorflow/mpi_ops.cc:369-391)
    x = jnp.arange(8 * 2 * 3, dtype=jnp.float32).reshape(8 * 2, 3)
    f = shard(lambda t: hvd.allgather(t), mesh8, P("data"), P())
    np.testing.assert_allclose(f(x), np.asarray(x), rtol=1e-6)


def test_spmd_broadcast(hvd, mesh8):
    x = jnp.asarray(np.random.RandomState(1).randn(8, 4), jnp.float32)
    root = 3

    def body(t):
        return hvd.broadcast(t, root_rank=root)

    f = shard(body, mesh8, P("data"), P("data"))
    out = np.asarray(f(x))
    for i in range(8):
        np.testing.assert_allclose(out[i], np.asarray(x)[root], rtol=1e-6)


def test_spmd_reducescatter(hvd, mesh8):
    x = jnp.arange(8 * 8, dtype=jnp.float32).reshape(8, 8)
    # each shard holds a (1,8) row; psum_scatter returns (1,) piece per dev
    f = shard(lambda t: hvd.reducescatter(t.reshape(-1), op=hvd.Sum),
              mesh8, P("data"), P("data"))
    out = np.asarray(f(x)).ravel()
    np.testing.assert_allclose(out, np.sum(np.asarray(x), axis=0), rtol=1e-6)


def test_spmd_alltoall(hvd, mesh8):
    x = jnp.arange(64, dtype=jnp.float32)
    f = shard(lambda t: hvd.alltoall(t), mesh8, P("data"), P("data"))
    out = np.asarray(f(x)).reshape(8, 8)
    # shard i sends its j-th element to shard j → transpose of input blocks
    expected = np.arange(64, dtype=np.float32).reshape(8, 8).T
    np.testing.assert_allclose(out, expected, rtol=1e-6)


def test_spmd_grouped_allreduce_matches_individual(hvd, mesh8):
    rs = np.random.RandomState(2)
    xs = [jnp.asarray(rs.randn(8, n), jnp.float32) for n in (3, 5, 7)]

    def body(*ts):
        return tuple(hvd.grouped_allreduce(list(ts), op=hvd.Average))

    f = shard(body, mesh8, (P("data"),) * 3, (P(),) * 3)
    outs = f(*xs)
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(np.asarray(o).reshape(-1),
                                   np.mean(np.asarray(x), 0), rtol=1e-5)


def test_spmd_grouped_allreduce_scaling_parity(hvd, mesh8):
    """grouped_allreduce honors prescale/postscale exactly like allreduce
    (the scaling rides the fused flat bucket)."""
    rs = np.random.RandomState(4)
    xs = [jnp.asarray(rs.randn(8, n), jnp.float32) for n in (3, 5, 7)]

    def grouped(*ts):
        return tuple(hvd.grouped_allreduce(
            list(ts), op=hvd.Sum, prescale_factor=0.5,
            postscale_factor=3.0))

    def individual(*ts):
        return tuple(hvd.allreduce(t, op=hvd.Sum, prescale_factor=0.5,
                                   postscale_factor=3.0) for t in ts)

    f = shard(grouped, mesh8, (P("data"),) * 3, (P(),) * 3)
    g = shard(individual, mesh8, (P("data"),) * 3, (P(),) * 3)
    for got, want in zip(f(*xs), g(*xs)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5)


def test_spmd_grouped_allreduce_rejects_process_set(hvd, mesh8):
    """Non-global process sets are an eager-plane concept; the SPMD path
    must reject them loudly, exactly like allreduce
    (``_reject_spmd_process_set``)."""
    ps = collective.ProcessSet([0], set_id=7)
    x = jnp.ones((8, 2), jnp.float32)

    def body(t):
        return hvd.grouped_allreduce([t], process_set=ps)[0]

    f = shard(body, mesh8, P("data"), P())
    with pytest.raises(ValueError, match="process_set"):
        f(x)
    # ... and the global set passes through untouched (same as allreduce).
    g = shard(lambda t: hvd.grouped_allreduce(
        [t], process_set=collective.global_process_set)[0],
        mesh8, P("data"), P())
    np.testing.assert_allclose(np.asarray(g(x)).reshape(-1),
                               np.ones(2), rtol=1e-6)


def test_eager_grouped_allreduce_scaling(hvd):
    """Eager (no axis) path: scaling forwards to per-tensor allreduce."""
    xs = [jnp.asarray([2.0, 4.0]), jnp.asarray([[1.0], [3.0]])]
    outs = hvd.grouped_allreduce(xs, op=hvd.Sum, prescale_factor=2.0,
                                 postscale_factor=0.5)
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(np.asarray(o), np.asarray(x), rtol=1e-6)


def test_spmd_allreduce_grad(hvd, mesh8):
    """Gradient of allreduce-mean is mean of cotangent (reference
    test_tensorflow.py:385-460 grad checks)."""
    x = jnp.asarray(np.random.RandomState(3).randn(8, 4), jnp.float32)

    def loss(t):
        return jnp.sum(hvd.allreduce(t, op=hvd.Average) ** 2)

    f = shard(jax.grad(loss), mesh8, P("data"), P("data"))
    g = np.asarray(f(x))
    mean = np.mean(np.asarray(x), 0)
    # every shard computes loss=sum(mean^2); x_i feeds all 8 shard losses
    # with weight 1/8 each → d/dx_i = 8 * 2*mean/8 = 2*mean
    for i in range(8):
        np.testing.assert_allclose(g[i], 2 * mean, rtol=1e-5)


def test_fusion_bucketing():
    from horovod_tpu.ops.fusion import _bucket_leaves
    leaves = [np.zeros(10, np.float32), np.zeros(10, np.int32),
              np.zeros(10, np.float32), np.zeros(1000, np.float32)]
    buckets = _bucket_leaves(leaves, threshold=10 * 4 * 2)
    # same-dtype grouping, threshold respected
    for b in buckets:
        dts = {str(leaves[i].dtype) for i in b}
        assert len(dts) == 1
        assert sum(leaves[i].nbytes for i in b) <= 10 * 4 * 2 or len(b) == 1
    covered = sorted(i for b in buckets for i in b)
    assert covered == [0, 1, 2, 3]


def test_fused_psum_threshold_split(hvd, mesh8):
    rs = np.random.RandomState(4)
    xs = [jnp.asarray(rs.randn(8, n), jnp.float32) for n in (2, 3, 4, 5)]

    def body(*ts):
        from horovod_tpu.ops.fusion import fused_psum
        return tuple(fused_psum(list(ts), "data", mean=True, threshold=24))

    f = shard(body, mesh8, (P("data"),) * 4, (P(),) * 4)
    outs = f(*xs)
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(np.asarray(o).reshape(-1),
                                   np.mean(np.asarray(x), 0), rtol=1e-5)


# ---------------------------------------------------------------------------
# Eager plane (single process: 1-rank semantics, handles, errors)
# ---------------------------------------------------------------------------

def test_eager_allreduce_single_proc(hvd):
    x = np.random.RandomState(5).randn(4, 3).astype(np.float32)
    out = hvd.allreduce(jnp.asarray(x), op=hvd.Sum)
    np.testing.assert_allclose(out, x, rtol=1e-6)
    out = hvd.allreduce(jnp.asarray(x), op=hvd.Average)
    np.testing.assert_allclose(out, x, rtol=1e-6)  # size 1 → identity


def test_eager_allgather_broadcast_single_proc(hvd):
    x = np.arange(6, dtype=np.float32).reshape(3, 2)
    np.testing.assert_allclose(hvd.allgather(jnp.asarray(x)), x)
    np.testing.assert_allclose(hvd.broadcast(jnp.asarray(x), 0), x)
    with pytest.raises(ValueError, match="out of range"):
        hvd.broadcast(jnp.asarray(x), root_rank=2)


def test_async_handle_poll_synchronize(hvd):
    x = np.ones((16,), np.float32)
    h = hvd.allreduce_async(x, op=hvd.Sum, name="t_async")
    out = hvd.synchronize(h)
    assert hvd.poll(h)
    np.testing.assert_allclose(out, x)


def test_async_duplicate_name_error(hvd):
    """In-flight duplicate names must be rejected (reference
    common.h:155-158, test_torch.py:390)."""
    import threading
    from horovod_tpu.ops.collective import _handles
    gate = _handles.allocate("dup_tensor", "allreduce")
    try:
        with pytest.raises(ValueError, match="same name"):
            hvd.allreduce_async(np.ones(4, np.float32), name="dup_tensor")
    finally:
        _handles.complete(gate)


def test_synchronize_unknown_handle(hvd):
    with pytest.raises(ValueError, match="Handle"):
        hvd.synchronize(123456)


def test_allgather_object_roundtrip(hvd):
    objs = hvd.allgather_object({"rank": 0, "data": [1, 2, 3]})
    assert objs == [{"rank": 0, "data": [1, 2, 3]}]


def test_broadcast_object_roundtrip(hvd):
    obj = hvd.broadcast_object({"lr": 0.1, "betas": (0.9, 0.999)})
    assert obj == {"lr": 0.1, "betas": (0.9, 0.999)}


def test_join_single_proc(hvd):
    assert hvd.join() == 0


def test_compression_fp16_bf16_roundtrip(hvd):
    from horovod_tpu.ops.compression import Compression
    x = jnp.asarray(np.random.RandomState(6).randn(8, 8), jnp.float32)
    for comp in (Compression.fp16, Compression.bf16):
        t, ctx = comp.compress(x)
        assert t.dtype in (jnp.float16, jnp.bfloat16)
        out = comp.decompress(t, ctx)
        assert out.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                                   atol=2e-2)
    t, ctx = Compression.none.compress(x)
    assert t is x and ctx is None


def test_eager_allreduce_with_compression(hvd):
    from horovod_tpu.ops.compression import Compression
    x = jnp.asarray(np.random.RandomState(7).randn(4), jnp.float32)
    out = hvd.allreduce(x, compression=Compression.fp16)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=1e-2)


def _ragged_oracle(xs, splits, cap):
    """numpy oracle for alltoall_ragged: xs[s] = sender s's rows grouped
    by destination per splits[s]; returns per-dest (padded out, recv)."""
    S = splits.shape[0]
    outs, recvs = [], []
    for d in range(S):
        rows = []
        for s in range(S):
            start = splits[s, :d].sum()
            rows.append(xs[s][start:start + splits[s, d]])
        cat = np.concatenate(rows, axis=0)[:cap]
        pad = np.zeros((cap - cat.shape[0],) + cat.shape[1:], cat.dtype)
        outs.append(np.concatenate([cat, pad], axis=0))
        recvs.append(splits[:, d])
    return np.stack(outs), np.stack(recvs)


def test_alltoall_ragged_matches_oracle(hvd, mesh8):
    """SPMD uneven alltoall: static-capacity ragged
    exchange inside shard_map, dense-twin route (CPU mesh), vs a numpy
    oracle.  Row payloads encode (sender, dest, i) so misrouting is
    detected, not just miscounting."""
    S, CAP = 8, 24
    rng = np.random.default_rng(3)
    splits = rng.integers(0, 4, size=(S, S)).astype(np.int32)
    n = int(splits.sum(axis=1).max()) + 2   # slack: rows past sum(splits)
    xs = np.zeros((S, n, 3), np.float32)
    for s in range(S):
        r = 0
        for d in range(S):
            for i in range(splits[s, d]):
                xs[s, r] = (s, d, i)
                r += 1
        xs[s, r:] = -777.0   # junk past sum(splits): must never arrive

    def f(x, sp):
        return hvd.alltoall_ragged(x, sp, CAP, axis_name="ep")

    from horovod_tpu.topology import build_mesh
    mesh = build_mesh(axes=("ep",), shape=(S,))
    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("ep"), P("ep")),
                              out_specs=(P("ep"), P("ep"))))
    out, recv = g(xs.reshape(S * n, 3), splits.reshape(-1))
    out = np.asarray(out).reshape(S, CAP, 3)
    recv = np.asarray(recv).reshape(S, S)
    want_out, want_recv = _ragged_oracle(xs, splits, CAP)
    np.testing.assert_array_equal(recv, want_recv)
    np.testing.assert_array_equal(out, want_out)


def test_alltoall_ragged_capacity_drop(hvd, mesh8):
    """Rows past the static capacity are dropped (the capacity-factor
    router contract), never written out of bounds."""
    S, CAP = 8, 3   # every rank receives 8 rows, keeps 3
    def f(x):
        sp = jnp.ones((S,), jnp.int32)
        return hvd.alltoall_ragged(x, sp, CAP, axis_name="ep")
    from horovod_tpu.topology import build_mesh
    mesh = build_mesh(axes=("ep",), shape=(S,))
    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("ep"),
                              out_specs=(P("ep"), P("ep"))))
    x = np.arange(S * S, dtype=np.float32).reshape(S * S, 1)
    out, recv = g(x)
    out = np.asarray(out).reshape(S, CAP)
    recv = np.asarray(recv).reshape(S, S)
    assert (recv == 1).all()
    for d in range(S):
        # Senders 0..2's rows survive (source order), the rest dropped.
        np.testing.assert_array_equal(
            out[d], [s * S + d for s in range(CAP)])


def test_alltoall_ragged_matches_eager(hvd, mesh8):
    """The SPMD ragged result equals the eager plane's uneven alltoall
    (padded), tying the two planes' contracts together."""
    # size-1 eager path: everything routes to self.
    x = np.arange(12, dtype=np.float32).reshape(6, 2)
    splits = np.array([6], np.int64)
    eager_out, eager_recv = hvd.alltoall(x, splits=splits, name="rg.eq")
    def f(xx):
        return hvd.alltoall_ragged(xx, jnp.ones((1,), jnp.int32) * 6, 8,
                                   axis_name="one")
    from horovod_tpu.topology import build_mesh
    mesh = build_mesh(axes=("one",), shape=(1,))
    out, recv = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P("one"), out_specs=(P("one"), P("one"))))(x)
    np.testing.assert_array_equal(np.asarray(out)[:6], np.asarray(eager_out))
    np.testing.assert_array_equal(np.asarray(recv), np.asarray(eager_recv))


def test_alltoall_ragged_gradient(hvd, mesh8):
    """The dense-twin route is differentiable end-to-end: every row that
    lands somewhere gets its cotangent back (2x for sum-of-squares),
    slack rows past sum(splits) get zero."""
    S, CAP, n = 8, 10, 3
    rng = np.random.default_rng(9)
    splits = rng.integers(0, 2, size=(S, S)).astype(np.int32)

    def loss(x, sp):
        out, _ = hvd.alltoall_ragged(x, sp, CAP, axis_name="ep")
        return (out ** 2).sum()

    from horovod_tpu.topology import build_mesh
    mesh = build_mesh(axes=("ep",), shape=(S,))
    g = jax.jit(jax.shard_map(jax.grad(loss), mesh=mesh,
                              in_specs=(P("ep"), P("ep")),
                              out_specs=P("ep")))
    xs = rng.standard_normal((S * n, 2)).astype(np.float32)
    gx = np.asarray(g(xs, splits.reshape(-1)))
    want = 2 * xs
    for s in range(S):
        sent = int(splits[s].sum())
        want[s * n + sent:(s + 1) * n] = 0
    np.testing.assert_allclose(gx, want, rtol=1e-5)
