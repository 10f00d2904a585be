"""Wire-level gradient compression (PR 7): codec resolution, the fp16
clamp regression, bucket chunking, error-feedback convergence properties,
elastic reshard parity, and trajectory equivalence of the compressed
training steps.

The EF property at the heart of the subsystem (Seide et al. 2014;
Karimireddy et al. 2019): each compressed step is lossy, but the residual
(what the codec dropped) is added back into the next transmission, so the
CUMULATIVE mean of the decoded outputs converges to the true mean of the
inputs over repeated steps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd_mod
from horovod_tpu.ops import fusion
from horovod_tpu.ops import compression as C


# ---------------------------------------------------------------------------
# Satellite 1: FP16 overflow clamp (legacy per-tensor API)
# ---------------------------------------------------------------------------

def test_fp16_compress_clamps_instead_of_inf():
    t = jnp.asarray([1e5, -3e38, 7.0, 0.0], jnp.float32)
    wire, ctx = C.FP16Compressor.compress(t)
    assert wire.dtype == jnp.float16
    assert bool(jnp.all(jnp.isfinite(wire)))          # the regression
    back = C.FP16Compressor.decompress(wire, ctx)
    assert back.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(back), [65504.0, -65504.0, 7.0, 0.0], rtol=1e-3)


def test_bf16_compress_handles_large_values_without_clamp():
    t = jnp.asarray([1e38, -1e38], jnp.float32)
    wire, ctx = C.BF16Compressor.compress(t)
    assert wire.dtype == jnp.bfloat16
    assert bool(jnp.all(jnp.isfinite(wire)))
    back = C.BF16Compressor.decompress(wire, ctx)
    np.testing.assert_allclose(np.asarray(back), np.asarray(t), rtol=1e-2)


def test_cast_codec_fp16_clamps_on_the_bucket_wire():
    codec = C.parse_codec("fp16")
    w = codec._to_wire(jnp.asarray([1e6, -1e6], jnp.float32))
    assert bool(jnp.all(jnp.isfinite(w)))


# ---------------------------------------------------------------------------
# Codec resolution (HOROVOD_COMPRESSION + compression= kwargs)
# ---------------------------------------------------------------------------

def test_parse_codec_names():
    assert isinstance(C.parse_codec("none"), C.NoneCodec)
    assert C.parse_codec("bf16").name == "bf16"
    assert C.parse_codec("fp16").name == "fp16"
    assert isinstance(C.parse_codec("int8"), C.Int8Codec)
    assert C.parse_codec("powersgd").rank == 4
    assert C.parse_codec("powersgd:7").rank == 7
    with pytest.raises(ValueError, match="unknown compression codec"):
        C.parse_codec("gzip")
    with pytest.raises(ValueError, match="rank must be >= 1"):
        C.PowerSGDCodec(rank=0)


def test_resolve_codec_forms(monkeypatch):
    monkeypatch.delenv(C.HOROVOD_COMPRESSION_VAR, raising=False)
    assert isinstance(C.resolve_codec(None), C.NoneCodec)
    assert isinstance(C.resolve_codec(C.Compression.none), C.NoneCodec)
    assert C.resolve_codec(C.Compression.fp16).name == "fp16"
    assert C.resolve_codec(C.Compression.bf16).name == "bf16"
    assert C.resolve_codec("int8").name == "int8"
    inst = C.PowerSGDCodec(rank=2)
    assert C.resolve_codec(inst) is inst
    with pytest.raises(TypeError, match="no bucket-codec equivalent"):
        class Weird(C.Compressor):
            pass
        C.resolve_codec(Weird)
    with pytest.raises(TypeError, match="compression must be"):
        C.resolve_codec(1234)


def test_resolve_codec_env_only_for_default_forms(monkeypatch):
    monkeypatch.setenv(C.HOROVOD_COMPRESSION_VAR, "int8")
    assert C.resolve_codec(None).name == "int8"
    assert C.resolve_codec(C.Compression.none).name == "int8"
    # explicit codecs (even "none") beat the env
    assert isinstance(C.resolve_codec("none"), C.NoneCodec)
    assert C.resolve_codec("bf16").name == "bf16"


def test_resolve_codec_bad_env_warns_and_falls_back(monkeypatch):
    monkeypatch.setenv(C.HOROVOD_COMPRESSION_VAR, "zstd")
    monkeypatch.setattr(C, "_warned_bad_env", False)
    assert isinstance(C.resolve_codec(None), C.NoneCodec)
    assert C._warned_bad_env


def test_as_legacy():
    assert C.as_legacy(C.NoneCodec()) is C.NoneCompressor
    assert C.as_legacy(C.parse_codec("fp16")) is C.FP16Compressor
    assert C.as_legacy(C.parse_codec("bf16")) is C.BF16Compressor
    assert C.as_legacy(C.Int8Codec()) is None
    assert C.as_legacy(C.PowerSGDCodec()) is None


# ---------------------------------------------------------------------------
# Satellite 2: bucket chunking at HOROVOD_MAX_BUCKET_BYTES
# ---------------------------------------------------------------------------

def test_plan_chunks_oversized_buckets_and_round_trips():
    # one 4096-elem fp32 leaf = 16 KB; a 4 KB cap must split it into 4
    leaves = [jnp.arange(4096, dtype=jnp.float32),
              jnp.arange(10, dtype=jnp.float32)]
    plan = fusion.make_reduce_scatter_plan(leaves, 8, threshold=1 << 20,
                                           cap=4096)
    assert len(plan.buckets) >= 4
    for b in range(len(plan.buckets)):
        size = plan.bucket_size(b)
        itemsize = plan.bucket_dtype(b).itemsize
        assert size * itemsize <= 4096
    # concat/split stays the identity across the chunk boundaries
    out = plan.split(plan.concat(leaves))
    for a, b_ in zip(leaves, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_plan_cap_zero_disables_chunking():
    leaves = [jnp.zeros((1 << 16,), jnp.float32)]
    plan = fusion.make_reduce_scatter_plan(leaves, 8, threshold=1 << 30,
                                           cap=0)
    assert len(plan.buckets) == 1


def test_max_bucket_bytes_env(monkeypatch):
    monkeypatch.delenv("HOROVOD_MAX_BUCKET_BYTES", raising=False)
    assert fusion.max_bucket_bytes() == fusion.DEFAULT_MAX_BUCKET_BYTES
    monkeypatch.setenv("HOROVOD_MAX_BUCKET_BYTES", "4mb")
    assert fusion.max_bucket_bytes() == 4 * 1024 * 1024
    monkeypatch.setenv("HOROVOD_MAX_BUCKET_BYTES", "0")
    assert fusion.max_bucket_bytes() == 0
    monkeypatch.setenv("HOROVOD_MAX_BUCKET_BYTES", "not-a-size")
    monkeypatch.setattr(fusion, "_warned_bad_cap", False)
    assert fusion.max_bucket_bytes() == fusion.DEFAULT_MAX_BUCKET_BYTES


def test_chunked_fused_allreduce_matches_unchunked(hvd, mesh8):
    """The span-based plan is wire-transparent: chunked and unchunked
    plans produce identical fused reduce-scatter/all-gather results."""
    rng = np.random.RandomState(3)
    g = [jnp.asarray(rng.randn(8, 300), jnp.float32),
         jnp.asarray(rng.randn(8, 33), jnp.float32)]

    def run(cap):
        proto = [jax.ShapeDtypeStruct((300,), jnp.float32),
                 jax.ShapeDtypeStruct((33,), jnp.float32)]
        plan = fusion.make_reduce_scatter_plan(proto, 8, threshold=1 << 20,
                                               cap=cap)

        def f(leaves):
            shards, plan_ = fusion.fused_reduce_scatter(
                list(leaves), "data", mean=True, plan=plan)
            return tuple(fusion.fused_all_gather(shards, plan_, "data"))

        fn = jax.jit(jax.shard_map(
            f, mesh=mesh8,
            in_specs=(tuple(P("data") for _ in g),),
            out_specs=tuple(P() for _ in g), check_vma=False))
        return fn(tuple(x.reshape(-1, *x.shape[2:]) for x in g))

    big = run(0)
    small = run(256)   # 64 fp32 elems per chunk
    for a, b in zip(big, small):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Satellite 3: error-feedback convergence properties (8-rank SPMD mesh)
# ---------------------------------------------------------------------------

_SHAPES = [(16, 8), (37,), (5,)]


def _ef_harness(mesh, codec_spec, steps, shapes=_SHAPES):
    """Cumulative-mean relative error per step for a codec, reducing the
    SAME per-rank gradients each step (the EF convergence property)."""
    codec = C.resolve_codec(codec_spec)
    rng = np.random.RandomState(0)
    g_all = [jnp.asarray(rng.randn(8, *s), jnp.float32) for s in shapes]
    true_mean = [g.mean(0) for g in g_all]
    proto = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    plan = fusion.make_reduce_scatter_plan(proto, 8, codec=codec)
    state = codec.init_state(plan)
    specs = codec.state_specs(plan, "data")

    def step(gs, st):
        out, st = C.compressed_allreduce(list(gs), "data", codec,
                                         plan=plan, state=st, mean=True)
        return tuple(out), st

    f = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(tuple(P("data") for _ in shapes), specs),
        out_specs=(tuple(P() for _ in shapes), specs),
        check_vma=False))
    gs_flat = tuple(g.reshape((-1,) + tuple(s[1:]))
                    for g, s in zip(g_all, [(8,) + tuple(sh)
                                            for sh in shapes]))
    acc = [jnp.zeros(s, jnp.float32) for s in shapes]
    errs = []
    for t in range(steps):
        out, state = f(gs_flat, state)
        acc = [a + o for a, o in zip(acc, out)]
        errs.append(max(
            float(jnp.abs(a / (t + 1) - m).max()
                  / (jnp.abs(m).max() + 1e-9))
            for a, m in zip(acc, true_mean)))
    return errs, plan


def test_none_codec_is_bit_exact(hvd, mesh8):
    """compressed_allreduce with the none codec == today's fused path,
    byte for byte."""
    codec = C.NoneCodec()
    rng = np.random.RandomState(5)
    g_all = [jnp.asarray(rng.randn(8, *s), jnp.float32) for s in _SHAPES]
    proto = [jax.ShapeDtypeStruct(s, jnp.float32) for s in _SHAPES]
    plan = fusion.make_reduce_scatter_plan(proto, 8)

    def via_codec(gs):
        out, _ = C.compressed_allreduce(list(gs), "data", codec,
                                        plan=plan, state=None, mean=True)
        return tuple(out)

    def via_fused(gs):
        shards, plan_ = fusion.fused_reduce_scatter(list(gs), "data",
                                                    mean=True, plan=plan)
        return tuple(fusion.fused_all_gather(shards, plan_, "data"))

    def run(f):
        fn = jax.jit(jax.shard_map(
            f, mesh=mesh8,
            in_specs=(tuple(P("data") for _ in _SHAPES),),
            out_specs=tuple(P() for _ in _SHAPES), check_vma=False))
        return fn(tuple(g.reshape((-1,) + tuple(s[1:]))
                        for g, s in zip(g_all,
                                        [(8,) + tuple(sh)
                                         for sh in _SHAPES])))

    for a, b in zip(run(via_codec), run(via_fused)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cast_codecs_bounded_error(hvd, mesh8):
    for spec, tol in (("bf16", 0.02), ("fp16", 0.005)):
        errs, _ = _ef_harness(mesh8, spec, steps=3)
        assert errs[-1] < tol, (spec, errs)


def test_int8_error_feedback_converges_to_true_mean(hvd, mesh8):
    errs, _ = _ef_harness(mesh8, "int8", steps=15)
    # lossy single step, but the cumulative mean closes in ~1/t
    assert errs[0] > errs[-1] * 3
    assert errs[-1] < 5e-3, errs


def test_powersgd_error_feedback_converges(hvd, mesh8):
    errs, plan = _ef_harness(mesh8, "powersgd:2", steps=20)
    # the (16, 8) leaf got a dedicated low-rank bucket
    assert len(plan.lowrank) == 1
    b = plan.lowrank[0]
    assert plan.bucket_leaf_shape(b) == (16, 8)
    # rank-2 transport of a full-rank random matrix: heavily lossy at
    # step 1, EF + warm-started factors close the cumulative gap
    assert errs[-1] < errs[0] / 3
    assert errs[-1] < 0.25, errs


def test_compression_telemetry_series(hvd, mesh8):
    from horovod_tpu import telemetry
    from horovod_tpu.telemetry import aggregate
    telemetry.registry().clear()
    telemetry.configure(enabled_flag=True)
    try:
        _ef_harness(mesh8, "int8", steps=1)
        snap = telemetry.metrics_snapshot()
        for name in ("hvd_compression_bytes_in_total",
                     "hvd_compression_bytes_out_total",
                     "hvd_compression_ratio",
                     "hvd_compression_encode_seconds_total",
                     "hvd_collective_bytes_total"):
            assert name in snap, name
        bytes_in = aggregate.counter_total(
            snap, "hvd_compression_bytes_in_total", {"codec": "int8"})
        bytes_out = aggregate.counter_total(
            snap, "hvd_compression_bytes_out_total", {"codec": "int8"})
        assert 0 < bytes_out < bytes_in
        # the headline counter: logical wire payload, labelled by codec
        wire = aggregate.counter_total(
            snap, "hvd_collective_bytes_total",
            {"plane": "spmd", "kind": "reduce_scatter", "codec": "int8"})
        assert 0 < wire < bytes_in
    finally:
        telemetry.configure(enabled_flag=False)
        telemetry.registry().clear()


@pytest.mark.parametrize("codec,floor", [("int8", 3.0), ("bf16", 1.9),
                                         ("fp16", 1.9)])
def test_codec_wire_byte_ratio(hvd, mesh8, codec, floor):
    """The transport saving docs/performance.md promises, on counts: the
    logical wire bytes ``hvd_collective_bytes_total`` books at trace time
    for one compressed allreduce of a layer-sized gradient, against the
    uncompressed wire.  int8 packs four f32 bytes into about one (less
    the per-bucket scales), the casts halve them."""
    from horovod_tpu import telemetry
    from horovod_tpu.telemetry import aggregate

    def wire_bytes(spec):
        telemetry.registry().clear()
        _ef_harness(mesh8, spec, steps=1,
                    shapes=[(256, 512), (512,), (512, 64)])
        snap = telemetry.metrics_snapshot()
        return sum(aggregate.counter_total(
            snap, "hvd_collective_bytes_total",
            {"plane": "spmd", "kind": kind,
             "codec": C.resolve_codec(spec).name})
            for kind in ("reduce_scatter", "all_gather"))

    telemetry.configure(enabled_flag=True)
    try:
        plain, packed = wire_bytes("none"), wire_bytes(codec)
    finally:
        telemetry.configure(enabled_flag=False)
        telemetry.registry().clear()
    assert packed > 0 and plain / packed >= floor, (plain, packed)


# ---------------------------------------------------------------------------
# Satellite 3 (cont.): residual state survives an elastic np change
# ---------------------------------------------------------------------------

def _pending_mean_leaves(codec, plan, state):
    """The codec's pending reduce-scatter correction in MEAN units,
    mapped back to per-leaf vectors (the reshard invariant)."""
    n = plan.axis_size
    pend = []
    for b in range(len(plan.buckets)):
        if state.rs[b] is not None:
            pend.append(state.rs[b].reshape(n, -1).sum(0) / n)
        else:
            pend.append(jnp.zeros((plan.padded_size(b),), jnp.float32))
    return plan.split(pend)


def test_int8_reshard_preserves_pending_error():
    codec = C.Int8Codec()
    proto = [jax.ShapeDtypeStruct(s, jnp.float32) for s in _SHAPES]
    old_plan = fusion.make_reduce_scatter_plan(proto, 8, codec=codec)
    new_plan = fusion.make_reduce_scatter_plan(proto, 4, codec=codec)
    rng = np.random.RandomState(7)
    state = codec.init_state(old_plan)
    state = C.CodecState(
        tuple(jnp.asarray(rng.randn(*r.shape), jnp.float32)
              if r is not None else None for r in state.rs),
        tuple(jnp.asarray(rng.randn(*a.shape), jnp.float32)
              if a is not None else None for a in state.ag),
        state.factors)

    new_state = codec.reshard_state(state, old_plan, new_plan)

    old_pend = _pending_mean_leaves(codec, old_plan, state)
    new_pend = _pending_mean_leaves(codec, new_plan, new_state)
    for a, b in zip(old_pend, new_pend):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    # all-gather residual is one global vector in update units: re-bucketed
    old_ag = old_plan.split([state.ag[b] for b in range(len(old_plan.buckets))])
    new_ag = new_plan.split([new_state.ag[b]
                             for b in range(len(new_plan.buckets))])
    for a, b in zip(old_ag, new_ag):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_powersgd_reshard_carries_factors():
    codec = C.PowerSGDCodec(rank=2)
    proto = [jax.ShapeDtypeStruct(s, jnp.float32) for s in _SHAPES]
    old_plan = fusion.make_reduce_scatter_plan(proto, 8, codec=codec)
    new_plan = fusion.make_reduce_scatter_plan(proto, 4, codec=codec)
    assert len(old_plan.lowrank) == len(new_plan.lowrank) == 1
    state = codec.init_state(old_plan)
    # make the warm-started factor distinguishable from a fresh init
    b_old = old_plan.lowrank[0]
    marked = list(state.factors)
    marked[b_old] = state.factors[b_old] + 17.0
    state = C.CodecState(state.rs, state.ag, marked)
    new_state = codec.reshard_state(state, old_plan, new_plan)
    b_new = new_plan.lowrank[0]
    np.testing.assert_allclose(np.asarray(new_state.factors[b_new]),
                               np.asarray(marked[b_old]))


def test_zero_reshard_state_carries_wire(hvd, mesh8):
    """`zero.reshard_state` parity: an 8-way int8 state re-bucketed for a
    4-way world keeps the pending error feedback."""
    import optax
    from horovod_tpu.parallel import zero
    params = {"w": jnp.arange(24, dtype=jnp.float32).reshape(6, 4) * 0.1,
              "b": jnp.ones((5,), jnp.float32)}
    z8 = zero.ShardedOptimizer(optax.adam(1e-2), "data", axis_size=8,
                               compression="int8")
    z4 = zero.ShardedOptimizer(optax.adam(1e-2), "data", axis_size=4,
                               compression="int8")
    s8, s4 = z8.init(params), z4.init(params)
    rng = np.random.RandomState(11)
    wire = C.CodecState(
        tuple(jnp.asarray(rng.randn(*r.shape), jnp.float32)
              if r is not None else None for r in s8.wire.rs),
        tuple(jnp.asarray(rng.randn(*a.shape), jnp.float32)
              if a is not None else None for a in s8.wire.ag),
        s8.wire.factors)
    s8 = zero.ZeroShardedState(s8.inner, s8.plan, s8.treedef, s8.optimizer,
                               wire=wire, codec=s8.codec)
    out = zero.reshard_state(s8, like=s4)
    assert out.wire is not None
    old_pend = _pending_mean_leaves(z8.codec, s8.plan, s8.wire)
    new_pend = _pending_mean_leaves(z4.codec, out.plan, out.wire)
    for a, b in zip(old_pend, new_pend):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Training-step trajectory equivalence (the acceptance property in small)
# ---------------------------------------------------------------------------

def _params(seed=0):
    k = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(k, 3)
    return {
        "dense1": {"w": jax.random.normal(k1, (13, 7)) * 0.3,
                   "b": jnp.zeros((7,))},
        "dense2": {"w": jax.random.normal(k2, (7, 3)) * 0.3},
        "scale": jax.random.normal(k3, (5,)) * 0.1,
    }


def _loss_fn(p, batch):
    x, y = batch
    h = jnp.tanh(x @ p["dense1"]["w"] + p["dense1"]["b"])
    out = h @ p["dense2"]["w"] * jnp.mean(p["scale"])
    return jnp.mean((out - y) ** 2)


def _batch(i, n=16):
    x = jax.random.normal(jax.random.PRNGKey(1000 + i), (n, 13))
    y = jax.random.normal(jax.random.PRNGKey(2000 + i), (n, 3))
    return x, y


def _run_steps(step, params, steps=8):
    p = jax.tree_util.tree_map(jnp.array, params)
    s = step.init(p)
    losses = []
    for i in range(steps):
        p, s, loss = step(p, s, _batch(i))
        losses.append(float(loss))
    return p, losses


@pytest.mark.parametrize("codec", ["int8", "powersgd:2"])
def test_zero_step_with_codec_tracks_none(hvd, mesh8, codec):
    opt = optax.adam(1e-2)
    params = _params()
    base = hvd_mod.make_training_step(_loss_fn, opt, mesh8,
                                      shard_optimizer=True)
    comp = hvd_mod.make_training_step(_loss_fn, opt, mesh8,
                                      shard_optimizer=True,
                                      compression=codec)
    _, l_base = _run_steps(base, params)
    _, l_comp = _run_steps(comp, params)
    assert all(np.isfinite(l_comp))
    # loss parity at equal steps: EF keeps the trajectory within a few %
    for a, b in zip(l_base[2:], l_comp[2:]):
        assert abs(a - b) <= 0.05 * abs(a) + 1e-3, (l_base, l_comp)


def test_replicated_step_with_stateful_codec(hvd, mesh8):
    """make_training_step without shard_optimizer engages the compressed
    replicated path for stateful codecs; trajectory tracks uncompressed."""
    opt = optax.adam(1e-2)
    params = _params(2)
    base = hvd_mod.make_training_step(_loss_fn, opt, mesh8)
    comp = hvd_mod.make_training_step(_loss_fn, opt, mesh8,
                                      compression="int8")
    assert comp.codec.name == "int8"
    _, l_base = _run_steps(base, params)
    _, l_comp = _run_steps(comp, params)
    assert all(np.isfinite(l_comp))
    for a, b in zip(l_base[2:], l_comp[2:]):
        assert abs(a - b) <= 0.05 * abs(a) + 1e-3, (l_base, l_comp)


def test_replicated_step_requires_init_first(hvd, mesh8):
    step = hvd_mod.make_training_step(_loss_fn, optax.adam(1e-2), mesh8,
                                      compression="int8")
    with pytest.raises(RuntimeError, match="step.init"):
        step(_params(), (None, None), _batch(0))
