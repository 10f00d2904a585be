"""Correctness tests for the parallelism modules (8-device CPU mesh).

Every SP/TP/PP/EP implementation is checked against a single-device
numerical oracle — the strongest form of correctness test these admit.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _mesh(hvd, axes, shape):
    from horovod_tpu.topology import build_mesh
    return build_mesh(axes=axes, shape=shape)


# ---------------------------------------------------------------------------
# Sequence parallelism
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_local(hvd, causal):
    from horovod_tpu.parallel.sequence import local_attention, ring_attention

    mesh = _mesh(hvd, ("seq",), (8,))
    b, t, h, d = 2, 32, 4, 16
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
               for _ in range(3))

    oracle = local_attention(q, k, v, causal=causal)

    ring = jax.jit(jax.shard_map(
        functools.partial(ring_attention, axis_name="seq", causal=causal),
        mesh=mesh, in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq")))
    out = ring(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_attention_matches_local(hvd):
    from horovod_tpu.parallel.sequence import (local_attention,
                                               ulysses_attention)

    mesh = _mesh(hvd, ("seq",), (8,))
    b, t, h, d = 2, 32, 8, 16
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
               for _ in range(3))
    oracle = local_attention(q, k, v, causal=True)
    uly = jax.jit(jax.shard_map(
        functools.partial(ulysses_attention, axis_name="seq", causal=True),
        mesh=mesh, in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq")))
    out = uly(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_gradients(hvd):
    """d(sum(attn))/dq must match the oracle's — exercises ppermute
    transpose and the online-softmax backward."""
    from horovod_tpu.parallel.sequence import local_attention, ring_attention

    b, t, h, d = 1, 16, 2, 8
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
               for _ in range(3))

    g_oracle = jax.grad(lambda q: local_attention(q, k, v).sum())(q)

    devs = jax.devices()[:4]
    mesh4 = Mesh(np.array(devs), ("seq",))
    ring_loss = jax.shard_map(
        lambda q, k, v: lax.psum(
            ring_attention(q, k, v, "seq").sum(), "seq"),
        mesh=mesh4, in_specs=(P(None, "seq"),) * 3, out_specs=P(),
        check_vma=True)
    g_ring = jax.jit(jax.grad(lambda q: ring_loss(q, k, v)))(q)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_oracle),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Tensor parallelism
# ---------------------------------------------------------------------------

def test_tp_mlp_matches_dense(hvd):
    """Column->row parallel MLP == dense MLP, values AND gradients."""
    from horovod_tpu.parallel.tensor import (column_parallel, region_input,
                                             row_parallel)

    mesh = _mesh(hvd, ("model",), (8,))
    d, f, n = 16, 64, 4
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((d, f)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((f, d)) * 0.1, jnp.float32)

    def dense(x, w1, w2):
        return jax.nn.gelu(x @ w1) @ w2

    def tp_fwd(x, w1_l, w2_l):
        u = jax.nn.gelu(column_parallel(x, w1_l, "model"))
        return row_parallel(u, w2_l, "model")

    tp_fn = jax.jit(jax.shard_map(
        tp_fwd, mesh=mesh,
        in_specs=(P(), P(None, "model"), P("model", None)),
        out_specs=P()))
    np.testing.assert_allclose(np.asarray(tp_fn(x, w1, w2)),
                               np.asarray(dense(x, w1, w2)),
                               rtol=2e-5, atol=2e-5)

    # Gradients, computed INSIDE shard_map (the manual-SPMD pattern the
    # boundary operators are designed for: each device differentiates its
    # local program; region_input's backward psum merges branch gradients
    # exactly once).
    g_dense = jax.grad(lambda x, w1, w2: dense(x, w1, w2).sum(),
                       argnums=(0, 1, 2))(x, w1, w2)

    def local_grads(x, a, b):
        return jax.grad(lambda *args: tp_fwd(*args).sum(),
                        argnums=(0, 1, 2))(x, a, b)

    g_tp = jax.jit(jax.shard_map(
        local_grads, mesh=mesh,
        in_specs=(P(), P(None, "model"), P("model", None)),
        out_specs=(P(), P(None, "model"), P("model", None)),
        check_vma=True))(x, w1, w2)
    for got, want in zip(g_tp, g_dense):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Hierarchical collectives
# ---------------------------------------------------------------------------

def test_hierarchical_allreduce_matches_flat_psum(hvd):
    from horovod_tpu.parallel.hierarchical import hierarchical_allreduce

    mesh = _mesh(hvd, ("dcn", "ici"), (2, 4))
    x = jnp.arange(2 * 4 * 5, dtype=jnp.float32).reshape(8, 5)

    def flat(x):
        return lax.psum(x, ("dcn", "ici"))

    def hier(x):
        return hierarchical_allreduce(x, ici_axis="ici", dcn_axis="dcn")

    args = dict(mesh=mesh, in_specs=P(("dcn", "ici")),
                out_specs=P(("dcn", "ici")), check_vma=True)
    a = jax.jit(jax.shard_map(flat, **args))(x)
    b = jax.jit(jax.shard_map(hier, **args))(x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_hierarchical_allreduce_uneven_payload(hvd):
    """Payload not divisible by the ICI size exercises the pad path."""
    from horovod_tpu.parallel.hierarchical import hierarchical_allreduce

    mesh = _mesh(hvd, ("dcn", "ici"), (2, 4))
    x = jnp.arange(7, dtype=jnp.float32)   # 7 % 4 != 0

    out = jax.jit(jax.shard_map(
        lambda x: hierarchical_allreduce(x, "ici", "dcn", average=True),
        mesh=mesh, in_specs=P(), out_specs=P()))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=1e-6)


# ---------------------------------------------------------------------------
# Pipeline parallelism
# ---------------------------------------------------------------------------

def test_pipeline_matches_sequential(hvd):
    from horovod_tpu.parallel.pipeline import (pipeline_apply,
                                               stack_stage_params)

    mesh = _mesh(hvd, ("pipe",), (4,))
    d, mb, m = 8, 2, 6
    rng = np.random.default_rng(4)
    stage_ws = [jnp.asarray(rng.standard_normal((d, d)) * 0.3, jnp.float32)
                for _ in range(4)]
    stacked = stack_stage_params([{"w": w} for w in stage_ws])
    xs = jnp.asarray(rng.standard_normal((m, mb, d)), jnp.float32)

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"][0])

    # Oracle: apply the 4 stages sequentially to each microbatch.
    want = xs
    for w in stage_ws:
        want = jnp.tanh(want @ w)

    run = jax.jit(jax.shard_map(
        functools.partial(pipeline_apply, stage_fn, axis_name="pipe"),
        mesh=mesh, in_specs=({"w": P("pipe", None, None)}, P()),
        out_specs=P()))
    got = run(stacked, xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_gradients_flow(hvd):
    from horovod_tpu.parallel.pipeline import (pipeline_apply,
                                               stack_stage_params)

    mesh = _mesh(hvd, ("pipe",), (2,))
    d, mb, m = 4, 2, 3
    rng = np.random.default_rng(5)
    stage_ws = [jnp.asarray(rng.standard_normal((d, d)) * 0.3, jnp.float32)
                for _ in range(2)]
    stacked = stack_stage_params([{"w": w} for w in stage_ws])
    xs = jnp.asarray(rng.standard_normal((m, mb, d)), jnp.float32)

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"][0])

    def oracle_loss(ws, xs):
        y = xs
        for i in range(2):
            y = jnp.tanh(y @ ws["w"][i])
        return jnp.sum(y ** 2)

    def pipe_loss(ws, xs):
        y = pipeline_apply(stage_fn, ws, xs, axis_name="pipe")
        return jnp.sum(y ** 2)

    g_oracle = jax.grad(oracle_loss)(stacked, xs)
    pipe = jax.shard_map(
        pipe_loss, mesh=mesh,
        in_specs=({"w": P("pipe", None, None)}, P()), out_specs=P(),
        check_vma=True)
    g_pipe = jax.jit(jax.grad(lambda ws: pipe(ws, xs)))(stacked)
    np.testing.assert_allclose(np.asarray(g_pipe["w"]),
                               np.asarray(g_oracle["w"]),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Expert parallelism (MoE)
# ---------------------------------------------------------------------------

def test_top1_routing(hvd):
    """Deterministic routing unit test: forced assignments, capacity
    accounting, overflow drops."""
    from horovod_tpu.parallel.expert import top1_routing

    t, e = 32, 4
    router_assign = np.arange(t) % e
    logits = jax.nn.one_hot(jnp.asarray(router_assign), e) * 50.0
    dispatch, combine = top1_routing(logits, capacity=t)
    assert dispatch.shape == (t, e, t)
    # every token dispatched exactly once; gate ~1.0 at this margin
    np.testing.assert_allclose(np.asarray(dispatch.sum(axis=(1, 2))), 1.0)
    np.testing.assert_allclose(np.asarray(combine.sum(axis=(1, 2))), 1.0,
                               rtol=1e-5)
    # capacity 1: only the first token per expert survives
    dispatch, _ = top1_routing(logits, capacity=1)
    kept = np.asarray(dispatch.sum(axis=(1, 2)))
    assert kept.sum() == e
    np.testing.assert_allclose(kept[:e], 1.0)
    np.testing.assert_allclose(kept[e:], 0.0)


def test_moe_layer_end_to_end(hvd):
    """Full distributed MoE: zero router => every token to expert 0; with
    identity experts output == input * gate (gate = 1/E uniform)."""
    from horovod_tpu.parallel.expert import moe_layer

    mesh = _mesh(hvd, ("expert",), (4,))
    t, d = 8, 6
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((4 * t, d)), jnp.float32)

    def expert_fn(params, tokens):
        del params
        return tokens

    run = jax.jit(jax.shard_map(
        lambda x: moe_layer(x, jnp.zeros((d, 4)), expert_fn, {},
                            axis_name="expert", capacity_factor=4.0),
        mesh=mesh, in_specs=P("expert"), out_specs=P("expert"),
        check_vma=True))
    out = run(x)
    # uniform router: gate = 1/4 for the argmax expert, identity expert
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 0.25,
                               rtol=1e-5, atol=1e-6)


def test_top2_routing(hvd):
    """GShard top-2: both choices dispatched with renormalized gates;
    second choices queue behind firsts and drop first at capacity."""
    from horovod_tpu.parallel.expert import top2_routing

    t, e = 8, 4
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.standard_normal((t, e)), jnp.float32)
    dispatch, combine = top2_routing(logits, capacity=2 * t)

    probs = np.asarray(jax.nn.softmax(logits, -1))
    i1 = probs.argmax(-1)
    masked = probs * (1 - np.eye(e)[i1])
    i2 = masked.argmax(-1)
    # two dispatches per token; gates renormalize to 1
    np.testing.assert_allclose(np.asarray(dispatch.sum(axis=(1, 2))), 2.0)
    np.testing.assert_allclose(np.asarray(combine.sum(axis=(1, 2))), 1.0,
                               rtol=1e-5)
    # dispatched exactly to the two argmax experts
    per_expert = np.asarray(dispatch.sum(axis=2))          # [T, E]
    for tok in range(t):
        assert per_expert[tok, i1[tok]] == 1.0
        assert per_expert[tok, i2[tok]] == 1.0

    # capacity 1: at each expert only ONE slot — and a first choice
    # outranks any earlier-arriving second choice
    d1, _ = top2_routing(logits, capacity=1)
    kept = np.asarray(d1.sum(axis=2))                      # [T, E]
    for ex in range(e):
        takers = np.nonzero(kept[:, ex])[0]
        assert len(takers) <= 1
        if len(takers) == 1 and (i1 == ex).any():
            # the surviving slot belongs to the FIRST first-choice token
            assert takers[0] == np.nonzero(i1 == ex)[0][0]


def test_moe_layer_top2_matches_dense(hvd):
    """Distributed top-2 MoE output equals the dense per-token oracle
    (gate1*E_i1(x) + gate2*E_i2(x)) when capacity admits everything;
    experts scale by (expert_index + 1) so wrong routing is visible."""
    from horovod_tpu.parallel.expert import moe_layer

    mesh = _mesh(hvd, ("expert",), (4,))
    t, d, e = 8, 6, 4
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((4 * t, d)), jnp.float32)
    router_w = jnp.asarray(rng.standard_normal((d, e)), jnp.float32)

    def expert_fn(params, tokens):
        # params: this chip's scale (expert_index + 1)
        return tokens * params

    scales = jnp.arange(1.0, e + 1.0)
    run = jax.jit(jax.shard_map(
        lambda x, s: moe_layer(x, router_w, expert_fn, s,
                               axis_name="expert", capacity_factor=8.0,
                               router="top2"),
        mesh=mesh, in_specs=(P("expert"), P("expert")),
        out_specs=P("expert"), check_vma=True))
    out = np.asarray(run(x, scales))

    probs = np.asarray(jax.nn.softmax(np.asarray(x) @ np.asarray(router_w),
                                      -1))
    i1 = probs.argmax(-1)
    p1 = probs[np.arange(4 * t), i1]
    masked = probs * (1 - np.eye(e)[i1])
    i2 = masked.argmax(-1)
    p2 = masked[np.arange(4 * t), i2]
    g1, g2 = p1 / (p1 + p2 + 1e-9), p2 / (p1 + p2 + 1e-9)
    want = (g1[:, None] * (i1 + 1)[:, None] * np.asarray(x) +
            g2[:, None] * (i2 + 1)[:, None] * np.asarray(x))
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Transformer LM end-to-end
# ---------------------------------------------------------------------------

def _tiny_cfg():
    from horovod_tpu.models.transformer import TransformerConfig
    return TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                             n_layers=2, d_ff=64, max_seq=64,
                             dtype=jnp.float32)


def test_transformer_tp_sp_matches_single_device(hvd):
    """forward() under model x seq sharding == single-device forward —
    the composition test for TP boundaries + ring attention."""
    import functools as ft

    from horovod_tpu.models import transformer as tfm

    cfg = _tiny_cfg()
    mesh = _mesh(hvd, ("model", "seq"), (2, 4))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(8).integers(0, cfg.vocab_size, (2, 32)),
        jnp.int32)

    oracle = tfm.forward(params, tokens, cfg)

    specs = tfm.param_specs(cfg, "model")
    fwd = jax.jit(jax.shard_map(
        ft.partial(tfm.forward, cfg=cfg, model_axis="model",
                   seq_axis="seq"),
        mesh=mesh, in_specs=(specs, P(None, "seq")),
        out_specs=P(None, "seq")))
    out = fwd(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=5e-4, atol=5e-4)


def test_transformer_train_step_dp_tp_sp(hvd):
    """Full 3-axis training step (2 data x 2 model x 2 seq): runs, loss
    finite and decreasing."""
    import optax

    from horovod_tpu.models import transformer as tfm

    cfg = _tiny_cfg()
    mesh = _mesh(hvd, ("data", "model", "seq"), (2, 2, 2))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.sgd(0.1)
    opt_state = opt.init(params)

    step, specs, opt_specs = tfm.make_train_step(
        cfg, opt, mesh, data_axis="data", model_axis="model",
        seq_axis="seq")

    rng = np.random.default_rng(9)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
    labels = jnp.asarray(np.roll(np.asarray(tokens), -1, axis=1), jnp.int32)

    params = jax.device_put(params, jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs))
    opt_state = jax.device_put(opt_state, jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), opt_specs,
        is_leaf=lambda x: isinstance(x, P)))
    data_sharding = NamedSharding(mesh, P("data", "seq"))
    tokens = jax.device_put(tokens, data_sharding)
    labels = jax.device_put(labels, data_sharding)

    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        losses.append(float(np.asarray(loss)))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("axes,shape,kw", [
    (("data",), (4,), {"attention": "local"}),
    (("data", "model", "seq"), (2, 2, 2),
     {"model_axis": "model", "seq_axis": "seq", "attention": "ring"}),
])
def test_transformer_train_step_matches_single_device(hvd, axes, shape, kw):
    """The sharded training step takes the SAME steps as one device on
    the same global batch.  "Loss finite and decreasing" cannot see a
    gradient that is N times too large — which is what autodiff under
    check_vma=True produced for replicated params before the step pinned
    them to vary over the gradient axes (models/transformer.py)."""
    import warnings

    import optax

    from horovod_tpu.models import transformer as tfm

    cfg = _tiny_cfg()
    toks = np.random.default_rng(9).integers(0, cfg.vocab_size, (4, 33),
                                             dtype=np.int32)

    def losses(axes, shape, model_axis=None, seq_axis=None,
               attention="local"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")      # underfilled-mesh notice
            mesh = _mesh(hvd, axes, shape)
        opt = optax.sgd(0.1, momentum=0.9)
        step, specs, opt_specs = tfm.make_train_step(
            cfg, opt, mesh, model_axis=model_axis, seq_axis=seq_axis,
            attention=attention)
        params = jax.device_put(
            tfm.init_params(jax.random.PRNGKey(0), cfg),
            jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs))
        opt_state = jax.device_put(
            opt.init(params), jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), opt_specs,
                is_leaf=lambda x: isinstance(x, P)))
        data = NamedSharding(mesh, P("data", seq_axis))
        tokens = jax.device_put(toks[:, :-1], data)
        labels = jax.device_put(toks[:, 1:], data)
        out = []
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, tokens,
                                           labels)
            out.append(float(np.asarray(loss)))
        return out

    np.testing.assert_allclose(losses(axes, shape, **kw),
                               losses(("data",), (1,)), rtol=1e-5)


def test_sharding_aware_clip_matches_unsharded_oracle(hvd):
    """parallel.tensor.clip_by_global_norm under a 2-way TP shard_map must
    reproduce optax's single-device global-norm clip exactly."""
    import optax

    from horovod_tpu.parallel.tensor import clip_by_global_norm, shard_dim

    mesh = _mesh(hvd, ("model",), (2,))
    rng = np.random.default_rng(3)
    grads = {
        "col": jnp.asarray(rng.standard_normal((8, 16))),   # col-sharded
        "row": jnp.asarray(rng.standard_normal((16, 8))),   # row-sharded
        "rep": jnp.asarray(rng.standard_normal((8,))),      # replicated
    }
    specs = {"col": P(None, "model"), "row": P("model", None), "rep": P()}

    oracle, _ = optax.clip_by_global_norm(0.5).update(
        grads, optax.EmptyState())

    clip = clip_by_global_norm(0.5, specs)

    def body(g):
        out, _ = clip.update(g, clip.init(None))
        return out

    clipped = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(specs,), out_specs=specs))(grads)
    for k in grads:
        np.testing.assert_allclose(np.asarray(clipped[k]),
                                   np.asarray(oracle[k]), rtol=1e-5,
                                   atol=1e-6, err_msg=k)


def test_train_step_adam_tp(hvd):
    """Adam (param-like opt state) + TP: opt-state specs must align by
    optimizer structure even when distinct params share a shape
    (vocab == d_ff collision regression)."""
    import optax

    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                d_ff=64, n_layers=1, max_seq=32,
                                dtype=jnp.float32)
    mesh = _mesh(hvd, ("data", "model"), (2, 2))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adam(1e-2)
    step, specs, opt_specs = tfm.make_train_step(
        cfg, opt, mesh, data_axis="data", model_axis="model")
    params = jax.device_put(params, jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs))
    opt_state = jax.device_put(opt.init(params), jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), opt_specs,
        is_leaf=lambda x: isinstance(x, P)))
    rng = np.random.default_rng(11)
    tokens = jnp.asarray(rng.integers(0, 64, (4, 32)), jnp.int32)
    labels = jnp.asarray(np.roll(np.asarray(tokens), -1, axis=1), jnp.int32)
    sh = NamedSharding(mesh, P("data"))
    tokens, labels = jax.device_put(tokens, sh), jax.device_put(labels, sh)
    losses = []
    for _ in range(4):
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        losses.append(float(np.asarray(loss)))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses


def test_hierarchical_allgather(hvd):
    """Two-level allgather == flat allgather over the composed mesh
    (reference MPIHierarchicalAllgather semantics)."""
    mesh = _mesh(hvd, ("dcn", "ici"), (2, 4))
    per = 3

    def body(x):
        from horovod_tpu.parallel.hierarchical import hierarchical_allgather
        return hierarchical_allgather(x, "ici", "dcn")

    x = jnp.arange(8 * per * 2, dtype=jnp.float32).reshape(8 * per, 2)
    # check_vma=True is the point: the masked-psum gather form makes the
    # output provably replicated, so it flows through P().
    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(("dcn", "ici")),
        out_specs=P(), check_vma=True))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_transformer_decode_under_tp(hvd):
    """KV-cache decode with 2-way tensor parallelism matches the
    single-device decode oracle."""
    import functools as ft

    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                d_ff=64, n_layers=1, max_seq=8,
                                dtype=jnp.float32)
    mesh = _mesh(hvd, ("model",), (2,))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok = jnp.asarray([5, 9], jnp.int32)

    cache0 = tfm.init_kv_cache(cfg, 2, 4)
    oracle, _ = tfm.decode_step(params, tok, cache0, 0, cfg)

    specs = tfm.param_specs(cfg, "model")
    # GLOBAL-shaped cache; in_specs shards the head dim (the
    # model_axis_size arg is for manually pre-sharded callers).
    cache_tp = tfm.init_kv_cache(cfg, 2, 4)
    cache_spec = [{"k": P(None, None, "model"),
                   "v": P(None, None, "model")}
                  for _ in range(cfg.n_layers)]
    step = jax.jit(jax.shard_map(
        ft.partial(tfm.decode_step, pos=0, cfg=cfg, model_axis="model"),
        mesh=mesh, in_specs=(specs, P(), cache_spec),
        out_specs=(P(), cache_spec), check_vma=False))
    logits, _ = step(params, tok, cache_tp)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(oracle),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_transformer_pipelined_matches_forward(hvd):
    """forward_pipelined over 4 pipe stages == plain forward (values and
    gradients) — PP composed with a real model, not just a toy stage."""
    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                d_ff=64, n_layers=4, max_seq=16,
                                dtype=jnp.float32)
    mesh = _mesh(hvd, ("pipe",), (4,))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, 64, (4, 16)), jnp.int32)

    oracle = tfm.forward(params, tokens, cfg, attention="local")

    stacked = tfm.stack_layer_params(params, 4)
    sspec = {k: tfm.stacked_layer_specs("pipe") for k in stacked}
    base = {k: v for k, v in params.items() if k != "layers"}
    base_spec = {k: P() for k in base}

    def fwd(base_p, stk, toks):
        p = dict(base_p, layers=[])
        return tfm.forward_pipelined(p, stk, toks, cfg, "pipe",
                                     n_microbatches=2)

    out = jax.jit(jax.shard_map(
        fwd, mesh=mesh, in_specs=(base_spec, sspec, P()),
        out_specs=P(), check_vma=False))(base, stacked, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-4, atol=2e-4)

    # Gradients flow through the pipeline to every stage's weights.
    def loss(stk):
        out = jax.shard_map(
            fwd, mesh=mesh, in_specs=(base_spec, sspec, P()),
            out_specs=P(), check_vma=False)(base, stk, tokens)
        return jnp.mean(jnp.square(out))

    g = jax.jit(jax.grad(loss))(stacked)
    for k, leaf in g.items():
        norms = [float(jnp.linalg.norm(leaf[s])) for s in range(4)]
        assert all(n > 0 for n in norms), (k, norms)


@pytest.mark.slow
def test_transformer_pipelined_gradients_exact(hvd):
    """Gradients THROUGH the pipeline (base + every stage) equal the
    plain forward's gradients — the property make_train_step_pipelined
    relies on."""
    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                                d_ff=32, n_layers=4, max_seq=8,
                                dtype=jnp.float32)
    mesh = _mesh(hvd, ("pipe",), (4,))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, 32, (4, 8)), jnp.int32)
    labels = jnp.asarray(np.roll(np.asarray(tokens), -1, 1), jnp.int32)

    g_oracle = jax.grad(
        lambda p: tfm.loss_fn(p, tokens, labels, cfg,
                              attention="local"))(params)

    split = tfm.split_pipeline_params(params, 4)
    base, stacked = split["base"], split["stacked"]
    sspec = {k: P("pipe") for k in stacked}
    bspec = {k: P() for k in base}

    def loss_pp(bp, stk):
        logits = jax.shard_map(
            lambda b_, s_, t_: tfm.forward_pipelined(
                dict(b_, layers=[]), s_, t_, cfg, "pipe",
                n_microbatches=2),
            mesh=mesh, in_specs=(bspec, sspec, P()), out_specs=P(),
            check_vma=False)(bp, stk, tokens)
        return tfm.xent(logits, labels)

    g_base, g_stk = jax.jit(jax.grad(loss_pp, argnums=(0, 1)))(base,
                                                               stacked)
    for k in base:
        np.testing.assert_allclose(np.asarray(g_base[k]),
                                   np.asarray(g_oracle[k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)
    oracle_stk = tfm.stack_layer_params(g_oracle, 4)
    for k in g_stk:
        np.testing.assert_allclose(np.asarray(g_stk[k]),
                                   np.asarray(oracle_stk[k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)


def test_make_train_step_pipelined(hvd):
    """The DPxPP train step runs and learns on a (data=2, pipe=4) mesh."""
    import optax

    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                                d_ff=32, n_layers=4, max_seq=8,
                                dtype=jnp.float32)
    mesh = _mesh(hvd, ("data", "pipe"), (2, 4))
    full = tfm.init_params(jax.random.PRNGKey(0), cfg)
    params = tfm.split_pipeline_params(full, 4)
    opt = optax.adam(3e-3)
    step, shardings = tfm.make_train_step_pipelined(
        cfg, opt, mesh, data_axis="data", pipe_axis="pipe")
    p_sh, opt_sh = shardings(params)
    params = {g: {k: jax.device_put(v, p_sh[g][k])
                  for k, v in params[g].items()} for g in params}
    opt_state = jax.device_put(opt.init(params), opt_sh)

    rng = np.random.default_rng(2)
    losses = []
    for i in range(8):
        start = rng.integers(0, 32, (4, 1))
        toks = (start + np.arange(9)) % 32     # learnable +1 language
        tokens = jnp.asarray(toks[:, :-1], jnp.int32)
        labels = jnp.asarray(toks[:, 1:], jnp.int32)
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        losses.append(float(np.asarray(loss)))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses


def test_pipeline_1f1b_matches_oracle(hvd):
    """1F1B loss AND gradients (stage params, aux head, microbatch inputs)
    equal the plain sequential computation — the same exact-gradient gate
    GPipe passes, on the hand-scheduled interleaved schedule."""
    from horovod_tpu.parallel.pipeline import (make_pipeline_1f1b_loss,
                                               stack_stage_params)

    mesh = _mesh(hvd, ("pipe",), (4,))
    d, mb, m = 8, 2, 6
    rng = np.random.default_rng(7)
    stage_ws = [jnp.asarray(rng.standard_normal((d, d)) * 0.3, jnp.float32)
                for _ in range(4)]
    stacked = stack_stage_params([{"w": w} for w in stage_ws])
    xs = jnp.asarray(rng.standard_normal((m, mb, d)), jnp.float32)
    tgts = jnp.asarray(rng.standard_normal((m, mb, d)), jnp.float32)
    aux = {"scale": jnp.asarray(rng.standard_normal((d,)), jnp.float32)}

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"][0])

    def loss_fn(y, tgt, aux):
        return jnp.mean((y * aux["scale"] - tgt) ** 2)

    def oracle(ws, aux, xs):
        y = xs
        for i in range(4):
            y = jnp.tanh(y @ ws["w"][i])
        per_mb = jnp.mean((y * aux["scale"] - tgts) ** 2, axis=(1, 2))
        return jnp.mean(per_mb)

    want_loss = oracle(stacked, aux, xs)
    g_want = jax.grad(oracle, argnums=(0, 1, 2))(stacked, aux, xs)

    f = make_pipeline_1f1b_loss(stage_fn, loss_fn, mesh,
                                stage_spec={"w": P("pipe", None, None)},
                                mb_spec=P(), axis_name="pipe")
    got_loss = jax.jit(f)(stacked, aux, xs, tgts)
    np.testing.assert_allclose(np.asarray(got_loss), np.asarray(want_loss),
                               rtol=2e-5, atol=2e-5)

    g_got = jax.jit(jax.grad(
        lambda ws, a, x: f(ws, a, x, tgts), argnums=(0, 1, 2)))(
            stacked, aux, xs)
    np.testing.assert_allclose(np.asarray(g_got[0]["w"]),
                               np.asarray(g_want[0]["w"]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(g_got[1]["scale"]),
                               np.asarray(g_want[1]["scale"]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(g_got[2]), np.asarray(g_want[2]),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("dp", [1, 2])
def test_train_step_1f1b_matches_gpipe(hvd, dp):
    """One SGD step under schedule='1f1b' produces the SAME params as
    schedule='gpipe' (=> identical exact gradients end-to-end), with and
    without a data axis."""
    import optax

    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                                d_ff=32, n_layers=4, max_seq=8,
                                dtype=jnp.float32)
    axes = ("data", "pipe") if dp > 1 else ("pipe",)
    shape = (dp, 4) if dp > 1 else (4,)
    mesh = _mesh(hvd, axes, shape)
    data_axis = "data" if dp > 1 else None
    full = tfm.init_params(jax.random.PRNGKey(0), cfg)
    params0 = tfm.split_pipeline_params(full, 4)
    opt = optax.sgd(0.1)

    rng = np.random.default_rng(3)
    toks = rng.integers(0, 32, (4, 9))
    tokens = jnp.asarray(toks[:, :-1], jnp.int32)
    labels = jnp.asarray(toks[:, 1:], jnp.int32)

    results = {}
    for sched in ("gpipe", "1f1b"):
        step, shardings = tfm.make_train_step_pipelined(
            cfg, opt, mesh, data_axis=data_axis, pipe_axis="pipe",
            n_microbatches=2, schedule=sched, donate=False)
        p_sh, opt_sh = shardings(params0)
        params = {g: {k: jax.device_put(v, p_sh[g][k])
                      for k, v in params0[g].items()} for g in params0}
        opt_state = jax.device_put(opt.init(params), opt_sh)
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        results[sched] = (jax.tree_util.tree_map(np.asarray, params),
                          float(np.asarray(loss)))

    assert np.isclose(results["gpipe"][1], results["1f1b"][1],
                      rtol=1e-5), (results["gpipe"][1], results["1f1b"][1])
    flat_g, _ = jax.tree_util.tree_flatten_with_path(results["gpipe"][0])
    flat_f = dict(jax.tree_util.tree_flatten_with_path(
        results["1f1b"][0])[0])
    for path, leaf in flat_g:
        np.testing.assert_allclose(
            flat_f[path], leaf, rtol=2e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.slow
def test_interleaved_pipeline_matches_oracle(hvd):
    """Interleaved (virtual-stage) schedule at P=4, v=2, M=8: loss AND
    every gradient (base + all 8 round-robin chunks) equal the plain
    forward's — the same exact-gradient gate the GPipe/1F1B schedules
    pass."""
    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                                d_ff=32, n_layers=8, max_seq=8,
                                dtype=jnp.float32)
    mesh = _mesh(hvd, ("pipe",), (4,))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, 32, (8, 8)), jnp.int32)
    labels = jnp.asarray(np.roll(np.asarray(tokens), -1, 1), jnp.int32)

    g_oracle = jax.grad(
        lambda p: tfm.loss_fn(p, tokens, labels, cfg,
                              attention="local"))(params)

    split = tfm.split_pipeline_params(params, 4, virtual=2)
    base, stacked = split["base"], split["stacked"]
    sspec = {k: P("pipe") for k in stacked}
    bspec = {k: P() for k in base}

    def loss_pp(bp, stk):
        logits = jax.shard_map(
            lambda b_, s_, t_: tfm.forward_pipelined(
                dict(b_, layers=[]), s_, t_, cfg, "pipe",
                n_microbatches=8, virtual=2),
            mesh=mesh, in_specs=(bspec, sspec, P()), out_specs=P(),
            check_vma=False)(bp, stk, tokens)
        return tfm.xent(logits, labels)

    loss = jax.jit(loss_pp)(base, stacked)
    oracle_loss = tfm.loss_fn(params, tokens, labels, cfg,
                              attention="local")
    np.testing.assert_allclose(float(loss), float(oracle_loss), rtol=1e-5)

    g_base, g_stk = jax.jit(jax.grad(loss_pp, argnums=(0, 1)))(base,
                                                               stacked)
    for k in base:
        np.testing.assert_allclose(np.asarray(g_base[k]),
                                   np.asarray(g_oracle[k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)
    oracle_stk = tfm.stack_layer_params_interleaved(g_oracle, 4, 2)
    for k in g_stk:
        np.testing.assert_allclose(np.asarray(g_stk[k]),
                                   np.asarray(oracle_stk[k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)


@pytest.mark.slow
@pytest.mark.parametrize("dp,n_micro", [(1, 8), (2, 8), (1, 16)])
def test_interleaved_1f1b_matches_gpipe(hvd, dp, n_micro):
    """The FULL Megatron schedule (3-phase interleaved 1F1B, P=4, v=2):
    one SGD step produces the SAME loss and the SAME updated params as
    GPipe (exact gradients), with and without a data axis.  M=16 covers
    the saved-input ring-buffer WRAPAROUND (v·M=32 > nbuf=2vP=16 — at
    M=8 every slot is used exactly once and `% nbuf` never wraps).
    The round-robin [vP, ...] chunk rows are re-mapped onto GPipe's
    contiguous [P, lps, ...] stages for the comparison."""
    import optax

    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                                d_ff=32, n_layers=8, max_seq=8,
                                dtype=jnp.float32)
    axes = ("data", "pipe") if dp > 1 else ("pipe",)
    shape = (dp, 4) if dp > 1 else (4,)
    mesh = _mesh(hvd, axes, shape)
    data_axis = "data" if dp > 1 else None
    full = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    # GPipe microbatches each data shard locally: local batch must be
    # divisible by M, so the global batch scales with dp.
    toks = rng.integers(0, 32, (n_micro * dp, 9))
    tokens = jnp.asarray(toks[:, :-1], jnp.int32)
    labels = jnp.asarray(toks[:, 1:], jnp.int32)
    opt = optax.sgd(0.1)

    results = {}
    for sched, v in (("gpipe", 1), ("interleaved_1f1b", 2)):
        params0 = tfm.split_pipeline_params(
            jax.tree_util.tree_map(jnp.array, full), 4, virtual=v)
        step, shardings = tfm.make_train_step_pipelined(
            cfg, opt, mesh, data_axis=data_axis, pipe_axis="pipe",
            n_microbatches=n_micro, schedule=sched, virtual=v,
            donate=False)
        p_sh, opt_sh = shardings(params0)
        params = {g: {k: jax.device_put(x, p_sh[g][k])
                      for k, x in params0[g].items()} for g in params0}
        opt_state = jax.device_put(opt.init(params), opt_sh)
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        results[sched] = (jax.tree_util.tree_map(np.asarray, params),
                          float(np.asarray(loss)))

    gp, il = results["gpipe"], results["interleaved_1f1b"]
    np.testing.assert_allclose(gp[1], il[1], rtol=1e-5)
    for k in gp[0]["base"]:
        np.testing.assert_allclose(il[0]["base"][k], gp[0]["base"][k],
                                   rtol=2e-4, atol=1e-5, err_msg=k)
    for k in gp[0]["stacked"]:
        g = gp[0]["stacked"][k]       # [4, 2, ...]: stage row, layer col
        i = il[0]["stacked"][k]       # [8, 1, ...]: row p*v+kk = chunk kk*4+p
        for row in range(8):
            p, kk = row // 2, row % 2
            chunk = kk * 4 + p
            np.testing.assert_allclose(
                i[row, 0], g[chunk // 2, chunk % 2],
                rtol=2e-4, atol=1e-5, err_msg=f"{k} row{row}")


def test_interleaved_layout_and_guards(hvd):
    """Round-robin stacking puts global chunk k·P+p at device p slot k;
    the schedule refuses M not divisible by P and mis-stacked params."""
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel.pipeline import pipeline_apply_interleaved

    cfg = tfm.TransformerConfig(vocab_size=8, d_model=4, n_heads=1,
                                d_ff=8, n_layers=8, max_seq=4,
                                dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    stacked = tfm.stack_layer_params_interleaved(params, 4, 2)
    # global row j = p*v + k holds chunk (j % v)*P + j//v (lpc=1 layer)
    for j in range(8):
        chunk = (j % 2) * 4 + j // 2
        np.testing.assert_array_equal(
            np.asarray(stacked["wq"][j, 0]),
            np.asarray(params["layers"][chunk]["wq"]))

    mesh = _mesh(hvd, ("pipe",), (4,))
    mb = jnp.zeros((6, 1, 4, 4), jnp.float32)   # M=6 not divisible by 4

    def run(stk, mb_):
        return pipeline_apply_interleaved(
            tfm._pipe_stage_fn(cfg), stk, mb_, "pipe", virtual=2)

    with pytest.raises(ValueError, match="divisible"):
        jax.shard_map(run, mesh=mesh,
                      in_specs=({k: P("pipe") for k in stacked}, P()),
                      out_specs=P(), check_vma=False)(stacked, mb)

    # mis-stacked params: the contiguous (non-round-robin) layout has
    # the right leading dim only by accident of v == stages/device; a
    # wrong-virtual stack must be refused, not silently mis-placed
    wrong = tfm.stack_layer_params(params, 4)       # leads {1} after shard
    mb_ok = jnp.zeros((4, 1, 4, 4), jnp.float32)
    with pytest.raises(ValueError, match="virtual"):
        jax.shard_map(run, mesh=mesh,
                      in_specs=({k: P("pipe") for k in wrong}, P()),
                      out_specs=P(), check_vma=False)(wrong, mb_ok)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_flash_matches_local(hvd, causal):
    """use_flash=True routes Ulysses' post-all-to-all attention through
    the Pallas kernel (interpret mode here): values AND gradients equal
    the packed local oracle."""
    from horovod_tpu.parallel.sequence import (local_attention,
                                               ulysses_attention)

    mesh = _mesh(hvd, ("seq",), (4,))
    b, t, h, d = 2, 128, 4, 16
    rng = np.random.default_rng(9)
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
               for _ in range(3))
    seg = np.zeros((b, t), np.int32)
    seg[:, 70:] = 1
    seg = jnp.asarray(seg)

    oracle = local_attention(q, k, v, causal=causal, segment_ids=seg)
    smapped = jax.shard_map(
        lambda q, k, v, s: ulysses_attention(q, k, v, "seq", causal,
                                             segment_ids=s,
                                             use_flash=True),
        mesh=mesh, in_specs=(P(None, "seq"),) * 4,
        out_specs=P(None, "seq"), check_vma=False)
    out = jax.jit(smapped)(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=3e-5, atol=3e-5)
    g_u = jax.jit(jax.grad(
        lambda q: jnp.sum(smapped(q, k, v, seg) ** 2)))(q)
    g_o = jax.grad(lambda q: jnp.sum(local_attention(
        q, k, v, causal=causal, segment_ids=seg) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g_u), np.asarray(g_o),
                               rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_attention_matches_local(hvd, causal):
    """Flash-kernel ring attention (per-step Pallas block math, merged
    online-softmax state): forward AND gradients equal the local oracle.
    check_vma=False because the Pallas HLO interpreter's internal block
    slicing rejects vma-varying operands on CPU; the compiled TPU path
    is unaffected."""
    from horovod_tpu.parallel.sequence import (local_attention,
                                               ring_flash_attention)

    mesh = _mesh(hvd, ("seq",), (4,))
    b, t, h, d = 2, 64, 2, 16
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
               for _ in range(3))

    oracle = local_attention(q, k, v, causal=causal)
    smapped = jax.shard_map(
        functools.partial(ring_flash_attention, axis_name="seq",
                          causal=causal, interpret=True),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False)
    out = jax.jit(smapped)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)

    g_r = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(smapped(q, k, v) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    g_o = jax.grad(
        lambda q, k, v: jnp.sum(local_attention(q, k, v,
                                                causal=causal) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for gr, go, nm in zip(g_r, g_o, "qkv"):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(go),
                                   rtol=5e-5, atol=5e-5, err_msg=nm)


def test_ring_flash_attention_segment_ids(hvd):
    """Sequence packing on the flash-ring route: K-side segment ids
    rotate with their blocks into the kernel's separate kseg ref;
    values and gradients equal the packed local oracle."""
    from horovod_tpu.parallel.sequence import (local_attention,
                                               ring_flash_attention)

    mesh = _mesh(hvd, ("seq",), (4,))
    b, t, h, d = 2, 64, 2, 16
    rng = np.random.default_rng(6)
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
               for _ in range(3))
    seg = np.zeros((b, t), np.int32)
    seg[0, 23:] = 1                  # boundaries off the shard edges
    seg[1, 9:40] = 1
    seg[1, 40:] = 2
    seg = jnp.asarray(seg)

    oracle = local_attention(q, k, v, causal=True, segment_ids=seg)
    smapped = jax.shard_map(
        lambda q, k, v, s: ring_flash_attention(
            q, k, v, "seq", True, None, True, s),
        mesh=mesh, in_specs=(P(None, "seq"),) * 4,
        out_specs=P(None, "seq"), check_vma=False)
    out = jax.jit(smapped)(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)

    g_r = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(smapped(q, k, v, seg) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    g_o = jax.grad(
        lambda q, k, v: jnp.sum(local_attention(
            q, k, v, causal=True, segment_ids=seg) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for gr, go, nm in zip(g_r, g_o, "qkv"):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(go),
                                   rtol=5e-5, atol=5e-5, err_msg=nm)


def test_transformer_ring_flash_route(hvd, monkeypatch):
    """attention='ring_flash' through the model equals the ring route
    (same math, kernel blockwise); 'auto' under a seq axis upgrades to
    ring_flash when the local chunk clears the flash threshold (lowered
    here so T_local=16 crosses it)."""
    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=32, d_model=32, n_heads=2,
                                d_ff=64, n_layers=1, max_seq=64,
                                dtype=jnp.float32)
    mesh = _mesh(hvd, ("seq",), (4,))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(0, 32, (2, 64)), jnp.int32)

    def run(attn):
        return jax.jit(jax.shard_map(
            lambda p, t: tfm.forward(p, t, cfg, seq_axis="seq",
                                     attention=attn),
            mesh=mesh, in_specs=(jax.tree_util.tree_map(
                lambda _: P(), params), P(None, "seq")),
            out_specs=P(None, "seq"), check_vma=False))(params, tokens)

    a = run("ring_flash")
    b_ = run("ring")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                               rtol=2e-4, atol=2e-4)

    # auto upgrade: needs T_local % 128 == 0 AND the (lowered) threshold
    # cleared — T=512 over 4 shards gives T_local=128; auto must take
    # the ring_flash branch and still match ring exactly
    monkeypatch.setenv("HOROVOD_FLASH_AUTO_MIN_T", "128")
    cfg2 = tfm.TransformerConfig(vocab_size=32, d_model=32, n_heads=2,
                                 d_ff=64, n_layers=1, max_seq=512,
                                 dtype=jnp.float32)
    params2 = tfm.init_params(jax.random.PRNGKey(1), cfg2)
    tokens2 = jnp.asarray(rng.integers(0, 32, (1, 512)), jnp.int32)

    def run2(attn):
        return jax.jit(jax.shard_map(
            lambda p, t: tfm.forward(p, t, cfg2, seq_axis="seq",
                                     attention=attn),
            mesh=mesh, in_specs=(jax.tree_util.tree_map(
                lambda _: P(), params2), P(None, "seq")),
            out_specs=P(None, "seq"), check_vma=False))(params2, tokens2)

    # both routes are the same math, so ALSO assert the branch taken:
    # auto must actually dispatch to ring_flash_attention here
    from horovod_tpu.parallel import sequence as seq_mod
    calls = []
    real = seq_mod.ring_flash_attention

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(seq_mod, "ring_flash_attention", spy)
    auto_out = run2("auto")
    assert calls, "auto did not dispatch to ring_flash"
    monkeypatch.setattr(seq_mod, "ring_flash_attention", real)
    np.testing.assert_allclose(np.asarray(auto_out),
                               np.asarray(run2("ring")),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_segment_ids(hvd, causal):
    """Sequence packing on the ring route: segment ids rotate with their
    K/V blocks; output equals the packed local-attention oracle."""
    from horovod_tpu.parallel.sequence import local_attention, ring_attention

    mesh = _mesh(hvd, ("seq",), (8,))
    b, t, h, d = 2, 32, 4, 16
    rng = np.random.default_rng(8)
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
               for _ in range(3))
    # Packed segments with boundaries NOT aligned to the 8 shard edges.
    seg = jnp.asarray(np.concatenate(
        [np.zeros(5), np.ones(9), np.full(11, 2), np.full(7, 3)]
    ).astype(np.int32)[None].repeat(b, 0))

    oracle = local_attention(q, k, v, causal=causal, segment_ids=seg)

    ring = jax.jit(jax.shard_map(
        lambda q, k, v, s: ring_attention(q, k, v, "seq", causal=causal,
                                          segment_ids=s),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq"),
                  P(None, "seq")),
        out_specs=P(None, "seq")))
    out = ring(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_segment_ids(hvd, causal):
    """Sequence packing on the Ulysses route: seq-sharded ids are
    all-gathered after the head scatter; equals the packed oracle."""
    from horovod_tpu.parallel.sequence import (local_attention,
                                               ulysses_attention)

    mesh = _mesh(hvd, ("seq",), (8,))
    b, t, h, d = 2, 32, 8, 16
    rng = np.random.default_rng(9)
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
               for _ in range(3))
    seg = jnp.asarray(np.concatenate(
        [np.zeros(13), np.ones(6), np.full(13, 2)]
    ).astype(np.int32)[None].repeat(b, 0))

    oracle = local_attention(q, k, v, causal=causal, segment_ids=seg)

    uly = jax.jit(jax.shard_map(
        lambda q, k, v, s: ulysses_attention(q, k, v, "seq", causal=causal,
                                             segment_ids=s),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq"),
                  P(None, "seq")),
        out_specs=P(None, "seq")))
    out = uly(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("attention", ["ring", "ulysses"])
def test_packed_forward_seq_sharded(hvd, attention):
    """The packed transformer forward on a seq-sharded mesh equals the
    unsharded packed forward — sequence packing reaches the SP routes
    (previously rejected with ValueError)."""
    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=32, d_model=16, n_heads=8,
                                d_ff=32, n_layers=2, max_seq=16,
                                dtype=jnp.float32)
    mesh = _mesh(hvd, ("seq",), (8,))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(10)
    tokens = jnp.asarray(rng.integers(0, 32, (2, 16)), jnp.int32)
    seg = jnp.asarray(np.concatenate(
        [np.zeros(7), np.ones(9)]).astype(np.int32)[None].repeat(2, 0))

    oracle = tfm.forward(params, tokens, cfg, attention="local",
                         segment_ids=seg)

    smapped = jax.jit(jax.shard_map(
        lambda p, t, s: tfm.forward(p, t, cfg, seq_axis="seq",
                                    attention=attention, segment_ids=s),
        mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(), params),
                  P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"), check_vma=False))
    got = smapped(params, tokens, seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                               rtol=3e-4, atol=3e-4)


def test_moe_ragged_matches_dense(hvd):
    """moe_layer_ragged == moe_layer(router="top1") exactly when nothing
    overflows (ample capacity): same routing decision, same expert math,
    ragged vs dense transport."""
    from horovod_tpu.parallel import expert as ep
    from horovod_tpu.topology import build_mesh
    from jax.sharding import PartitionSpec as P

    S, T, D = 4, 8, 6
    mesh = build_mesh(axes=("expert",), shape=(S,))
    rng = np.random.default_rng(11)
    x = rng.standard_normal((S * T, D)).astype(np.float32)
    rw = rng.standard_normal((D, S)).astype(np.float32) * 0.5
    epar = rng.standard_normal((S, 1, D, D)).astype(np.float32) * 0.3

    def run(layer):
        def f(xx, rr, pp):
            return layer(xx, rr, lambda p, tok: jnp.tanh(tok @ p[0]),
                         pp[0], axis_name="expert",
                         capacity_factor=float(S))  # ample: no drops
        return np.asarray(jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=(P("expert"), P(None), P("expert")),
            out_specs=P("expert"), check_vma=False))(x, rw, epar))

    dense = run(lambda *a, **k: ep.moe_layer(*a, router="top1", **k))
    ragged = run(ep.moe_layer_ragged)
    np.testing.assert_allclose(ragged, dense, rtol=1e-5, atol=1e-6)


def test_moe_ragged_drops_to_zero(hvd):
    """At capacity 1 per expert most tokens overflow; dropped tokens
    must contribute exactly zero and survivors stay finite."""
    from horovod_tpu.parallel import expert as ep
    from horovod_tpu.topology import build_mesh
    from jax.sharding import PartitionSpec as P

    S, T, D = 4, 8, 4
    mesh = build_mesh(axes=("expert",), shape=(S,))
    rng = np.random.default_rng(12)
    x = rng.standard_normal((S * T, D)).astype(np.float32)
    rw = np.zeros((D, S), np.float32)
    rw[0, 0] = 5.0   # bias routing toward expert 0: force overflow
    epar = np.ones((S, 1, D, D), np.float32)

    def f(xx, rr, pp):
        return ep.moe_layer_ragged(
            xx, rr, lambda p, tok: tok @ p[0], pp[0],
            axis_name="expert", capacity_factor=0.5)  # capacity 1
    out = np.asarray(jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(P("expert"), P(None), P("expert")),
        out_specs=P("expert"), check_vma=False))(x, rw, epar))
    assert np.isfinite(out).all()
    # With buf = S*1 = 4 rows per expert and 32 tokens mostly routed to
    # expert 0, most rows drop to exactly zero but the capacity grants
    # survive.
    zero_rows = int((out == 0).all(axis=1).sum())
    assert S * T * 3 // 4 <= zero_rows < S * T, zero_rows


def test_moe_ragged_gradients_flow(hvd):
    """Gradients flow through the double ragged exchange to tokens,
    router and expert weights (dense-twin AD route)."""
    from horovod_tpu.parallel import expert as ep
    from horovod_tpu.topology import build_mesh
    from jax.sharding import PartitionSpec as P

    S, T, D = 4, 6, 4
    mesh = build_mesh(axes=("expert",), shape=(S,))
    rng = np.random.default_rng(13)
    x = rng.standard_normal((S * T, D)).astype(np.float32)
    rw = rng.standard_normal((D, S)).astype(np.float32) * 0.5
    epar = rng.standard_normal((S, 1, D, D)).astype(np.float32) * 0.3

    def loss(xx, rr, pp):
        y = ep.moe_layer_ragged(
            xx, rr, lambda p, tok: jnp.tanh(tok @ p[0]), pp[0],
            axis_name="expert", capacity_factor=float(S))
        return lax.psum((y ** 2).sum(), "expert")

    g = jax.jit(jax.shard_map(
        jax.grad(loss, argnums=(0, 1, 2)), mesh=mesh,
        in_specs=(P("expert"), P(None), P("expert")),
        out_specs=(P("expert"), P(None), P("expert")), check_vma=False))
    gx, grw, gep = g(x, rw, epar)
    assert np.isfinite(np.asarray(gx)).all()
    assert float(np.abs(np.asarray(gx)).sum()) > 0
    assert float(np.abs(np.asarray(grw)).sum()) > 0
    assert float(np.abs(np.asarray(gep)).sum()) > 0


def test_moe_ragged_overflow_values_match_oracle(hvd):
    """Survivor VALUES at overflow vs a numpy oracle of the layer's
    documented capacity semantics: the expert's buffer is granted in
    source-rank order, survivors keep gate * expert(token), dropped rows
    are zero — the one regime where ragged and dense diverge."""
    from horovod_tpu.parallel import expert as ep
    from horovod_tpu.topology import build_mesh
    from jax.sharding import PartitionSpec as P

    S, T, D = 4, 8, 4
    cf = 0.75                       # capacity 1/expert -> buf 4: overflow
    mesh = build_mesh(axes=("expert",), shape=(S,))
    rng = np.random.default_rng(21)
    x = rng.standard_normal((S * T, D)).astype(np.float32)
    rw = rng.standard_normal((D, S)).astype(np.float32)
    w = rng.standard_normal((S, D, D)).astype(np.float32) * 0.3

    def f(xx, rr, pp):
        return ep.moe_layer_ragged(
            xx, rr, lambda p, tok: tok @ p[0], pp,
            axis_name="expert", capacity_factor=cf)
    out = np.asarray(jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(P("expert"), P(None), P("expert")),
        out_specs=P("expert"), check_vma=False))(x, rw, w)).reshape(S, T, D)

    # numpy oracle
    capacity = max(int(cf * T / S), 1)
    buf = S * capacity
    xs = x.reshape(S, T, D)
    logits = xs @ rw                                  # [S, T, E]
    e_ = np.exp(logits - logits.max(-1, keepdims=True))
    probs = e_ / e_.sum(-1, keepdims=True)
    dest = probs.argmax(-1)                           # [S, T]
    gate = np.take_along_axis(probs, dest[..., None], -1)[..., 0]
    want = np.zeros_like(xs)
    # Per expert j: grants go to shards in rank order, tokens within a
    # shard in (stable-sorted) token order.
    for j in range(S):
        used = 0
        for s in range(S):
            for tok in range(T):
                if dest[s, tok] != j:
                    continue
                if used < buf:
                    want[s, tok] = gate[s, tok] * (xs[s, tok] @ w[j])
                used += 1
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
