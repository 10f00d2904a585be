"""GLM-4.7-Flash's block through the normal LM step, at tiny widths that
keep the shape of the thing: latent attention whose head width is not
``d_model / n_heads`` and whose rotary part is narrower than the head, one
leading dense layer, expert layers of SwiGLU experts under the sigmoid
router with a shared expert (this chip holding a share), and a prediction
module of one such layer; against the plain reference of
``perfbench/reference/mla_moe_lm.py``, which shares no code with the
program.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import moe
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import grouped_matmul as gm
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.sequence import local_attention
from perfbench.reference import mla_moe_lm as reference

F32_REL = 5e-5

# 3 heads of 32 on a hidden size of 64 (3 x 32 != 64), 8 of them rotary;
# 1 dense + 2 expert layers + the module; 8 experts top-2, 4 held from 2.
GLM_TINY = tfm.TransformerConfig(
    vocab_size=128, d_model=64, n_heads=3, n_layers=3, d_ff=160, max_seq=128,
    dtype=jnp.float32, positions="rope", rope_theta=1e6, norm_eps=1e-5,
    tie_embeddings=False, head_width=32, q_latent_rank=24, kv_latent_rank=16,
    rope_dim=8, mlp="swiglu", n_experts=8, experts_per_token=2, d_expert=48,
    d_shared=48, routed_scale=1.8, experts_held=4, experts_held_from=2,
    dense_layers=1, mtp_layer_types=("full_attention",), mtp_loss_coef=0.1)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _params(cfg, seed=0):
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    # As the benchmark's adapter: at the program's 0.02 every token is the
    # same token to the router.
    params["embed"] = params["embed"] * 50.0
    return params


def _batch(cfg, batch=2, seq=128, seed=1):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                              cfg.vocab_size)
    return toks[:, :-1], toks[:, 1:]


def _dims(cfg):
    return {"n_heads": cfg.n_heads, "head_dim": cfg.head_dim,
            "rope_dim": cfg.rope_dim, "kv_rank": cfg.kv_latent_rank,
            "eps": cfg.norm_eps, "theta": cfg.rope_theta,
            "top_k": cfg.experts_per_token,
            "routed_scale": cfg.routed_scale,
            "held_from": cfg.experts_held_from}


def _reference(cfg, params, tokens, labels, **kw):
    """Every leaf the reference can differentiate, not only the cell's."""
    kw.setdefault("names", tuple(reference.LEAVES))
    return reference.loss_and_tail_grads(
        params, tokens, labels, dims=_dims(cfg),
        dense_layers=cfg.dense_layers, mtp_coef=cfg.mtp_loss_coef, **kw)


def _checked(tree, cfg=GLM_TINY):
    return {name: reference.leaf(tree, path)
            for name, path in reference.leaf_paths(cfg.n_layers).items()}


# --- the model against the reference ------------------------------------------

@pytest.mark.parametrize("attention", ("local", "flash"))
def test_loss_and_every_kind_of_leaf_match_the_reference(attention):
    """Float32 program against the float32 reference: the loss (both
    terms) and the gradient of every kind of leaf: both latents' norms,
    ``W_qb``, ``W_kva``, ``W_kvb``, ``W_o``, the router, a routed and the
    shared ``w_down``, the dense layer's, ``W_eh`` and the final norm."""
    cfg = GLM_TINY
    params, (tokens, labels) = _params(cfg), _batch(cfg)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(tfm.loss_fn)(
            params, tokens, labels, cfg, attention=attention)
        want, want_g, stats = _reference(cfg, params, tokens, labels)
    assert abs(loss - want) <= F32_REL * abs(want)
    assert set(want_g) == set(reference.LEAVES)
    got_g = _checked(grads)
    for name, g in want_g.items():
        assert float(jnp.linalg.norm(g)) > 0, name
        assert _rel(got_g[name], g) <= F32_REL, name
    # Two expert layers and the module's; the dense layer routes nothing.
    assert stats["rows"].shape == (3, 4)


@pytest.mark.parametrize("control,moved", [
    (dict(shared_expert=False), "w_shared_down_last"),
    (dict(rotate_shared_key=False), "w_kvb_last"),
    (dict(low_precision=jnp.float8_e4m3fn), "wo_last")],
    ids=["no_shared_expert", "k_r_unrotated", "float8"])
def test_the_oracle_sees_what_the_cells_controls_change(control, moved):
    """The three references that the cell's check must refuse are other
    functions at this size too."""
    cfg = GLM_TINY
    params, (tokens, labels) = _params(cfg), _batch(cfg)
    want, want_g, _ = _reference(cfg, params, tokens, labels,
                                 names=reference.CHECKED)
    off, off_g, _ = _reference(cfg, params, tokens, labels,
                               names=reference.CHECKED, **control)
    assert abs(off - want) > 1e-4 * abs(want)
    assert _rel(off_g[moved], want_g[moved]) > 0.02


def test_the_second_loss_is_the_prediction_modules():
    cfg = GLM_TINY
    params, (tokens, labels) = _params(cfg), _batch(cfg)
    plain = dataclasses.replace(cfg, mtp_layer_types=(), mtp_loss_coef=0.0)
    with jax.default_matmul_precision("highest"):
        both = tfm.loss_fn(params, tokens, labels, cfg, attention="local")
        first = tfm.loss_fn({k: v for k, v in params.items() if k != "mtp"},
                            tokens, labels, plain, attention="local")
        twice = tfm.loss_fn(params, tokens, labels, dataclasses.replace(
            cfg, mtp_loss_coef=0.2), attention="local")
    assert float(both - first) > 0.1
    assert abs((twice - first) - 2 * (both - first)) <= 1e-5 * abs(both)


@pytest.mark.parametrize("remat", ("dots", "full"))
def test_remat_leaves_loss_and_gradients_alone(remat):
    cfg = GLM_TINY
    params, (tokens, labels) = _params(cfg), _batch(cfg, seq=64)
    run = lambda r: jax.value_and_grad(tfm.loss_fn)(
        params, tokens, labels, cfg, attention="local", remat=r)
    (loss, grads), (want, want_g) = run(remat), run("none")
    assert abs(loss - want) <= 1e-6 * abs(want)
    for got, g in zip(jax.tree_util.tree_leaves(grads),
                      jax.tree_util.tree_leaves(want_g)):
        if float(jnp.linalg.norm(g)):      # the selection bias's is zero
            assert _rel(got, g) <= 1e-5


# --- latent attention -----------------------------------------------------------

def _attention_layer(cfg=GLM_TINY, seed=3, tokens=32):
    layer = tfm.init_params(jax.random.PRNGKey(seed), cfg)["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (2, tokens, cfg.d_model))
    return layer, h, jnp.arange(tokens)


def test_the_rotary_key_is_one_head_shared_by_all():
    """Every head's key ends in the same rotary part, the rotation of
    ``h W_kva``'s tail, and its gradient is the sum over the heads'."""
    cfg = GLM_TINY
    layer, h, positions = _attention_layer()
    rank, nope = cfg.kv_latent_rank, cfg.head_dim - cfg.rope_dim
    q, k, v, wide = tfm._latent_qkv(h, layer, cfg, positions)
    assert wide == cfg.n_heads * cfg.head_dim == 96
    assert q.shape == k.shape == v.shape == (2, 32, 3, 32)
    one = tfm._rotary((h @ layer["w_kva"])[..., None, rank:], positions,
                      cfg.rope_theta)
    for head in range(cfg.n_heads):
        np.testing.assert_allclose(k[..., head, nope:], one[..., 0, :],
                                   rtol=1e-6, atol=1e-6)
    # Its rotary-free part differs by head.
    assert _rel(k[..., 0, :nope], k[..., 1, :nope]) > 0.5
    weight = jax.random.normal(jax.random.PRNGKey(9), k.shape)

    def through_all_heads(w_kva):
        return jnp.sum(weight * tfm._latent_qkv(
            h, dict(layer, w_kva=w_kva), cfg, positions)[1])

    def through_one_key(w_kva):
        key = tfm._rotary((h @ w_kva)[..., None, rank:], positions,
                          cfg.rope_theta)[..., 0, :]
        return jnp.sum(jnp.sum(weight[..., nope:], axis=-2) * key)

    got = jax.grad(through_all_heads)(layer["w_kva"])[:, rank:]
    want = jax.grad(through_one_key)(layer["w_kva"])[:, rank:]
    assert _rel(got, want) <= 1e-5


def test_only_the_tail_of_a_query_head_is_rotary():
    """Position reaches q through its last ``rope_dim`` dims alone, and v
    and the rotary-free keys not at all."""
    cfg = GLM_TINY
    layer, h, positions = _attention_layer()
    nope = cfg.head_dim - cfg.rope_dim
    here = tfm._latent_qkv(h, layer, cfg, positions)
    later = tfm._latent_qkv(h, layer, cfg, positions + 7)
    for a, b in zip(here[:2], later[:2]):
        np.testing.assert_array_equal(a[..., :nope], b[..., :nope])
        assert _rel(a[..., nope:], b[..., nope:]) > 0.1
    np.testing.assert_array_equal(here[2], later[2])


@pytest.mark.parametrize("blocks", (None, 128), ids=["auto", "128"])
def test_flash_kernels_at_head_dim_256_match_the_lax_attention(blocks):
    """Forward, dQ and dK+dV at ``D = 256`` in the interpreter."""
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q, k, v, weight = (jax.random.normal(key, (1, 256, 2, 256))
                       for key in keys)

    def through(attend):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(weight * attend(q, k, v)), (0, 1, 2))(
                q, k, v)

    with jax.default_matmul_precision("highest"):
        got = through(lambda q, k, v: flash_attention(
            q, k, v, True, None, blocks, blocks, True))
        want = through(lambda q, k, v: local_attention(q, k, v, causal=True))
    assert abs(got[0] - want[0]) <= 1e-5 * abs(want[0])
    for name, a, b in zip(("dq", "dk", "dv"), got[1], want[1]):
        assert _rel(a, b) <= 1e-5, name


def test_auto_blocks_at_head_dim_256_are_the_sweeps_choice():
    from horovod_tpu.ops import flash_attention as fa

    assert fa._auto_block(8192, 256) == fa._auto_block(8192, 128) == 1024
    assert fa._auto_block(8192, 512) == 512       # not swept: the old cap
    # With segment ids the dK+dV kernel at 1024^2 is refused for VMEM
    # (tests/test_flash_compile.py compiles what this chooses).
    assert fa._auto_block(8192, 256, segments=True) == 512
    assert fa._auto_block(8192, 128, segments=True) == 1024
    assert fa._auto_block(1536, 256) == 512


# --- the expert layer ------------------------------------------------------------

def _expert_layer(cfg=GLM_TINY, seed=3, tokens=64):
    only = dataclasses.replace(cfg, n_layers=1, dense_layers=0,
                               mtp_layer_types=(), mtp_loss_coef=0.0)
    layer = tfm.init_params(jax.random.PRNGKey(seed), only)["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (tokens, cfg.d_model))
    return only, layer, u


def _experts(seed, n, d=64, f=48):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"w_gate": jax.random.normal(keys[0], (n, d, f)) * d ** -0.5,
            "w_up": jax.random.normal(keys[1], (n, d, f)) * d ** -0.5,
            "w_down": jax.random.normal(keys[2], (n, f, d)) * f ** -0.5}


def _held(experts, first, count):
    return {name: w[first:first + count] for name, w in experts.items()}


@pytest.mark.parametrize("held", (2, 4))
def test_the_shares_add_up(held):
    """The routed parts that the ``8 / held`` shares of eight experts
    compute, plus the shared expert counted once, are the uncut layer:
    the reference's with every expert in its tree."""
    cfg, layer, u = _expert_layer()
    whole = dataclasses.replace(cfg, experts_held=0, experts_held_from=0)
    experts = _experts(7, 8)
    dims = dict(_dims(cfg), held_from=0)
    with jax.default_matmul_precision("highest"):
        want, _ = reference._moe_part(u, dict(layer, **experts), dims, None,
                                      True)
        shared, _ = reference._moe_part(
            u, dict(layer, **_held(experts, 0, 0)), dims, None, True)
        total, rows = shared, []
        for first in range(0, 8, held):
            share = dataclasses.replace(cfg, experts_held=held,
                                        experts_held_from=first)
            y, held_rows = moe.sigmoid_moe_ffn(
                u, dict(layer, **_held(experts, first, held)), share)
            total = total + (y - shared)
            rows.append(held_rows)
        uncut, uncut_rows = moe.sigmoid_moe_ffn(u, dict(layer, **experts),
                                                whole)
    assert _rel(total, want) <= F32_REL
    assert _rel(uncut, want) <= F32_REL
    # Every assignment is some share's, once.
    assert int(jnp.sum(jnp.concatenate(rows))) == 64 * cfg.experts_per_token
    np.testing.assert_array_equal(jnp.concatenate(rows), uncut_rows)


@pytest.fixture()
def small_row_tiles(monkeypatch):
    """A row tile that lets a prefix of tiny shapes be shorter than their
    bound."""
    monkeypatch.setattr(gm, "TILE_M", 16)
    monkeypatch.setattr(gm, "SUB_M", 4)


def test_nothing_held_is_dropped_under_an_adversarial_router(
        small_row_tiles):
    """Every token picks both held experts of sixteen (their bias
    dominates): the buffer of tokens x 2 is full to the last row, four
    times past the prefix, and every row is computed, forward and
    backward."""
    cfg, layer, u = _expert_layer(dataclasses.replace(
        GLM_TINY, n_experts=16, experts_held=2, experts_held_from=6))
    prefix = moe.rows_prefix(64, cfg.experts_per_token, 2, 16)
    assert prefix == 64 < moe.rows_bound(64, cfg.experts_per_token, 2) == 128
    layer = dict(layer, router_bias=jnp.zeros((16,)).at[6:8].set(10.0))

    def through(ffn):
        return jax.value_and_grad(
            lambda u, layer: jnp.sum(jnp.sin(ffn(u, layer))), (0, 1))(
                u, layer)

    with jax.default_matmul_precision("highest"):
        _, rows = moe.sigmoid_moe_ffn(u, layer, cfg)
        got = through(lambda u, layer: moe.sigmoid_moe_ffn(u, layer, cfg)[0])
        want = through(lambda u, layer: reference._moe_part(
            u, layer, _dims(cfg), None, True)[0])
    np.testing.assert_array_equal(rows, [64, 64])
    assert abs(got[0] - want[0]) <= F32_REL * abs(want[0])
    assert _rel(got[1][0], want[1][0]) <= F32_REL
    for name in ("w_gate", "w_up", "w_down", "w_shared_down", "router"):
        assert _rel(got[1][1][name], want[1][1][name]) <= F32_REL, name


def test_an_ordinary_batch_stays_on_the_prefix(small_row_tiles):
    cfg, layer, u = _expert_layer(dataclasses.replace(
        GLM_TINY, n_experts=16, experts_held=2, experts_held_from=6))
    with jax.default_matmul_precision("highest"):
        got, rows = moe.sigmoid_moe_ffn(u, layer, cfg)
        want, want_rows = reference._moe_part(u, layer, _dims(cfg), None,
                                              True)
    assert 0 < int(rows.sum()) <= moe.rows_prefix(64, 2, 2, 16)
    np.testing.assert_array_equal(rows, want_rows)
    assert _rel(got, want) <= F32_REL


def test_the_bias_chooses_and_the_weights_are_the_scores():
    """``noaux_tc``: the selection bias changes who is chosen and not a
    weight; the weights are the chosen scores over their sum, x 1.8."""
    cfg, layer, u = _expert_layer()
    bias = jnp.zeros((8,)).at[5].set(10.0)
    top_w, top_i = moe.route_sigmoid(u, layer["router"], bias, 2, 1.8)
    assert bool(jnp.all(jnp.any(top_i == 5, axis=-1)))
    np.testing.assert_allclose(jnp.sum(top_w, axis=-1), 1.8, rtol=1e-6)
    scores = jax.nn.sigmoid(u @ layer["router"])
    chosen = jnp.take_along_axis(scores, top_i, axis=-1)
    np.testing.assert_allclose(
        top_w, 1.8 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    grads = jax.grad(lambda layer: jnp.sum(jnp.sin(moe.sigmoid_moe_ffn(
        u, layer, cfg)[0])))(layer)
    assert float(jnp.abs(grads["router_bias"]).max()) == 0.0


# --- through make_train_step ------------------------------------------------------

@pytest.mark.parametrize("devices", (1, 4))
def test_train_step_takes_the_gradient_of_the_global_batch(hvd, devices):
    """Through ``make_train_step``, on one device and on a four-device
    data mesh, recomputed (``remat="full"``): loss = the reference's on
    the whole batch; the momentum slot after one step from zero = the
    reference's gradient of the **global** batch mean."""
    from horovod_tpu.topology import build_mesh

    cfg, lr = GLM_TINY, 0.1
    mesh = build_mesh(axes=("data",), devices=jax.devices()[:devices])
    optimizer = optax.sgd(lr, momentum=0.9)
    step, _, _ = tfm.make_train_step(cfg, optimizer, mesh, attention="local",
                                     donate=False, remat="full")
    params = _params(cfg)
    tokens, labels = _batch(cfg, batch=4)
    new, opt_state, loss = step(params, optimizer.init(params), tokens,
                                labels)
    want, want_g, _ = jax.jit(lambda *a: _reference(cfg, *a))(
        params, tokens, labels)
    assert abs(loss - want) <= F32_REL * abs(want)
    momentum = _checked(opt_state[0].trace)
    after, before = _checked(new), _checked(params)
    for name, g in want_g.items():
        assert _rel(momentum[name], g) <= F32_REL, name
        assert _rel((after[name] - before[name]) / -lr, g) <= 3e-3, name
    # The selection bias is not trained.
    np.testing.assert_array_equal(new["layers"][1]["router_bias"], 0.0)


def test_specs_and_abstract_params_cover_every_leaf():
    cfg = GLM_TINY
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    specs = tfm.param_specs(cfg, None)
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(
                specs, is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec)))
    dense, expert = params["layers"][0], params["layers"][1]
    assert dense["w_down"].shape == (160, 64) and "router" not in dense
    assert expert["w_down"].shape == (4, 48, 64)
    assert expert["router"].shape == (64, 8)
    assert set(params["mtp"]["layers"][0]) == set(expert)
    assert expert["w_qb"].shape == (24, 96)
    assert expert["w_kva"].shape == (64, 16 + 8)
    assert expert["w_kvb"].shape == (16, 3 * (24 + 32))
    assert expert["wo"].shape == (96, 64)
    abstract = tfm.init_abstract(cfg)
    assert (jax.tree_util.tree_map(lambda a: a.shape, abstract)
            == jax.tree_util.tree_map(lambda a: a.shape, params))


def test_trace_time_series_count_the_expert_layers_alone(hvd):
    from horovod_tpu import telemetry

    cfg = GLM_TINY
    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
        jax.eval_shape(lambda p, t: tfm.loss_fn(
            p, t, t, cfg, attention="local"), tfm.init_abstract(cfg), tokens)
        text = telemetry.render_prometheus()
        for layer in ("1", "2", "mtp_0"):
            assert f'hvd_moe_experts_held{{layer="{layer}"}} 4' in text, text
            assert (f'hvd_moe_rows_bound{{layer="{layer}"}} '
                    f'{256 * 2}') in text, text
            assert (f'hvd_moe_expert_weight_copy_bytes{{layer="{layer}"}} 0'
                    ) in text, text
        # The dense layer holds no expert.
        assert 'hvd_moe_experts_held{layer="0"}' not in text
        # Data, not static, on a share: not counted.
        assert "hvd_moe_assignments_total" not in text
        whole = dataclasses.replace(cfg, experts_held=0, experts_held_from=0)
        jax.eval_shape(lambda p, t: tfm.loss_fn(
            p, t, t, whole, attention="local"), tfm.init_abstract(whole),
            tokens)
        assert ('hvd_moe_assignments_total{layer="1"} 512'
                in telemetry.render_prometheus())
    finally:
        telemetry.reset_for_tests()


def test_scopes_name_the_new_parts(hvd):
    """The lowered step carries the sub-scopes the per-layer metrics read
    (``perfbench/mla_reduce.py``)."""
    cfg = GLM_TINY
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = jax.jit(lambda p, t: tfm.loss_fn(
        p, t, t, cfg, attention="local")).lower(
            tfm.init_abstract(cfg), tokens).as_text(debug_info=True)
    for scope in ("layer_1/attn/qkv/mla_q", "layer_1/attn/qkv/mla_kv",
                  "layer_1/attn/qkv/mla_rope", "layer_0/mlp/mlp_dense",
                  "layer_1/mlp/moe_router", "layer_1/mlp/moe_shared",
                  "mtp/layer_0/attn/qkv/mla_kv", "mtp/layer_0/mlp/moe_experts"):
        assert scope in text, scope
    assert "layer_1/mlp/mlp_dense" not in text
    # (The module's own layer_0 is an expert layer.)
    assert ")/layer_0/mlp/moe_router" not in text


# --- refusals: never a silent fall back ---------------------------------------------

@pytest.mark.parametrize("axis", ("model", "seq"))
def test_model_and_sequence_axes_are_refused_by_name(hvd, axis):
    from horovod_tpu.topology import build_mesh

    mesh = build_mesh(axes=("data", axis), shape=(2, 2),
                      devices=jax.devices()[:4])
    # Latent attention alone, without experts or a module to refuse first.
    cfg = dataclasses.replace(
        GLM_TINY, mlp="swiglu", n_experts=0, experts_per_token=0, d_expert=0,
        d_shared=0, routed_scale=1.0, experts_held=0, experts_held_from=0,
        dense_layers=0, mtp_layer_types=(), mtp_loss_coef=0.0)
    with pytest.raises(NotImplementedError,
                       match=f"{axis}_axis.*head_width"):
        tfm.make_train_step(cfg, optax.sgd(0.1), mesh,
                            **{f"{axis}_axis": axis})
    with pytest.raises(NotImplementedError, match="n_experts|seq_axis"):
        tfm.make_train_step(GLM_TINY, optax.sgd(0.1), mesh,
                            **{f"{axis}_axis": axis})


def test_packed_is_refused_by_the_prediction_module(hvd):
    from horovod_tpu.topology import build_mesh

    mesh = build_mesh(axes=("data",), devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="packed.*prediction"):
        tfm.make_train_step(GLM_TINY, optax.sgd(0.1), mesh, packed=True)


def test_decode_and_the_pipelined_builder_refuse_latent_attention_by_name(
        hvd):
    from horovod_tpu.topology import build_mesh

    cfg = GLM_TINY
    with pytest.raises(NotImplementedError, match="decode_step.*head_width"):
        tfm.decode_step(tfm.init_abstract(cfg), jnp.zeros((2,), jnp.int32),
                        tfm.init_kv_cache(cfg, 2, 8), 0, cfg)
    mesh = build_mesh(axes=("data", "pipe"), shape=(2, 2),
                      devices=jax.devices()[:4])
    with pytest.raises(NotImplementedError, match="pipelined.*head_width"):
        tfm.make_train_step_pipelined(cfg, optax.sgd(0.1), mesh)


@pytest.mark.parametrize("fields,error,message", [
    (dict(head_width=0), ValueError, "latent attention needs head_width"),
    (dict(rope_dim=40), ValueError, "rope_dim=40 is wider than head_width"),
    (dict(qk_norm_per_head=True), NotImplementedError, "qk_norm_per_head"),
    (dict(rope_dim=0), ValueError, "come together"),
    (dict(positions="none"), ValueError, "positions='rope'"),
    (dict(rope_dim=7), ValueError, "even rope_dim"),
    (dict(qk_norm=True), NotImplementedError, "qk_norm"),
    (dict(n_kv_heads=1), NotImplementedError, "n_kv_heads"),
    (dict(dense_layers=4), ValueError, "dense_layers=4 must lie in 0..n"),
    (dict(dense_layers=1, mlp="relu2", d_latent=16), NotImplementedError,
     "leading dense MLP is SwiGLU"),
    (dict(n_experts=0, experts_per_token=0, d_expert=0, experts_held=0,
          experts_held_from=0, d_shared=0, routed_scale=1.0), ValueError,
     "dense_layers"),
    (dict(n_experts=0, experts_per_token=0, d_expert=0, experts_held=0,
          experts_held_from=0, dense_layers=0, routed_scale=1.0),
     ValueError, "d_shared is the shared expert"),
    (dict(d_shared=0), ValueError, "routed_scale"),
    (dict(d_latent=8), ValueError, "d_latent means nothing"),
    (dict(router_aux_coef=0.01), NotImplementedError, "no auxiliary loss"),
    (dict(norm_topk_prob=True), NotImplementedError, "renormalises"),
])
def test_config_says_what_the_new_fields_cannot_mean(fields, error, message):
    with pytest.raises(error, match=message):
        dataclasses.replace(GLM_TINY, **fields)


@pytest.mark.parametrize("fields", [
    dict(d_shared=0, routed_scale=1.0), dict(dense_layers=3),
    dict(dense_layers=0),
    dict(q_latent_rank=0, kv_latent_rank=0, rope_dim=0)],
    ids=["softmax_router_after_a_dense_layer", "every_layer_dense",
         "no_dense_layer", "a_head_width_alone"])
def test_config_takes_what_the_new_fields_can_mean(fields):
    cfg = dataclasses.replace(GLM_TINY, **fields)
    layers = tfm.init_abstract(cfg)["layers"]
    assert [("router" in layer) for layer in layers] == [
        i >= cfg.dense_layers for i in range(cfg.n_layers)]


def test_sigmoid_router_and_shared_expert_are_legal_with_swiglu_experts():
    """``d_shared`` and ``routed_scale`` used to mean nothing without
    ``mlp='relu2'``."""
    cfg = GLM_TINY
    assert cfg.sigmoid_router and cfg.latent_attention
    assert cfg.head_dim == 32 != cfg.d_model // cfg.n_heads
    softmax = dataclasses.replace(cfg, d_shared=0, routed_scale=1.0)
    assert not softmax.sigmoid_router
    layer = tfm.init_params(jax.random.PRNGKey(0), softmax)["layers"][1]
    assert "router_bias" not in layer and "w_shared_up" not in layer
