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
import pytest

from horovod_tpu.models import attention, moe
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import grouped_matmul as gm
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.sequence import local_attention
from perfbench.reference import mla_moe_lm as reference

F32_REL = 5e-5

# The families every configuration shares, and the table of configurations
# (tests/test_lm_configs.py); those that compile this row's program run
# here, in the row's own file: a file is one worker's chain.
from test_lm_configs import *  # noqa: E402,F401,F403
from test_lm_configs import GLM_TINY, glm_dims as _dims  # noqa: E402

COSTLY_ROWS = ("glm",)


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


# --- the model against the reference ------------------------------------------


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
    q, k, v, wide = attention.latent_qkv(h, layer, cfg, positions)
    assert wide == cfg.n_heads * cfg.head_dim == 96
    assert q.shape == k.shape == v.shape == (2, 32, 3, 32)
    one = attention.rotary((h @ layer["w_kva"])[..., None, rank:], positions,
                      cfg.rope_theta)
    for head in range(cfg.n_heads):
        np.testing.assert_allclose(k[..., head, nope:], one[..., 0, :],
                                   rtol=1e-6, atol=1e-6)
    # Its rotary-free part differs by head.
    assert _rel(k[..., 0, :nope], k[..., 1, :nope]) > 0.5
    weight = jax.random.normal(jax.random.PRNGKey(9), k.shape)

    def through_all_heads(w_kva):
        return jnp.sum(weight * attention.latent_qkv(
            h, dict(layer, w_kva=w_kva), cfg, positions)[1])

    def through_one_key(w_kva):
        key = attention.rotary((h @ w_kva)[..., None, rank:], positions,
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
    here = attention.latent_qkv(h, layer, cfg, positions)
    later = attention.latent_qkv(h, layer, cfg, positions + 7)
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


# --- refusals: never a silent fall back ---------------------------------------------


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
