"""Compressed convolutional attention, the MLP router with a state carried
from expert layer to expert layer, one expert a token or a skip, and the
scaled residual merge (``TransformerConfig.cca_taps``, ``rotary_dims``,
``router_width``, ``residual_scaling``), at tiny sizes on the virtual CPU
mesh.

Oracles: the benchmark's plain float32 reference
(``perfbench/reference/cca_moe_lm.py``), which shares no code with the
program, writes the convolutions as shifted adds, the experts as a loop
with a mask a token and the carried state as a Python variable;
``jax.lax.conv_general_dilated`` with the left padding written out; and the
uncut layer for the two shares of its experts.  Float32 everywhere.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import attention, moe, parts
from horovod_tpu.models import transformer as tfm
from perfbench.reference import cca_moe_lm as reference

# The families every configuration shares, and the table of configurations
# (tests/test_lm_configs.py); those that compile this row's program run
# here, in the row's own file: a file is one worker's chain.
from test_lm_configs import *  # noqa: E402,F401,F403
from test_lm_configs import ZAYA_TINY, built, rel, zaya_dims  # noqa: E402

COSTLY_ROWS = ("zaya",)

REL = 5e-5


def _layer(cfg, key=0):
    return tfm.init_params(jax.random.PRNGKey(key), cfg)["layers"][0]


def _normed_input(cfg, batch=2, seq=32, key=1):
    return jax.random.normal(jax.random.PRNGKey(key),
                             (batch, seq, cfg.d_model), jnp.float32)


# --- the convolutions, the shift and the rotation ---------------------------

def test_both_convolutions_are_lax_convolutions_padded_on_the_left():
    """The depthwise convolution is ``feature_group_count = channels``,
    the grouped one ``= heads``, each over a sequence padded with ONE zero
    row on the left (``taps - 1``) and none on the right."""
    cfg = ZAYA_TINY
    layer, hd = _layer(cfg), cfg.head_dim
    heads = cfg.n_heads + cfg.kv_heads
    c = jax.random.normal(jax.random.PRNGKey(2), (2, 32, heads * hd))
    with jax.default_matmul_precision("highest"):
        got = attention.cca_convolutions(c, layer, hd, jnp.float32)
        # [taps, in / groups, out]: a channel of its own.
        c1 = jax.lax.conv_general_dilated(
            c, layer["cca_dw_w"][:, None, :], window_strides=(1,),
            padding=[(1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
            feature_group_count=heads * hd) + layer["cca_dw_b"]
        # [taps, head_dim, heads x head_dim]: head j's block of outputs
        # reads head j's block of inputs.
        kernel = jnp.transpose(layer["cca_gw_w"], (0, 2, 1, 3)).reshape(
            2, hd, heads * hd)
        c2 = jax.lax.conv_general_dilated(
            c1, kernel, window_strides=(1,), padding=[(1, 0)],
            dimension_numbers=("NWC", "WIO", "NWC"),
            feature_group_count=heads) + layer["cca_gw_b"]
    assert rel(got.reshape(c2.shape), c2) <= 1e-6
    # The first row saw a zero row before it, not the last (no wrap).
    alone = attention.cca_convolutions(c[:, :1], layer, hd, jnp.float32)
    np.testing.assert_allclose(alone, got[:, :1], rtol=1e-5, atol=1e-6)


def test_the_shift_pads_a_zero_row_on_the_left():
    a = jnp.arange(24.0).reshape(2, 4, 3) + 1.0
    got = parts.shifted(a)
    np.testing.assert_array_equal(got[:, 0], 0.0)
    np.testing.assert_array_equal(got[:, 1:], a[:, :-1])


@pytest.mark.parametrize("at", (1, 17, 31))
def test_no_head_reads_a_later_token(at):
    """A change of the input at position ``at`` moves no q, k or v before
    it: both convolutions and the value shift look back only.  And the
    shifted half of the values at ``at + 1`` does move."""
    cfg = ZAYA_TINY
    layer, u = _layer(cfg), _normed_input(cfg)
    moved = u.at[:, at].add(1.0)
    positions = jnp.arange(u.shape[1])
    before = attention.cca_qkv(u, layer, cfg, positions)
    after = attention.cca_qkv(moved, layer, cfg, positions)
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a[:, :at], b[:, :at])
        assert float(jnp.abs(a[:, at] - b[:, at]).max()) > 1e-3
    if at + 1 < u.shape[1]:
        v_a, v_b = before[2][:, at + 1], after[2][:, at + 1]
        half = cfg.kv_heads // 2
        np.testing.assert_array_equal(v_a[:, :half], v_b[:, :half])
        assert float(jnp.abs(v_a[:, half:] - v_b[:, half:]).max()) > 1e-3


def test_rotary_turns_the_first_dims_of_a_head_alone():
    """``rotary_dims`` 4 of 8: dims 4-7 of every head are what they are
    at position 0, dims 0-3 are not; 0 turns the whole head."""
    cfg = ZAYA_TINY
    layer, u = _layer(cfg), _normed_input(cfg)
    positions = 5 + jnp.arange(u.shape[1])
    still = attention.cca_qkv(u, layer, cfg, jnp.zeros_like(positions))
    turned = attention.cca_qkv(u, layer, cfg, positions)
    whole = attention.cca_qkv(u, layer,
                              dataclasses.replace(cfg, rotary_dims=0),
                              positions)
    r = cfg.rotary_dims
    for a, b, c in zip(still[:2], turned[:2], whole[:2]):
        np.testing.assert_array_equal(a[..., r:], b[..., r:])
        assert float(jnp.abs(a[..., :r] - b[..., :r]).max()) > 1e-2
        assert float(jnp.abs(a[..., r:] - c[..., r:]).max()) > 1e-2
        # A rotation: the norm of a head stays sqrt(head_dim) (k: times
        # its temperature).
        np.testing.assert_allclose(jnp.linalg.norm(a, axis=-1),
                                   jnp.linalg.norm(b, axis=-1), rtol=1e-5)
    np.testing.assert_allclose(jnp.linalg.norm(turned[0], axis=-1),
                               cfg.head_dim ** 0.5, rtol=1e-5)
    np.testing.assert_array_equal(still[2], turned[2])


def test_the_heads_match_the_reference():
    """q, k, v of one sequence against the reference's own lines."""
    cfg = ZAYA_TINY
    layer, u = _layer(cfg), _normed_input(cfg, batch=1)
    with jax.default_matmul_precision("highest"):
        got = attention.cca_qkv(u, layer, cfg, jnp.arange(u.shape[1]))
        want = reference.cca_heads(u[0], layer, zaya_dims(cfg),
                                   reference.MODEL)
    for a, b in zip(got, want):
        assert rel(a[0], b) <= 1e-5


# --- the share tied to the model --------------------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 on one chip and 4-7 on the other: what each gives,
    with what both compute alike (the router, the skip's term) counted
    once, is the uncut layer's output, and the router state is the same
    on both."""
    whole = dataclasses.replace(ZAYA_TINY, experts_held=0,
                                experts_held_from=0)
    layer = _layer(whole)
    u = _normed_input(whole, batch=4, seq=64)
    state = 0.3 * jax.random.normal(jax.random.PRNGKey(4),
                                    u.shape[:2] + (whole.router_width,))
    dims = dict(zaya_dims(whole), held_from=0)
    with jax.default_matmul_precision("highest"):
        routed, skipped, want_state, choice = jax.vmap(
            lambda a, b: reference.expert_branch(a, b, layer, dims))(
                u, state)
        uncut, got_state = moe.zaya_moe_ffn(u, state, layer, whole)
        shares = []
        for first in (0, 4):
            cfg = dataclasses.replace(whole, experts_held=4,
                                      experts_held_from=first)
            held = dict(layer, **{name: layer[name][first:first + 4]
                                  for name in moe.EXPERT_LEAVES})
            y, share_state = moe.zaya_moe_ffn(u, state, held, cfg)
            np.testing.assert_array_equal(share_state, got_state)
            shares.append(y)
    assert 0 < int((choice == 8).sum()) < choice.size // 2
    assert all(int((choice == e).sum()) for e in range(8))
    assert rel(uncut, routed + skipped) <= REL
    assert rel(got_state, want_state) <= REL
    # Both chips computed the skip's term: counted once.
    assert rel(shares[0] + shares[1] - skipped, routed + skipped) <= REL
    # Neither share is the whole of it.
    assert rel(shares[0], routed + skipped) > 0.1


def test_the_first_layer_takes_no_state_and_every_layer_hands_one_on():
    cfg = ZAYA_TINY
    layer, u = _layer(cfg), _normed_input(cfg)
    _, first = moe.zaya_moe_ffn(u, None, layer, cfg)
    assert first.shape == u.shape[:2] + (cfg.router_width,)
    assert first.dtype == jnp.float32
    _, second = moe.zaya_moe_ffn(u, first, layer, cfg)
    np.testing.assert_allclose(
        second, first + layer["router_state_scale"] * first, rtol=1e-5)


def test_the_selection_bias_chooses_and_does_not_weigh():
    """A bias that moves every token to the skip changes the choice and
    leaves the chosen probability the softmax's own."""
    cfg = ZAYA_TINY
    layer, u = _layer(cfg), _normed_input(cfg)
    flat = u.reshape(-1, cfg.d_model)
    p_c, c, _ = moe.route_mlp(flat, None, layer, cfg)
    pushed = dict(layer, router_bias=layer["router_bias"].at[8].add(10.0))
    p_skip, c_skip, _ = moe.route_mlp(flat, None, pushed, cfg)
    assert (np.asarray(c_skip) == 8).all() and (np.asarray(c) != 8).any()
    assert float(p_skip.max()) < 1.0
    y, _ = moe.zaya_moe_ffn(u, None, pushed, cfg)
    np.testing.assert_allclose(y.reshape(flat.shape), p_skip[:, None] * flat,
                               rtol=1e-6)


# --- the seam ---------------------------------------------------------------

def test_the_merge_is_a_plain_add_without_the_field():
    cfg = dataclasses.replace(ZAYA_TINY, residual_scaling=False)
    layer = _layer(cfg)
    assert not [name for name in layer if name.startswith("merge")]
    x, y = _normed_input(cfg, key=5), _normed_input(cfg, key=6)
    np.testing.assert_array_equal(parts.merged(x, y, layer, "merge1", cfg),
                                  x + y)
    scaled = _layer(ZAYA_TINY)
    a_r, b_r, a_o, b_o = (scaled[name]
                          for name in parts.merge_names("merge2"))
    np.testing.assert_allclose(
        parts.merged(x, y, scaled, "merge2", ZAYA_TINY),
        a_r * (x + b_r) + a_o * (y + b_o), rtol=1e-6)


@pytest.mark.parametrize("tied", (True, False))
def test_the_logits_take_a_constant(tied):
    """``logit_scale``: the head's logits times the constant, so the final
    norm's scale times it is the same function and that scale's gradient
    is the constant times what it was; 1.0 lowers to the text without the
    field's multiply."""
    cfg = dataclasses.replace(ZAYA_TINY, tie_embeddings=tied)
    scaled = dataclasses.replace(cfg, logit_scale=0.25)
    params = tfm.init_params(jax.random.PRNGKey(3), cfg)
    x = _normed_input(cfg)
    plain = tfm._logits_head(x, params, cfg)
    np.testing.assert_allclose(tfm._logits_head(x, params, scaled),
                               0.25 * plain, rtol=1e-6)
    folded = dict(params, ln_f_scale=0.25 * params["ln_f_scale"])
    np.testing.assert_allclose(tfm._logits_head(x, folded, cfg),
                               0.25 * plain, rtol=1e-6)

    def norm_of_logits(scale, cfg_):
        return jnp.sum(tfm._logits_head(
            x, dict(params, ln_f_scale=scale), cfg_) ** 2)

    grad = jax.grad(norm_of_logits)
    np.testing.assert_allclose(
        grad(params["ln_f_scale"], scaled),
        0.25 * grad(folded["ln_f_scale"], cfg), rtol=1e-5)
    text = lambda cfg_: jax.jit(
        lambda x, p: tfm._logits_head(x, p, cfg_)).lower(x, params).as_text()
    assert text(dataclasses.replace(cfg, logit_scale=1.0)) == text(cfg)
    assert text(scaled) != text(cfg)


def test_the_carry_is_an_input_and_an_output_of_the_recomputed_block():
    """Under ``remat`` ``full`` every expert block's ``jax.checkpoint``
    takes the router state as an argument and returns the next: the
    jaxpr's checkpointed calls that hold an expert layer have one more
    float32 ``[B, T, w]`` input from the second layer on, and the state
    is never a constant closed over."""
    row, cfg = built("zaya"), ZAYA_TINY
    tokens, labels = row.batch()
    jaxpr = jax.make_jaxpr(lambda p: tfm.loss_fn(
        p, tokens, labels, cfg, attention="local", remat="full"))(
            row.params)
    state = (tokens.shape[0], tokens.shape[1], cfg.router_width)
    blocks = [eqn for eqn in jaxpr.jaxpr.eqns
              if eqn.primitive.name in ("checkpoint", "remat2")]
    takes = [sum(v.aval.shape == state for v in eqn.invars)
             for eqn in blocks]
    hands = [sum(v.aval.shape == state for v in eqn.outvars)
             for eqn in blocks]
    # Mixer, experts, mixer, experts, mixer, experts.
    assert takes == [0, 0, 0, 1, 0, 1], takes
    assert hands == [0, 1, 0, 1, 0, 1], hands


def test_the_lowered_loss_of_a_looped_stack_is_the_parents_text():
    """``tests/test_looped_lm.py`` holds the eight rows that loop nothing
    to the text they lowered to before the seam carried anything; this is
    the ninth, taken at PR 53's parent (908f42b): with no part that
    carries and ``residual_scaling`` off, ``run_layers`` and ``block``
    write the program they wrote."""
    import hashlib

    from test_lm_configs import _traced

    loss, params, batch = _traced(built("ouro"))
    text = jax.jit(jax.value_and_grad(loss)).lower(params, *batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == OUROS_TEXT


OUROS_TEXT = "3d336b76c57e0bbf"


def test_the_trace_time_series_say_argmax_and_the_bound(hvd):
    from horovod_tpu import telemetry

    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        cfg = ZAYA_TINY
        tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
        jax.eval_shape(lambda p, t: tfm.loss_fn(p, t, t, cfg,
                                                attention="local"),
                       tfm.init_abstract(cfg), tokens)
        text = telemetry.render_prometheus()
    finally:
        telemetry.reset_for_tests()
    for layer in range(3):
        assert (f'hvd_moe_router_choices_total{{layer="{layer}",'
                f'path="argmax"}} 1') in text
        assert f'hvd_cca_rows_total{{layer="{layer}"}} 128' in text
    # One slot a token; four times a uniform router's rows pass the bound,
    # so the layer works on the whole buffer and moves no row by the
    # prefix's kernels.
    assert moe.rows_bound(128, 1, 4) == 128
    assert moe.rows_prefix(128, 1, 4, moe.router_choices(cfg)) == 128
    assert moe.moves_path(jnp.zeros((2, 64, 64)), cfg) is None
    assert "hvd_moe_row_moves_total" not in text
