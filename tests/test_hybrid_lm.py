"""Per-layer types and the gated-delta-rule linear-attention layer
(``models/linear_attention.py``), at tiny sizes on the virtual CPU mesh.

Oracle: the benchmark's plain float32 reference
(``perfbench/reference/hybrid_lm.py``), which shares no code with the
program and walks the recurrence one token at a time.  Tolerances, float32
everywhere unless a test says otherwise: 5e-5 relative L2, which is
float32 rounding through four layers and a 256-token recurrence (the
chunked form sums in another order and inverts a [64, 64] triangular
system where the reference substitutes; bfloat16 operands anywhere read
3e-3 and up, and one test proves that).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import linear_attention as la
from horovod_tpu.models import transformer as tfm
from perfbench.reference import hybrid_lm as reference

F32_REL = 5e-5

# The families every configuration shares, and the table of configurations
# (tests/test_lm_configs.py); those that compile this row's program run
# here, in the row's own file: a file is one worker's chain.
from test_lm_configs import *  # noqa: E402,F401,F403
from test_lm_configs import HYBRID_TINY, OLMOE_TINY, GPT2_TINY  # noqa: E402

COSTLY_ROWS = ("hybrid",)

PATTERN = ("linear_attention",) * 3 + ("full_attention",)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# --- the chunked recurrence and the layer -----------------------------------

# g = log alpha per token and head is drawn uniformly from the range.
GATES = {"alpha_mid": (-0.2, 0.0), "alpha_near_0": (-30.0, -5.0),
         "alpha_near_1": (-1e-4, 0.0)}


@pytest.mark.parametrize("blocks", (1, 4))
@pytest.mark.parametrize("gates", GATES.values(), ids=GATES.keys())
def test_chunked_recurrence_matches_token_by_token(gates, blocks):
    """Forward and all five gradients, with beta up to 2 (the negative
    eigenvalues), against the reference's scan over single tokens."""
    t, h, dk, dv = blocks * la.BLOCK, 3, 24, 40
    ks = jax.random.split(jax.random.key(0), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (t, h, dk)))
    v = jax.random.normal(ks[2], (t, h, dv))
    g = jax.random.uniform(ks[3], (t, h), minval=gates[0], maxval=gates[1])
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (t, h)) + 1.0)
    assert float(beta.max()) > 1.5

    def chunked(q, k, v, g, beta):
        return la.gated_delta_rule(q[None], k[None], v[None], g[None],
                                   beta[None], jnp.float32)[0]

    def by_token(q, k, v, g, beta):
        return reference._delta_rule(q, k, v, jnp.exp(g), beta, None)

    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(loss(chunked), range(5))(
            q, k, v, g, beta)
        want, want_g = jax.value_and_grad(loss(by_token), range(5))(
            q, k, v, g, beta)
        assert _rel(chunked(q, k, v, g, beta),
                    by_token(q, k, v, g, beta)) <= F32_REL
    assert abs(got - want) <= F32_REL * abs(want)
    for name, a, b in zip("q k v g beta".split(), got_g, want_g):
        # Near alpha = 0 the gradient with respect to g is itself 1e-3
        # of the others, and what is left of it is the rounding of
        # exp(b_i - b_j) sums that cancel: an absolute floor at 1e-6 of
        # the gradient of v.
        bound = (2e-5 * np.linalg.norm(b)
                 + 1e-6 * np.linalg.norm(want_g[2]))
        assert np.linalg.norm(np.asarray(a - b)) <= bound, name


def test_unit_lower_inverse_and_its_gradient():
    n = jnp.tril(jax.random.normal(jax.random.key(1), (3, 64, 64)), -1) * 0.3
    eye = jnp.eye(64)
    with jax.default_matmul_precision("highest"):
        got = la._unit_lower_inverse(n)
        want = jnp.linalg.inv(eye + n)
        g = jax.grad(lambda n: jnp.sum(jnp.cos(la._unit_lower_inverse(n))))(n)
        w = jax.grad(lambda n: jnp.sum(jnp.cos(jnp.linalg.inv(eye + n))))(n)
    assert _rel(got, want) <= 1e-5
    assert _rel(g, jnp.tril(w, -1)) <= 1e-5


def test_causal_conv_sees_no_future_token_and_pads_with_zeros():
    x = jax.random.normal(jax.random.key(2), (1, 16, 6))
    w = jax.random.normal(jax.random.key(3), (4, 6))
    got = la.causal_conv(x, w)
    np.testing.assert_allclose(got[0], reference._conv(x[0], w), atol=1e-6)
    # The newest tap weighs the token itself, and the first output is
    # that tap alone.
    np.testing.assert_allclose(got[0, 0], w[3] * x[0, 0], atol=1e-6)
    moved = la.causal_conv(x.at[0, 9].add(1.0), w) - got
    assert not np.asarray(moved[0, :9]).any()
    assert np.asarray(moved[0, 9:13]).any(axis=-1).all()
    assert not np.asarray(moved[0, 13:]).any()


def _linear_layer(gates):
    cfg = HYBRID_TINY
    layer = tfm.init_params(jax.random.PRNGKey(4), cfg)["layers"][0]
    lo, hi = gates
    # alpha = exp(-exp(a_log) softplus(. + dt_bias)): a softplus near
    # 0.7 (dt_bias 0) times a rate drawn from the range.
    rate = jnp.linspace(max(-hi, 1e-4), -lo, cfg.linear_value_heads)
    return dict(layer, lin_a_log=jnp.log(rate / 0.7),
                lin_dt_bias=jnp.zeros_like(layer["lin_dt_bias"]))


@pytest.mark.parametrize("gates", GATES.values(), ids=GATES.keys())
def test_linear_mixer_matches_the_reference_in_every_leaf(gates):
    """Projections, convolution, gates, recurrence, gated norm and out
    projection: forward, and the gradient of each of the eleven leaves
    and of the input."""
    cfg, layer = HYBRID_TINY, _linear_layer(gates)
    x = jax.random.normal(jax.random.key(5), (2, 256, cfg.d_model))

    def program(x, layer):
        return la.mixer(x, layer, cfg)

    def plain(x, layer):
        return jax.vmap(lambda s: reference._linear_mixer(
            s, layer, cfg.linear_value_heads, cfg.linear_key_head_dim,
            cfg.norm_eps, True, None))(x)

    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    with jax.default_matmul_precision("highest"):
        assert _rel(program(x, layer), plain(x, layer)) <= F32_REL
        got = jax.grad(loss(program), (0, 1))(x, layer)
        want = jax.grad(loss(plain), (0, 1))(x, layer)
    assert sorted(got[1]) == sorted(la.LEAVES + ("ln1_scale", "ln2_scale",
                                                 "w_gate", "w_up", "w_down"))
    assert _rel(got[0], want[0]) <= 2 * F32_REL
    for name in la.LEAVES:
        assert np.abs(np.asarray(want[1][name])).max() > 0, name
        assert _rel(got[1][name], want[1][name]) <= 4 * F32_REL, name


def test_sequence_length_must_be_whole_blocks():
    cfg, layer = HYBRID_TINY, _linear_layer(GATES["alpha_mid"])
    with pytest.raises(ValueError, match=f"block of {la.BLOCK}"):
        la.mixer(jnp.zeros((1, la.BLOCK + 8, cfg.d_model)), layer, cfg)


# --- the four-layer 3:1 model, against the plain reference ------------------


# --- refusals: never a silent fall back -------------------------------------


# --- the trees, the counters, and what the other configurations lower to ----


def test_published_gate_initialisation_ranges():
    layer = tfm.init_params(jax.random.PRNGKey(7), dataclasses.replace(
        HYBRID_TINY, linear_key_heads=64, linear_value_heads=64))["layers"][0]
    a = np.exp(np.asarray(layer["lin_a_log"]))
    dt = np.log1p(np.exp(np.asarray(layer["lin_dt_bias"])))
    assert a.min() >= la.A_INIT_RANGE[0] and a.max() <= la.A_INIT_RANGE[1]
    assert (dt.min() >= la.DT_INIT_RANGE[0] * 0.999
            and dt.max() <= la.DT_INIT_RANGE[1] * 1.001)
    assert np.abs(np.asarray(layer["lin_conv"])).max() <= 0.5


def _mixer_grads(cfg, t, norm, monkeypatch, conv="kernel"):
    """One linear mixer's output and the gradients of all of its leaves
    and of its input, the gated norm by ``norm``."""
    from horovod_tpu.telemetry import scopes

    ks = jax.random.split(jax.random.key(0), 3)
    layer = la.init_layer(
        ks[0], cfg, lambda k, shape: jax.random.normal(k, shape)
        * shape[0] ** -0.5)
    layer["lin_norm_scale"] = 1.0 + 0.1 * jax.random.normal(
        ks[0], layer["lin_norm_scale"].shape)
    x = jax.random.normal(ks[1], (2, t, cfg.d_model))
    dy = jax.random.normal(ks[2], x.shape)
    if norm == "xla":
        monkeypatch.setattr(la, "norm_path", lambda x, cfg: "xla")
    if conv == "xla":
        monkeypatch.setattr(la, "conv_path", lambda x, cfg: "xla")
    assert la.norm_path(x, cfg) == norm
    traced = str(jax.make_jaxpr(lambda l, x: la.mixer(x, l, cfg))(layer, x))
    assert (scopes.GATED_NORM_FWD in traced) is (norm == "kernel")
    with jax.default_matmul_precision("highest"):
        return (la.mixer(x, layer, cfg), jax.grad(
            lambda l, x: jnp.sum(la.mixer(x, l, cfg) * dy),
            (0, 1))(layer, x))


@pytest.mark.parametrize("t,conv", [(128, "kernel"), (64, "kernel"),
                                    (128, "xla")],
                         ids=["head_major", "one_block", "token_major"])
def test_the_mixer_through_the_gated_norm_kernels(t, conv):
    """The whole mixer with the gated norm's kernels (interpreted) against
    the ``jax.numpy`` lines: its output and the gradient of every leaf and
    of its input.  ``o`` comes head-major from the recurrence's kernels
    behind the convolution's, token-major where either is the
    ``jax.numpy`` form (64 tokens are one block, which the recurrence's
    kernels do not take)."""
    with pytest.MonkeyPatch.context() as patch:
        got = _mixer_grads(HYBRID_TINY, t, "kernel", patch, conv)
    with pytest.MonkeyPatch.context() as patch:
        want = _mixer_grads(HYBRID_TINY, t, "xla", patch, conv)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(a, np.float64) - b)
            / np.linalg.norm(b), 0.0, atol=5e-5)


def test_the_gated_norm_kernels_take_the_cells_shapes():
    """``olmohybrid_t16k``: one sequence of 16384 tokens, 30 value heads
    of 192 channels, bfloat16, ``o`` head-major from the recurrence's
    kernels.  Shapes only; nothing runs."""
    from horovod_tpu.ops import gated_norm

    cfg = dataclasses.replace(
        HYBRID_TINY, d_model=3840, linear_key_heads=30,
        linear_value_heads=30, linear_key_head_dim=96,
        linear_value_head_dim=192, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((1, 16384, 3840), jnp.bfloat16)
    assert la._o_head_major(x, cfg) and la.norm_path(x, cfg) == "kernel"
    assert gated_norm.tiles(16384, 5760, 192, True, 2, 2) == 256
    assert la.norm_path(
        jax.ShapeDtypeStruct((1, 16392, 3840), jnp.bfloat16), cfg) == "xla"


def test_block_counters_count_what_was_traced(hvd):
    from horovod_tpu import telemetry

    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        tokens = jax.ShapeDtypeStruct((2, 256), jnp.int32)
        jax.eval_shape(lambda p, t: tfm.loss_fn(
            p, t, t, HYBRID_TINY, attention="local"),
            tfm.init_abstract(HYBRID_TINY), tokens)
        text = telemetry.render_prometheus()
        # batch 2 x 2 heads x 256 / BLOCK blocks; a float32 [24, 48]
        # state kept at the start of each.  Traced outside shard_map, the
        # kernels run them (here in the interpreter).
        blocks = 2 * 2 * 256 // la.BLOCK
        for layer in (0, 1, 2):
            assert (f'hvd_gdn_blocks_total{{layer="{layer}",path="kernel"}} '
                    f'{blocks}' in text), text
            assert (f'hvd_gdn_saved_state_bytes{{layer="{layer}"}} '
                    f'{blocks * 24 * 48 * 4}') in text, text
        assert 'hvd_gdn_blocks_total{layer="3"' not in text
        # The gated norm once a linear layer, batch x T rows, by its
        # kernels too (heads of 48 channels).
        for layer in (0, 1, 2):
            assert ('hvd_gated_norm_rows_total{layer="%d",path="kernel"} 512'
                    % layer) in text, text
        assert text.count("hvd_gated_norm_rows_total{") == 3
        assert 'path="xla"' not in text
    finally:
        telemetry.reset_for_tests()


def test_block_counters_say_which_path_the_training_step_took(hvd):
    """The label is read where the path is chosen: the training step on a
    CPU mesh traces the ``jax.numpy`` form (the interpreter cannot run
    inside ``shard_map(check_vma=True)``); on a TPU mesh it traces the
    kernels (``perfbench/tests/test_chip_compile_hybrid_lm.py`` compiles
    that step)."""
    from horovod_tpu import telemetry
    from horovod_tpu.topology import build_mesh

    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        mesh = build_mesh(axes=("data",), devices=jax.devices()[:2])
        optimizer = optax.sgd(0.1)
        step, _, _ = tfm.make_train_step(HYBRID_TINY, optimizer, mesh,
                                         attention="local")
        params = tfm.init_abstract(HYBRID_TINY)
        tokens = jax.ShapeDtypeStruct((4, 256), jnp.int32)
        lowered = step.lower(params, jax.eval_shape(optimizer.init, params),
                             tokens, tokens).as_text(debug_info=True)
        text = telemetry.render_prometheus()
        blocks = 2 * 2 * 256 // la.BLOCK
        for layer in (0, 1, 2):
            assert (f'hvd_gdn_blocks_total{{layer="{layer}",path="xla"}} '
                    f'{blocks}' in text), text
            assert ('hvd_gated_norm_rows_total{layer="%d",path="xla"} 512'
                    % layer) in text, text
        assert 'path="kernel"' not in text
        assert "cumsum" in lowered and "gdn_scan_fwd" not in lowered
    finally:
        telemetry.reset_for_tests()


@pytest.mark.parametrize("cfg", (GPT2_TINY, OLMOE_TINY),
                         ids=("gpt2", "olmoe"))
def test_configurations_without_linear_layers_lower_without_them(hvd, cfg):
    """Neither the GPT-2 block nor OLMoE's holds any of the linear layer
    in its lowered step: no ``gdn`` scope, no ``lin_`` leaf; and all-``full_attention`` layer types are the empty default, to
    the byte.  (Byte-equal programs against the parent commit at the
    cells' real sizes were compiled once, by hand: PERF.md, PR 31.)"""
    from horovod_tpu.topology import build_mesh

    mesh = build_mesh(axes=("data",), devices=jax.devices()[:2])
    optimizer = optax.sgd(0.1)
    tokens = jax.ShapeDtypeStruct((4, 64), jnp.int32)

    def lowered(cfg, **kw):
        step, _, _ = tfm.make_train_step(cfg, optimizer, mesh,
                                         attention="local")
        params = tfm.init_abstract(cfg)
        return step.lower(params, jax.eval_shape(optimizer.init, params),
                          tokens, tokens).as_text(**kw)

    named = lowered(cfg, debug_info=True)
    for absent in ("gdn_", "lin_"):
        assert absent not in named, absent
    spelled = dataclasses.replace(
        cfg, layer_types=("full_attention",) * cfg.n_layers)
    assert lowered(spelled) == lowered(cfg)
    hybrid = jax.jit(lambda p, t: tfm.loss_fn(
        p, t, t, HYBRID_TINY, attention="local")).lower(
        tfm.init_abstract(HYBRID_TINY),
        jax.ShapeDtypeStruct((4, 256), jnp.int32)).as_text(debug_info=True)
    for present in ("stablehlo.while", "gdn_scan_fwd", "gdn_scan",
                    "gdn_conv", "lin_"):
        assert present in hybrid, present
