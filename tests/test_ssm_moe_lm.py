"""Layers of one part each (Mamba-2, attention alone, the latent mixture of
experts alone), grouped-query attention, one chip's share of the experts
and the multi-token-prediction loss, at tiny sizes on the virtual CPU
mesh.

Oracle: the benchmark's plain float32 reference
(``perfbench/reference/ssm_moe_lm.py``), which shares no code with the
program, walks the recurrence one token at a time and loops over the held
experts.  Tolerances, float32 everywhere unless a test says otherwise:
5e-5 relative L2, which is float32 rounding through eleven layers and a
128-token recurrence summed in another order (bfloat16 operands anywhere
read 3e-3 and up, and one test proves that).
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import mamba2, moe
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import grouped_matmul as gm
from perfbench.reference import ssm_moe_lm as reference

F32_REL = 5e-5

# The table of configurations (tests/test_lm_configs.py).  This row's
# shared families all run there and not here: this file is the suite's
# longest chain without them.
from test_lm_configs import (NEMOTRON_TINY, OLMOE_TINY,  # noqa: E402
                             nemotron_dims as _dims)

KINDS = {"M": "mamba2", "*": "attention", "E": "mlp"}
PATTERN = tuple(KINDS[c] for c in "MEMEMEM*EME")


NO_SSM = dict(ssm_heads=0, ssm_head_dim=0, ssm_state=0, ssm_groups=0,
              ssm_conv_kernel=0, ssm_chunk=0)


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


def _reference(cfg, params, tokens, labels, **kw):
    """Every leaf the reference can differentiate, not only the cell's."""
    return reference.loss_and_tail_grads(
        params, tokens, labels, dims=_dims(cfg), layer_types=cfg.layer_types,
        mtp_layer_types=cfg.mtp_layer_types, mtp_coef=cfg.mtp_loss_coef,
        names=tuple(reference.LEAVES), **kw)


def _checked(tree):
    return {name: reference.leaf(tree, path)
            for name, path in reference.leaf_paths(PATTERN).items()}


# --- the chunked recurrence -------------------------------------------------

# log a per token and head is drawn uniformly from the range.
DECAYS = {"a_mid": (-0.2, 0.0), "a_near_0": (-30.0, -5.0),
          "a_near_1": (-1e-4, 0.0)}


@pytest.mark.parametrize("chunks", (1, 3))
@pytest.mark.parametrize("decay", DECAYS.values(), ids=DECAYS.keys())
def test_chunked_recurrence_matches_token_by_token(decay, chunks):
    """Forward and all five gradients against the reference's scan over
    single tokens, at a length that is and is not one chunk."""
    chunk, h, p, n, g = 32, 4, 16, 24, 2
    t = chunks * chunk
    ks = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(ks[0], (t, h, p))
    b_in = jax.random.normal(ks[1], (t, g, n)) * n ** -0.5
    c_in = jax.random.normal(ks[2], (t, g, n))
    log_a = jax.random.uniform(ks[3], (t, h), minval=decay[0],
                               maxval=decay[1])
    delta = jax.nn.softplus(jax.random.normal(ks[4], (t, h)))

    def chunked(x, b_in, c_in, delta, log_a):
        return mamba2.ssd(x[None], b_in[None], c_in[None], delta[None],
                          log_a[None], chunk, jnp.float32)[0]

    def by_token(x, b_in, c_in, delta, log_a):
        return reference._state_space(
            x, jnp.repeat(b_in, h // g, axis=1),
            jnp.repeat(c_in, h // g, axis=1), delta, jnp.exp(log_a), None,
            None)

    args = (x, b_in, c_in, delta, log_a)
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(loss(chunked), range(5))(*args)
        want, want_g = jax.value_and_grad(loss(by_token), range(5))(*args)
        assert _rel(chunked(*args), by_token(*args)) <= F32_REL
    assert abs(got - want) <= F32_REL * abs(want)
    for name, a, b in zip("x B C delta log_a".split(), got_g, want_g):
        # Near a = 0 the gradient with respect to log a is itself tiny,
        # and what is left of it is rounding: an absolute floor at 1e-6
        # of the gradient of x.
        bound = (2e-5 * np.linalg.norm(b)
                 + 1e-6 * np.linalg.norm(want_g[0]))
        assert np.linalg.norm(np.asarray(a - b)) <= bound, name


def test_carried_state_matters_to_the_oracle():
    """The reference with its state zeroed every chunk is another
    function: what the comparison on the chip must be able to see."""
    cfg = NEMOTRON_TINY
    params, (tokens, labels) = _params(cfg), _batch(cfg)
    whole = _reference(cfg, params, tokens, labels)[1]
    reset = _reference(cfg, params, tokens, labels,
                       reset_every=cfg.ssm_chunk)[1]
    assert _rel(reset["ssm_w_out_last"], whole["ssm_w_out_last"]) > 0.05


def test_sequence_length_must_be_whole_chunks():
    cfg = NEMOTRON_TINY
    tokens = jnp.zeros((1, 48), jnp.int32)
    with pytest.raises(ValueError, match="chunk of 32"):
        jax.eval_shape(lambda p: tfm.forward(p, tokens, cfg,
                                             attention="local"),
                       tfm.init_abstract(cfg))


# --- grouped-query attention ------------------------------------------------

def test_grouped_query_attention_is_attention_over_repeated_heads():
    """Two key-value heads under four query heads against full
    multi-head attention whose wk, wv hold each head twice: the same
    output, and the gradient of a shared head is the sum over its
    group."""
    gqa = tfm.TransformerConfig(
        vocab_size=64, d_model=64, n_heads=4, n_kv_heads=2, n_layers=1,
        d_ff=32, max_seq=32, dtype=jnp.float32, layer_types=("attention",),
        positions="none", tie_embeddings=False)
    mha = dataclasses.replace(gqa, n_kv_heads=0)
    params = tfm.init_params(jax.random.PRNGKey(0), gqa)
    assert params["layers"][0]["wk"].shape == (64, 32)
    assert set(params["layers"][0]) == {"ln1_scale", "wq", "wk", "wv", "wo"}
    tokens, labels = _batch(gqa, seq=32)

    def repeated(w):           # [d, 2 * 16] -> [d, 4 * 16], head h // 2
        return jnp.repeat(w.reshape(64, 2, 16), 2, axis=1).reshape(64, 64)

    def mha_loss(layer):
        full = dict(layer, wk=repeated(layer["wk"]),
                    wv=repeated(layer["wv"]))
        return tfm.loss_fn(dict(params, layers=[full]), tokens, labels, mha,
                           attention="local")

    def gqa_loss(layer):
        return tfm.loss_fn(dict(params, layers=[layer]), tokens, labels,
                           gqa, attention="local")

    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(gqa_loss)(params["layers"][0])
        want, want_g = jax.value_and_grad(mha_loss)(params["layers"][0])
    assert abs(got - want) <= 1e-6 * abs(want)
    for name in ("wq", "wk", "wv", "wo"):
        assert _rel(got_g[name], want_g[name]) <= 1e-5, name


@pytest.mark.parametrize("attention", ("local", "flash"))
def test_grouped_heads_reach_every_attention_route(attention):
    cfg = dataclasses.replace(NEMOTRON_TINY, max_seq=128)
    params, (tokens, labels) = _params(cfg), _batch(cfg, batch=1)
    with jax.default_matmul_precision("highest"):
        got = tfm.loss_fn(params, tokens, labels, cfg, attention=attention)
        want = _reference(cfg, params, tokens, labels)[0]
    assert abs(got - want) <= F32_REL * abs(want)


# --- the grouped matmuls at widths the tile does not divide -----------------

@pytest.mark.parametrize("k,n", [(128, 384), (384, 128), (384, 640)],
                         ids=["n_21x128_like", "k_21x128_like", "both"])
def test_grouped_matmul_at_widths_its_tile_does_not_divide(monkeypatch, k,
                                                           n):
    """Tiles of 256: 384 and 640 are multiples of 128 that 256 does not
    divide, as 2688 = 21 x 128 is to 2048; they take 128."""
    monkeypatch.setattr(gm, "TILE_M", 16)
    monkeypatch.setattr(gm, "SUB_M", 4)
    monkeypatch.setattr(gm, "TILE_K", 256)
    monkeypatch.setattr(gm, "TILE_N", 256)
    sizes = jnp.asarray([30, 0, 50, 1, 47], jnp.int32)
    rows = jax.random.normal(jax.random.key(0), (128, k))
    weights = jax.random.normal(jax.random.key(1), (5, k, n)) * k ** -0.5
    group = jnp.repeat(jnp.arange(5), sizes, total_repeat_length=128)

    def plain(rows, weights):
        return jnp.einsum("mk,mkn->mn", rows, weights[group])

    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(
            loss(lambda r, w: gm.grouped_matmul(r, w, sizes)), (0, 1))(
                rows, weights)
        want, want_g = jax.value_and_grad(loss(plain), (0, 1))(rows, weights)
    assert abs(got - want) <= 1e-5 * abs(want)
    assert _rel(got_g[0], want_g[0]) <= 1e-5
    assert _rel(got_g[1], want_g[1]) <= 1e-5


@pytest.mark.parametrize("dim,tile,expected", [
    (2688, 2048, 896), (1024, 2048, 1024), (2048, 2048, 2048),
    (4096, 2048, 2048), (5376, 2048, 1792), (64, 2048, 64)])
def test_tile_rule(dim, tile, expected):
    assert gm._tile(dim, tile, "N") == expected
    assert gm._tile(dim, tile, "K") == expected


def test_tile_rule_still_refuses_what_no_tile_divides():
    with pytest.raises(ValueError, match="N=2100"):
        gm._tile(2100, 2048, "N")
    with pytest.raises(ValueError, match="rows=600"):
        gm._tile(600, 512, "rows")


def test_rows_past_the_last_group_are_nobodys(monkeypatch):
    """Group sizes that sum to less than the buffer: the rows they cover
    are right, and the gradients count no row past them."""
    monkeypatch.setattr(gm, "TILE_M", 16)
    monkeypatch.setattr(gm, "SUB_M", 4)
    sizes = jnp.asarray([10, 0, 27], jnp.int32)
    rows = jax.random.normal(jax.random.key(0), (64, 32))
    weights = jax.random.normal(jax.random.key(1), (3, 32, 48))
    live = (jnp.arange(64) < 37)[:, None]
    group = jnp.repeat(jnp.arange(3), sizes, total_repeat_length=64)

    def kernel(rows, weights):
        return jnp.where(live, gm.grouped_matmul(rows, weights, sizes), 0.0)

    def plain(rows, weights):
        return jnp.where(live, jnp.einsum("mk,mkn->mn", rows,
                                          weights[group]), 0.0)

    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    with jax.default_matmul_precision("highest"):
        got_g = jax.grad(loss(kernel), (0, 1))(rows, weights)
        want_g = jax.grad(loss(plain), (0, 1))(rows, weights)
        assert _rel(kernel(rows, weights), plain(rows, weights)) <= 1e-6
    assert _rel(got_g[0][:37], want_g[0][:37]) <= 1e-5
    assert _rel(got_g[1], want_g[1]) <= 1e-5


# --- one chip's share of the expert layer -----------------------------------

def _expert_layer(cfg, seed=0):
    """One expert layer's leaves and normed inputs ``u`` [N, d]."""
    only = dataclasses.replace(cfg, n_layers=1, layer_types=("mlp",),
                               mtp_layer_types=(), mtp_loss_coef=0.0,
                               **NO_SSM)
    layer = tfm.init_params(jax.random.PRNGKey(seed), only)["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(seed + 1), (64, cfg.d_model))
    return only, layer, u


def test_the_shares_add_up():
    """The routed parts that the four shares of sixteen experts compute,
    plus the shared expert counted once, are the uncut layer: the
    reference's with every expert in its tree."""
    cfg, layer, u = _expert_layer(NEMOTRON_TINY)
    whole = dataclasses.replace(cfg, experts_held=0, experts_held_from=0)
    k_up, k_down = jax.random.split(jax.random.PRNGKey(7))
    w_up = jax.random.normal(k_up, (16, 32, 64)) * 32 ** -0.5
    w_down = jax.random.normal(k_down, (16, 64, 32)) * 64 ** -0.5
    dims = dict(_dims(cfg), held_from=0)
    with jax.default_matmul_precision("highest"):
        want = reference._moe_part(u, dict(layer, w_up=w_up, w_down=w_down),
                                   dims, None, True)
        shared = reference._moe_part(
            u, dict(layer, w_up=w_up[:0], w_down=w_down[:0]), dims, None,
            True)
        total, rows = shared, []
        for first in range(0, 16, 4):
            share = dataclasses.replace(cfg, experts_held_from=first)
            mine = dict(layer, w_up=w_up[first:first + 4],
                        w_down=w_down[first:first + 4])
            y, held_rows = moe.latent_moe_ffn(u, mine, share)
            total = total + (y - shared)
            rows.append(held_rows)
        uncut, uncut_rows = moe.latent_moe_ffn(
            u, dict(layer, w_up=w_up, w_down=w_down), whole)
    assert _rel(total, want) <= F32_REL
    assert _rel(uncut, want) <= F32_REL
    # Every assignment is some share's, once.
    assert int(jnp.sum(jnp.concatenate(rows))) == 64 * cfg.experts_per_token
    np.testing.assert_array_equal(jnp.concatenate(rows), uncut_rows)


def test_nothing_held_is_dropped_under_an_adversarial_router():
    """Every token picks all four held experts (their router columns
    dominate): the buffer of tokens x 4 is full to the last row and every
    row is computed."""
    cfg, layer, u = _expert_layer(NEMOTRON_TINY)
    bias = jnp.zeros((16,)).at[4:8].set(10.0)
    layer = dict(layer, router_bias=bias)
    with jax.default_matmul_precision("highest"):
        got, rows = moe.latent_moe_ffn(u, layer, cfg)
        want = reference._moe_part(u, layer, _dims(cfg), None, True)
    np.testing.assert_array_equal(rows, [64, 64, 64, 64])
    assert int(rows.sum()) == moe.rows_bound(64, cfg.experts_per_token, 4)
    assert _rel(got, want) <= F32_REL


def test_an_empty_share_is_the_shared_expert_alone():
    """No token picks a held expert: the buffer holds no row, no tile is
    visited, and nothing undefined reaches the output or a gradient."""
    cfg, layer, u = _expert_layer(NEMOTRON_TINY)
    layer = dict(layer,
                 router_bias=jnp.zeros((16,)).at[4:8].set(-10.0))

    def loss(layer):
        y, rows = moe.latent_moe_ffn(u, layer, cfg)
        return jnp.sum(jnp.sin(y)), rows

    with jax.default_matmul_precision("highest"):
        (_, rows), grads = jax.value_and_grad(loss, has_aux=True)(layer)
        want = reference._moe_part(
            u, dict(layer, w_up=layer["w_up"][:0],
                    w_down=layer["w_down"][:0]), _dims(cfg), None, True)
        got = moe.latent_moe_ffn(u, layer, cfg)[0]
    assert int(rows.sum()) == 0
    assert _rel(got, want) <= F32_REL
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree_util.tree_leaves(grads))
    assert float(jnp.abs(grads["w_up"]).max()) == 0.0


def test_undefined_tails_never_meet_a_product(monkeypatch):
    """What a grouped matmul leaves past its last group is any bits, in
    its result and in the gradient of its rows (on the chip: whatever the
    buffer held, ``nan`` among it).  Poisoned with ``nan`` here: the
    layer's output and every gradient stay what they are, because every
    tail is selected away before it meets a product (``0 * nan``)."""
    cfg, layer, u = _expert_layer(NEMOTRON_TINY)

    def poison(x, sizes):
        tail = (jnp.arange(x.shape[0]) >= jnp.sum(sizes))[:, None]
        return jnp.where(tail, jnp.nan, x)

    @jax.custom_vjp
    def poisoned(rows, weights, sizes):
        return poison(gm.grouped_matmul(rows, weights, sizes), sizes)

    def fwd(rows, weights, sizes):
        return poisoned(rows, weights, sizes), (rows, weights, sizes)

    def bwd(residuals, g):
        rows, weights, sizes = residuals
        d_rows, d_weights = jax.vjp(
            lambda r, w: gm.grouped_matmul(r, w, sizes), rows, weights)[1](g)
        return poison(d_rows, sizes), d_weights, None

    poisoned.defvjp(fwd, bwd)

    def loss(layer, u):
        return jnp.sum(jnp.sin(moe.latent_moe_ffn(u, layer, cfg)[0]))

    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(loss, (0, 1))(layer, u)
        monkeypatch.setattr(moe, "grouped_matmul", poisoned)
        got, got_g = jax.value_and_grad(loss, (0, 1))(layer, u)
    assert bool(jnp.isfinite(got)) and abs(got - want) <= 1e-6 * abs(want)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


# --- the rows that are live: a prefix of the sorted buffer --------------------

@pytest.fixture()
def small_row_tiles(monkeypatch):
    """A row tile that lets a prefix of tiny shapes be shorter than their
    bound."""
    monkeypatch.setattr(gm, "TILE_M", 16)
    monkeypatch.setattr(gm, "SUB_M", 4)


def _share(held, n_experts, k, act, tokens=256, seed=0):
    """A share's config, expert leaves, inputs and a uniform router's
    choice of ``k`` distinct experts a token."""
    cfg = dataclasses.replace(
        NEMOTRON_TINY, n_experts=n_experts, experts_per_token=k,
        experts_held=held, experts_held_from=n_experts // 2)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    d, f = 32, 64
    layer = {"w_up": jax.random.normal(keys[0], (held, d, f)) * d ** -0.5,
             "w_down": jax.random.normal(keys[1], (held, f, d)) * f ** -0.5}
    if act == "swiglu":
        layer["w_gate"] = jax.random.normal(keys[2], (held, d, f)) * d ** -0.5
    h = jax.random.normal(keys[3], (tokens, d))
    top_i = jnp.argsort(jax.random.uniform(keys[4], (tokens, n_experts)),
                        axis=-1)[:, :k].astype(jnp.int32)
    top_w = jax.random.uniform(keys[5], (tokens, k), minval=0.05)
    return cfg, layer, h, top_w, top_i


def _layer_grads(run, h, slot_w, layer):
    return jax.value_and_grad(
        lambda h, w, l: jnp.sum(jnp.sin(run(h, w, l))), (0, 1, 2))(
            h, slot_w, layer)


@pytest.mark.parametrize("held,n_experts,k", [(1, 64, 6), (4, 64, 6),
                                              (8, 512, 22)],
                         ids=["1_of_64", "4_of_64", "8_of_512"])
@pytest.mark.parametrize("act", ("relu2", "swiglu"))
def test_the_prefix_computes_what_the_bound_computes(small_row_tiles, act,
                                                     held, n_experts, k):
    """The same inputs over the prefix and over all of the buffer: value
    and the gradients of the tokens, the routing weights and every expert
    leaf."""
    cfg, layer, h, top_w, top_i = _share(held, n_experts, k, act)
    slot_w, slot_e, sizes, prefix = moe.this_chips_share(top_w, top_i, cfg)
    bound = moe.rows_bound(256, k, held)
    assert prefix % gm.TILE_M == 0 and int(sizes.sum()) <= prefix < bound

    def over(rows):
        return lambda h, w, l: moe.experts_ffn(h, w, slot_e, sizes, l,
                                               jnp.float32, act=act,
                                               prefix=rows)

    with jax.default_matmul_precision("highest"):
        got, got_g = _layer_grads(over(prefix), h, slot_w, layer)
        want, want_g = _layer_grads(over(bound), h, slot_w, layer)
    assert abs(got - want) <= 1e-6 * abs(want)
    assert set(got_g[2]) == set(layer)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        assert float(jnp.abs(b).max()) > 0.0
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _per_slot_oracle(h, slot_w, slot_e, layer):
    """float32, a slot at a time, every token through every held expert."""
    y = jnp.zeros_like(h)
    for e in range(layer["w_up"].shape[0]):
        out = jnp.square(jax.nn.relu(h @ layer["w_up"][e])) @ \
            layer["w_down"][e]
        y = y + jnp.sum(jnp.where(slot_e == e, slot_w, 0.0), axis=1,
                        keepdims=True) * out
    return y


@pytest.mark.parametrize("live", (64, 65, 256),
                         ids=["fills_the_prefix", "one_row_past_it",
                              "fills_the_bound"])
def test_a_batch_past_the_prefix_is_computed_whole(small_row_tiles, live):
    """64 tokens, four held experts, a prefix of 64 rows of the 256: with
    exactly 64 held rows the prefix holds them; with 65, and with all
    256, the layer works on every slot and drops nothing: value and
    gradients are the per-slot float32 oracle's, which the buffer's head
    alone is not, and not by a matter of tolerance."""
    _, layer, h, _, _ = _share(4, 64, 6, "relu2", tokens=64)
    key = jax.random.PRNGKey(live)
    held_here = jnp.zeros((256,), bool).at[
        jax.random.permutation(key, 256)[:live]].set(True).reshape(64, 4)
    slot_e = jnp.where(held_here, jnp.arange(4)[None, :], 4).astype(jnp.int32)
    slot_w = jnp.where(held_here, jax.random.uniform(key, (64, 4),
                                                     minval=0.5), 0.0)
    sizes = jnp.sum(held_here, axis=0, dtype=jnp.int32)
    run = lambda h, w, l: moe.experts_ffn(h, w, slot_e, sizes, l,
                                          jnp.float32, act="relu2",
                                          prefix=64)
    oracle = lambda h, w, l: _per_slot_oracle(h, w, slot_e, l)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(lambda *a: _layer_grads(run, *a))(
            h, slot_w, layer)
        want, want_g = _layer_grads(oracle, h, slot_w, layer)
        head = moe._head_ffn(
            64, "relu2", jnp.float32, h, slot_w, jnp.argsort(
                slot_e.reshape(-1), stable=True).astype(jnp.int32), sizes,
            layer)
    assert abs(got - want) <= F32_REL * abs(want)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        assert _rel(a, b) <= F32_REL
    lost = _rel(head, oracle(h, slot_w, layer))
    assert lost <= F32_REL if live == 64 else lost > 1e-3


def test_prefix_arithmetic(hvd):
    """Derived from the shapes: whole row tiles or the bound, never past
    the bound, the bound where every expert is held, and no shorter for
    holding more."""
    from horovod_tpu import telemetry

    tile = gm.TILE_M
    assert moe.rows_prefix(8192, 22, 8, 512) == 11264 == 22 * tile
    assert moe.rows_prefix(8192, 22, 512, 512) == 8192 * 22
    assert moe.rows_prefix(8192, 8, 64, 64) == moe.rows_bound(8192, 8, 64)
    for tokens, k, n_experts in ((8192, 22, 512), (8192, 8, 64),
                                 (256, 6, 16), (4096, 1, 128), (96, 2, 8)):
        before = 0
        for held in range(1, n_experts + 1):
            bound = moe.rows_bound(tokens, k, held)
            prefix = moe.rows_prefix(tokens, k, held, n_experts)
            assert before <= prefix <= bound
            assert prefix == bound or prefix % tile == 0
            assert prefix >= min(bound, moe.PREFIX_SLACK * tokens * k * held
                                 / n_experts)
            before = prefix
        assert prefix == bound
    cfg = dataclasses.replace(NEMOTRON_TINY, n_experts=512,
                              experts_per_token=22, experts_held=8)
    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        moe.record_held("x", 8192, cfg)
        text = telemetry.render_prometheus()
        assert 'hvd_moe_rows_bound{layer="x"} 65536' in text, text
        assert 'hvd_moe_rows_prefix{layer="x"} 11264' in text, text
    finally:
        telemetry.reset_for_tests()


def _eqns(jaxpr, cond_branch=None):
    """Every equation of ``jaxpr`` and of the jaxprs inside it (calls,
    loop bodies, branches) but the kernels' own; ``cond_branch``: of a
    conditional, that branch alone."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        values = ([eqn.params["branches"][cond_branch]]
                  if eqn.primitive.name == "cond" and cond_branch is not None
                  else eqn.params.values())
        for value in values:
            for inner in value if isinstance(value, (tuple, list)) else (
                    value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, cond_branch)


def _as_long_as_the_bound(eqns, bound):
    """Results with a row of numbers for every place of the buffer
    (indices, counts and masks are not such rows)."""
    return [v.aval for eqn in eqns for v in eqn.outvars
            if len(v.aval.shape) == 2 and v.aval.shape[0] == bound
            and v.aval.shape[1] > 1
            and jnp.issubdtype(v.aval.dtype, jnp.floating)]


@pytest.mark.parametrize("recompute", (False, True),
                         ids=["kept", "recomputed"])
def test_an_ordinary_batch_makes_no_pass_over_the_bound(recompute):
    """The jaxpr of a share's layer and its gradient, on the branch an
    ordinary batch takes: nothing is as long as the bound but indices,
    counts and masks; the other branch is the layer of before, and the
    search sees its rows.  Either branch runs forward twice, also where
    the model recomputes the layer for the backward pass: nothing there
    needs the recomputed result, so it is dropped."""
    cfg, layer, u = _expert_layer(dataclasses.replace(
        NEMOTRON_TINY, n_experts=512, experts_per_token=22, experts_held=8,
        experts_held_from=8))
    u = jnp.tile(u, (32, 1))
    tokens, bound = u.shape[0], moe.rows_bound(u.shape[0], 22, 8)
    assert moe.rows_prefix(tokens, 22, 8, 512) == 6 * gm.TILE_M < bound
    block = lambda layer, u: u + moe.latent_moe_ffn(u, layer, cfg)[0]
    if recompute:
        block = jax.checkpoint(block)
    jaxpr = jax.make_jaxpr(jax.grad(lambda layer, u: jnp.sum(jnp.sin(
        block(layer, u))), (0, 1)))(layer, u).jaxpr
    # lax.cond's branches: (false, true) = (every slot, the head).
    ordinary, overflow = list(_eqns(jaxpr, 1)), list(_eqns(jaxpr, 0))
    # Forward and backward, each choosing for itself.
    assert [e.primitive.name for e in ordinary].count("cond") == 2
    assert not _as_long_as_the_bound(ordinary, bound)
    assert _as_long_as_the_bound(overflow, bound)
    for taken in (ordinary, overflow):
        kernels = [e.params["name"] for e in taken
                   if e.primitive.name == "pallas_call"]
        # The router's choice, outside the conditional: its mask is kept
        # for the backward pass, or computed again with the block.
        chooses = kernels.count("moe_choose")
        assert chooses == 1 + recompute
        # 2 products forward; the backward computes them again and their
        # 2 + 2 gradients.
        assert len(kernels) - chooses == 2 + 2 + 2 * 2


def test_an_ordinary_batch_on_the_kernels_sums_by_token():
    """The same layer with rows that the kernels of ``ops/moe_rows.py``
    take (bf16, a latent width of one tile of float32): the ordinary
    branch holds no scatter-add of rows, its sums (the combine's, and
    the gather's gradient) are the kernel pair, and forward nothing
    float32 is the prefix's rows by the rows' width; the overflow branch
    is the layer of before."""
    from horovod_tpu.ops import moe_rows
    from horovod_tpu.telemetry import scopes

    cfg, layer, u = _expert_layer(dataclasses.replace(
        NEMOTRON_TINY, dtype=jnp.bfloat16, d_latent=1024, n_experts=512,
        experts_per_token=22, experts_held=8, experts_held_from=8))
    u = jnp.tile(u, (32, 1)).astype(jnp.bfloat16)
    prefix = moe.rows_prefix(u.shape[0], 22, 8, 512)
    assert moe.moves_path(u, cfg) == "kernel"
    block = lambda layer, u: u + moe.latent_moe_ffn(u, layer, cfg)[0]

    def sums_and_scatters(eqns):
        return ([e.params["name"] for e in eqns
                 if e.primitive.name == "pallas_call"
                 and e.params["name"].startswith("moe_row")],
                [e for e in eqns if e.primitive.name == "scatter-add"
                 and len(e.outvars[0].aval.shape) == 2
                 and ("moe_dispatch" in str(e.source_info.name_stack)
                      or "moe_combine" in str(e.source_info.name_stack))])

    jaxpr = jax.make_jaxpr(jax.grad(lambda layer, u: jnp.sum(jnp.sin(
        block(layer, u).astype(jnp.float32))), (0, 1)))(layer, u).jaxpr
    kernels, scatters = sums_and_scatters(list(_eqns(jaxpr, 1)))
    # Forward; the backward computes the form again (its sum is then dead
    # code) and sums the gather's gradient.
    assert sorted(kernels) == 3 * [scopes.MOE_ROW_TILES] + 3 * [
        scopes.MOE_ROWS_BACK] and not scatters
    kernels, scatters = sums_and_scatters(list(_eqns(jaxpr, 0)))
    assert not kernels and not scatters            # gathers both ways
    forward = list(_eqns(jax.make_jaxpr(block)(layer, u).jaxpr, 1))
    assert not [v for e in forward for v in e.outvars
                if v.aval.dtype == jnp.float32
                and v.aval.shape == (prefix, 1024)]
    assert [v for e in forward for v in e.outvars
            if v.aval.shape == (prefix, 1024 // moe_rows.LANES,
                                moe_rows.LANES)]


@pytest.mark.parametrize("layer_of", ("softmax_swiglu", "latent_relu2"))
def test_every_expert_held_is_one_pass(layer_of):
    """Where the chip holds every expert the prefix is the bound: one
    pass over it, no loop and no conditional, forward or backward."""
    if layer_of == "softmax_swiglu":
        cfg = OLMOE_TINY
        layer = tfm.init_params(jax.random.PRNGKey(0), cfg)["layers"][0]
        run = lambda layer, h: moe.moe_ffn(h, layer, cfg)[0]
    else:
        cfg, layer, _ = _expert_layer(dataclasses.replace(
            NEMOTRON_TINY, experts_held=0, experts_held_from=0))
        run = lambda layer, h: moe.latent_moe_ffn(h, layer, cfg)[0]
    h = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    assert cfg.held_experts == cfg.n_experts
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(run(*a)), (0, 1)))(
        layer, h).jaxpr
    eqns = list(_eqns(jaxpr))
    names = {e.primitive.name for e in eqns}
    assert "pallas_call" in names and not names & {"cond", "while"}
    # Rows move by gathers, both ways.
    assert not [e for e in eqns if e.primitive.name == "scatter-add"
                and ("moe_dispatch" in str(e.source_info.name_stack)
                     or "moe_combine" in str(e.source_info.name_stack))]


def test_sigmoid_router_weighs_over_all_chosen_and_bias_only_chooses():
    h = jax.random.normal(jax.random.key(0), (32, 16))
    w = jax.random.normal(jax.random.key(1), (16, 8))
    bias = jnp.zeros((8,)).at[3].set(100.0)
    top_w, top_i = moe.route_sigmoid(h, w, bias, 3, 5.0)
    scores = jax.nn.sigmoid(h @ w)
    assert bool(jnp.all(jnp.any(top_i == 3, axis=1)))
    np.testing.assert_allclose(jnp.sum(top_w, axis=1), 5.0, rtol=1e-5)
    chosen = jnp.take_along_axis(scores, top_i, axis=1)
    np.testing.assert_allclose(
        top_w, 5.0 * chosen / chosen.sum(1, keepdims=True), rtol=1e-5)
    g = jax.grad(lambda b: jnp.sum(moe.route_sigmoid(h, w, b, 3, 5.0)[0]
                                   ** 2))(bias)
    assert float(jnp.abs(g).max()) == 0.0


def test_held_slots_keep_every_held_assignment():
    """More experts a token than held here: the slots are the held ones,
    whatever their place among the token's choices."""
    top_i = jnp.asarray([[9, 4, 0, 7, 12, 5], [0, 1, 2, 3, 8, 9],
                         [15, 14, 6, 13, 12, 11]], jnp.int32)
    top_w = jnp.arange(18, dtype=jnp.float32).reshape(3, 6) + 1.0
    slot_w, slot_e = moe.held_slots(top_w, top_i, 4, 4)
    assert slot_e.shape == (3, 4)
    for row, want in enumerate(({0: 2.0, 3: 4.0, 1: 6.0}, {}, {2: 15.0})):
        got = {int(e): float(w) for e, w in zip(slot_e[row], slot_w[row])
               if e < 4}
        assert got == want
        assert float(slot_w[row][slot_e[row] == 4].sum()) == 0.0


def _routers_series(cfg, layer, u):
    """What ``record_router`` says of the layer, as Prometheus text."""
    from horovod_tpu import telemetry

    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        moe.record_router("x", u, layer, cfg)
        return telemetry.render_prometheus()
    finally:
        telemetry.reset_for_tests()


# The rehearsal shapes (16 experts, 4 held from 4, top-6: the mask from
# ``lax.top_k``, 16 experts being no lane group) and the cell's own
# (8 of 512 from 40, top-22, whole lane groups of tokens: the kernel).
NARROW_SHARES = {
    "rehearsal": (dict(), 64, "threshold_xla"),
    "cell": (dict(n_experts=512, experts_per_token=22, experts_held=8,
                  experts_held_from=40), 128, "threshold_kernel")}


@pytest.mark.parametrize("shape", sorted(NARROW_SHARES))
def test_a_share_no_wider_than_the_choice_routes_by_the_mask(shape,
                                                             monkeypatch):
    """The dense form's slots, rows, layer output and gradients are
    ``route_sigmoid`` + ``held_slots``' to float32 rounding: the same
    set, the sum of the chosen scores added in another order."""
    fields, tokens, path = NARROW_SHARES[shape]
    cfg, layer, u = _expert_layer(dataclasses.replace(NEMOTRON_TINY,
                                                      **fields))
    u = jnp.tile(u, (tokens // 64, 1)) + 0.1 * jax.random.normal(
        jax.random.PRNGKey(5), (tokens, cfg.d_model))
    layer = dict(layer, router_bias=0.05 * jax.random.normal(
        jax.random.PRNGKey(6), (cfg.n_experts,)))
    held, k = cfg.held_experts, cfg.experts_per_token
    assert held <= k and moe.choice_path(u, layer, cfg) == path
    text = _routers_series(cfg, layer, u)
    assert ('hvd_moe_router_choices_total{layer="x",path="%s"} 1' % path
            in text), text

    slot_w, slot_e, rows, prefix = moe._sigmoid_share(u, layer, cfg)
    top_w, top_i = moe.route_sigmoid(u, layer["router"],
                                     layer["router_bias"], k,
                                     cfg.routed_scale)
    want_w, want_e, want_rows, want_prefix = moe.this_chips_share(
        top_w, top_i, cfg)
    assert prefix == want_prefix and slot_w.shape == want_w.shape
    np.testing.assert_array_equal(rows, want_rows)
    assert int(rows.sum()) > 0
    # Held expert j has slot j; the index form's slots are sorted.
    by_expert = np.zeros((tokens, held + 1), np.float32)
    np.put_along_axis(by_expert, np.asarray(want_e), np.asarray(want_w), 1)
    np.testing.assert_allclose(slot_w, by_expert[:, :held], rtol=1e-6)
    np.testing.assert_array_equal(
        slot_e, np.where(by_expert[:, :held] > 0, np.arange(held), held))

    def loss_and_grads():
        return jax.value_and_grad(lambda layer, u: jnp.sum(jnp.sin(
            moe.latent_moe_ffn(u, layer, cfg)[0])), (0, 1))(layer, u)

    loss, (d_layer, d_u) = loss_and_grads()
    monkeypatch.setattr(moe, "choice_path", lambda *a: "top_k")
    want, (want_layer, want_u) = loss_and_grads()
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    assert _rel(d_u, want_u) <= 1e-6
    assert float(jnp.abs(d_layer["router"]).max()) > 0
    for name, g in d_layer.items():
        if name in ("router_bias", "ln2_scale"):   # chooses only; the norm
            assert float(jnp.abs(g).max()) == 0.0  # is outside the layer
        else:
            assert _rel(g, want_layer[name]) <= 1e-6, name


@pytest.mark.parametrize("fields,why", [
    (dict(experts_held=8), "a share wider than the choice (8 > 6)"),
    (dict(experts_held=0, experts_held_from=0), "every expert held")])
def test_elsewhere_the_sigmoid_router_is_top_ks_indices(fields, why):
    cfg, layer, u = _expert_layer(dataclasses.replace(NEMOTRON_TINY,
                                                      **fields))
    assert moe.choice_path(u, layer, cfg) == "top_k", why
    assert ('hvd_moe_router_choices_total{layer="x",path="top_k"} 1'
            in _routers_series(cfg, layer, u))
    names = {e.primitive.name for e in _eqns(jax.make_jaxpr(
        lambda layer, u: moe.latent_moe_ffn(u, layer, cfg)[0])(
            layer, u).jaxpr)}
    assert "top_k" in names


def test_the_softmax_router_is_top_ks_indices():
    cfg = OLMOE_TINY
    layer = tfm.init_params(jax.random.PRNGKey(0), cfg)["layers"][0]
    u = jnp.zeros((64, cfg.d_model))
    assert moe.choice_path(u, layer, cfg) == "top_k"


# --- the whole model --------------------------------------------------------


def test_the_second_loss_is_the_prediction_modules():
    """Without the module the loss is the first term alone, and the
    module's term is mtp_loss_coef times the rest."""
    cfg = NEMOTRON_TINY
    params, (tokens, labels) = _params(cfg), _batch(cfg)
    plain = dataclasses.replace(cfg, mtp_layer_types=(), mtp_loss_coef=0.0)
    bare = {k: v for k, v in params.items() if k != "mtp"}
    doubled = dataclasses.replace(cfg, mtp_loss_coef=0.2)
    first = tfm.loss_fn(bare, tokens, labels, plain, attention="local")
    both = tfm.loss_fn(params, tokens, labels, cfg, attention="local")
    more = tfm.loss_fn(params, tokens, labels, doubled, attention="local")
    assert float(both) > float(first)
    np.testing.assert_allclose(more - first, 2 * (both - first), rtol=1e-4)
    assert "mtp" not in tfm.init_params(jax.random.PRNGKey(0), plain)


def test_train_step_over_the_prefix_takes_the_same_gradient(hvd):
    """A share small enough for the prefix to be shorter than the bound
    (4 of 64 experts, 512 tokens: 1,024 rows of 2,048), through
    ``make_train_step`` under ``remat="full"``: the loss and every
    gradient the reference returns, and every expert layer's batch on the
    prefix (the reference's routing)."""
    from horovod_tpu.topology import build_mesh

    cfg = dataclasses.replace(NEMOTRON_TINY, n_experts=64)
    prefix = moe.rows_prefix(4 * 128, cfg.experts_per_token, 4, 64)
    assert prefix == 1024 < moe.rows_bound(4 * 128, cfg.experts_per_token, 4)
    mesh = build_mesh(axes=("data",), devices=jax.devices()[:1])
    optimizer = optax.sgd(0.1, momentum=0.9)
    step, _, _ = tfm.make_train_step(cfg, optimizer, mesh, attention="local",
                                     donate=False, remat="full")
    params = _params(cfg)
    tokens, labels = _batch(cfg, batch=4)
    _, opt_state, loss = step(params, optimizer.init(params), tokens, labels)
    want, want_g, stats = jax.jit(lambda *a: _reference(cfg, *a))(
        params, tokens, labels)
    assert 0 < int(stats["rows"].sum(axis=1).max()) <= prefix
    assert abs(loss - want) <= F32_REL * abs(want)
    momentum = _checked(opt_state[0].trace)
    for name, g in want_g.items():
        assert _rel(momentum[name], g) <= F32_REL, name


# --- refusals: never a silent fall back -------------------------------------


@pytest.mark.parametrize("field,value", [
    ("n_kv_heads", 1), ("layer_types", ("attention", "mlp")),
    ("mtp_layer_types", ("attention",))])
def test_model_axis_decode_and_the_pipelined_builder_refuse_by_name(
        hvd, field, value):
    from horovod_tpu.topology import build_mesh

    fields = {field: value}
    if field == "mtp_layer_types":
        fields["mtp_loss_coef"] = 0.1
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq=32, **fields)
    params = tfm.init_abstract(cfg)
    with pytest.raises(NotImplementedError, match=field):
        tfm.decode_step(params, jnp.zeros((2,), jnp.int32),
                        tfm.init_kv_cache(cfg, 2, 8), 0, cfg)
    mesh = build_mesh(axes=("data", "pipe"), shape=(2, 2),
                      devices=jax.devices()[:4])
    with pytest.raises(NotImplementedError, match=field):
        tfm.make_train_step_pipelined(cfg, optax.sgd(0.1), mesh)
    mesh = build_mesh(axes=("data", "model"), shape=(2, 2),
                      devices=jax.devices()[:4])
    if field == "layer_types":
        # The parts are asked, not the field: an attention-only and an
        # MLP-only layer are the GPT-2 block's two parts, each split over
        # the model axis as it is in a whole layer (the test below).
        return
    with pytest.raises(NotImplementedError, match=field):
        tfm.make_train_step(cfg, optax.sgd(0.1), mesh, model_axis="model")


def test_attention_only_and_mlp_only_layers_split_over_the_model_axis(hvd):
    """A GPT-2 block written as an attention-only and an MLP-only layer
    takes, on a 2 x 2 data x model mesh, the three steps one device takes
    on the same batch (the oracle of ``tests/test_parallel.py``), and
    every leaf ends where it ends there."""
    from jax.sharding import NamedSharding, PartitionSpec

    from horovod_tpu.topology import build_mesh

    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=4, d_ff=64,
        max_seq=32, dtype=jnp.float32,
        layer_types=("attention", "mlp", "mlp", "attention"))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 64)

    def three_steps(axes, shape, **axis):
        mesh = build_mesh(axes=axes, shape=shape,
                          devices=jax.devices()[:int(np.prod(shape))])
        optimizer = optax.sgd(0.1, momentum=0.9)
        step, specs, opt_specs = tfm.make_train_step(
            cfg, optimizer, mesh, attention="local", donate=False, **axis)
        place = lambda tree, spec: jax.device_put(
            tree, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), spec,
                is_leaf=lambda x: isinstance(x, PartitionSpec)))
        params = place(tfm.init_params(jax.random.PRNGKey(0), cfg), specs)
        opt_state = place(optimizer.init(params), opt_specs)
        losses = []
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, toks[:, :-1],
                                           toks[:, 1:])
            losses.append(float(loss))
        return losses, params

    got, got_p = three_steps(("data", "model"), (2, 2), model_axis="model")
    want, want_p = three_steps(("data",), (1,))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert want[-1] < want[0]
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_p),
                            jax.tree_util.tree_leaves(want_p)):
        assert _rel(a, b) <= 1e-5, path


def test_published_decay_initialisation_ranges():
    layer = mamba2.init_layer(jax.random.PRNGKey(0), dataclasses.replace(
        NEMOTRON_TINY, ssm_heads=512, ssm_groups=2),
        lambda key, shape: jnp.zeros(shape))
    a, dt = jnp.exp(layer["ssm_a_log"]), jax.nn.softplus(layer["ssm_dt_bias"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) <= 0.1 * 1.001


# Widths the Mamba-2 scan kernels take (``ops/mamba2_scan.takes``): a
# chunk, a group's channels and a state of whole lanes.
KERNEL_WIDTHS = dict(ssm_heads=16, ssm_head_dim=16, ssm_state=128,
                     ssm_chunk=128)


@pytest.mark.parametrize("widths,path", [({}, "xla"),
                                         (KERNEL_WIDTHS, "kernel")],
                         ids=["narrow", "whole_lanes"])
def test_trace_time_series_count_what_was_traced(hvd, widths, path):
    """Traced outside ``shard_map``, the kernels run the recurrence (here
    in the interpreter) wherever they take the layer's widths."""
    from horovod_tpu import telemetry

    cfg = dataclasses.replace(NEMOTRON_TINY, **widths)
    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
        jax.eval_shape(lambda p, t: tfm.loss_fn(
            p, t, t, cfg, attention="local"), tfm.init_abstract(cfg), tokens)
        text = telemetry.render_prometheus()
        chunks = 2 * cfg.ssm_heads * 128 // cfg.ssm_chunk
        for layer in (0, 2, 4, 6, 9):
            assert (f'hvd_ssm_chunks_total{{layer="{layer}",path="{path}"}} '
                    f'{chunks}') in text, text
            assert (f'hvd_ssm_saved_state_bytes{{layer="{layer}"}} '
                    f'{chunks * cfg.ssm_head_dim * cfg.ssm_state * 4}'
                    ) in text, text
        for layer in ("1", "3", "5", "8", "10", "mtp_1"):
            assert f'hvd_moe_experts_held{{layer="{layer}"}} 4' in text, text
            assert (f'hvd_moe_rows_bound{{layer="{layer}"}} '
                    f'{256 * 4}') in text, text
            # 4 x the 384 rows expected, in whole tiles, is past the bound.
            assert (f'hvd_moe_rows_prefix{{layer="{layer}"}} '
                    f'{256 * 4}') in text, text
        assert 'hvd_ssm_chunks_total{layer="1"' not in text
        other = {"xla": "kernel", "kernel": "xla"}[path]
        for series in ("hvd_ssm_chunks_total", "hvd_short_conv_rows_total"):
            assert not re.search(rf'{series}{{[^}}]*path="{other}"', text)
        # The gated norm's kernels take both: groups of 32 and of 128
        # channels are slabs of whole groups and whole lanes.  Once a
        # mixer layer, batch x T rows.
        for layer in (0, 2, 4, 6, 9):
            assert ('hvd_gated_norm_rows_total{layer="%d",path="kernel"} 256'
                    % layer) in text, text
        assert text.count("hvd_gated_norm_rows_total{") == 5
        # Data, not static, on a share: not counted.
        assert "hvd_moe_assignments_total" not in text
    finally:
        telemetry.reset_for_tests()


def _mixer_grads(cfg, t, norm, monkeypatch):
    """One Mamba-2 mixer's output and the gradients of all of its leaves
    and of its input, the gated norm by ``norm``."""
    from horovod_tpu.models import mamba2
    from horovod_tpu.telemetry import scopes

    ks = jax.random.split(jax.random.key(0), 3)
    layer = mamba2.init_layer(
        ks[0], cfg, lambda k, shape: jax.random.normal(k, shape)
        * shape[0] ** -0.5)
    layer["ssm_norm_scale"] = 1.0 + 0.1 * jax.random.normal(
        ks[0], layer["ssm_norm_scale"].shape)
    u = jax.random.normal(ks[1], (2, t, cfg.d_model))
    dy = jax.random.normal(ks[2], u.shape)
    if norm == "xla":
        monkeypatch.setattr(mamba2, "norm_path", lambda u, cfg: "xla")
    assert mamba2.norm_path(u, cfg) == norm
    traced = str(jax.make_jaxpr(lambda l, u: mamba2.mixer(u, l, cfg))(
        layer, u))
    assert (scopes.GATED_NORM_FWD in traced) is (norm == "kernel")
    with jax.default_matmul_precision("highest"):
        return (mamba2.mixer(u, layer, cfg), jax.grad(
            lambda l, u: jnp.sum(mamba2.mixer(u, l, cfg) * dy),
            (0, 1))(layer, u))


@pytest.mark.parametrize("widths", [{}, KERNEL_WIDTHS],
                         ids=["narrow", "whole_lanes"])
def test_the_mixer_through_the_gated_norm_kernels(widths):
    """The whole mixer with the gated norm's kernels (interpreted) against
    the ``jax.numpy`` lines: its output and the gradient of every leaf and
    of its input; groups of 32 channels (two of them a register) and of
    128."""
    cfg = dataclasses.replace(NEMOTRON_TINY, **widths)
    with pytest.MonkeyPatch.context() as patch:
        got = _mixer_grads(cfg, 128, "kernel", patch)
    with pytest.MonkeyPatch.context() as patch:
        want = _mixer_grads(cfg, 128, "xla", patch)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(a, np.float64) - b)
            / np.linalg.norm(b), 0.0, atol=5e-5)


def test_the_gated_norm_kernels_take_the_cells_shapes():
    """``nemotron3s_t8192``: one sequence of 8192 tokens, 128 heads of 64
    channels in 8 norm groups of 1024, bfloat16 with the scan's ``y`` in
    float32.  Shapes only; nothing runs."""
    from horovod_tpu.models import mamba2
    from horovod_tpu.ops import gated_norm

    cfg = dataclasses.replace(
        NEMOTRON_TINY, d_model=4096, ssm_heads=128, ssm_head_dim=64,
        ssm_state=128, ssm_groups=8, ssm_chunk=128, dtype=jnp.bfloat16)
    u = jax.ShapeDtypeStruct((1, 8192, 4096), jnp.bfloat16)
    assert mamba2.norm_path(u, cfg) == "kernel"
    assert gated_norm.tiles(8192, 8192, 1024, False, 4, 2) == 128
    # A length that is not whole sublane tiles goes the other way.
    assert mamba2.norm_path(
        jax.ShapeDtypeStruct((1, 8200, 4096), jnp.bfloat16), cfg) == "xla"


def test_chunk_counters_say_which_path_the_training_step_took(hvd):
    """The label is read where the path is chosen: a training step whose
    ``shard_map`` checks varying axes (no experts) traces the
    ``jax.numpy`` form on a CPU mesh, where the interpreter cannot run;
    on a TPU mesh it traces the kernels
    (``tests/test_flash_compile.py`` compiles them)."""
    from horovod_tpu import telemetry
    from horovod_tpu.topology import build_mesh

    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq=128, dtype=jnp.float32, positions="none",
        layer_types=("mamba2", "mlp"), ssm_groups=2, ssm_conv_kernel=4,
        **KERNEL_WIDTHS)
    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        mesh = build_mesh(axes=("data",), devices=jax.devices()[:2])
        optimizer = optax.sgd(0.1)
        step, _, _ = tfm.make_train_step(cfg, optimizer, mesh,
                                         attention="local")
        params = tfm.init_abstract(cfg)
        tokens = jax.ShapeDtypeStruct((4, 128), jnp.int32)
        lowered = step.lower(params, jax.eval_shape(optimizer.init, params),
                             tokens, tokens).as_text(debug_info=True)
        text = telemetry.render_prometheus()
        assert 'hvd_ssm_chunks_total{layer="0",path="xla"} 32' in text, text
        assert ('hvd_gated_norm_rows_total{layer="0",path="xla"} 256'
                in text), text
        assert 'path="kernel"' not in text
        assert "cumsum" in lowered and "ssm_scan_fwd" not in lowered
    finally:
        telemetry.reset_for_tests()


def test_all_experts_held_is_the_layer_of_before(hvd):
    """With every expert held the SwiGLU layer's step lowers without the
    share's masks, and says how many it holds."""
    from horovod_tpu import telemetry
    from horovod_tpu.topology import build_mesh

    mesh = build_mesh(axes=("data",), devices=jax.devices()[:2])
    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        step, _, _ = tfm.make_train_step(OLMOE_TINY, optax.sgd(0.1), mesh,
                                         attention="local")
        params = tfm.init_abstract(OLMOE_TINY)
        tokens = jax.ShapeDtypeStruct((4, 32), jnp.int32)
        text = step.lower(params, jax.eval_shape(optax.sgd(0.1).init,
                                                 params),
                          tokens, tokens).as_text()
        assert "moe_latent" not in text and "ssm_" not in text
        assert "mtp" not in text
        series = telemetry.render_prometheus()
        assert 'hvd_moe_experts_held{layer="0"} 8' in series
        assert 'hvd_moe_assignments_total{layer="0"}' in series
    finally:
        telemetry.reset_for_tests()


def test_the_shares_of_a_gated_softmax_layer_add_up():
    """Which experts are held and which form they have are two things:
    two shares of the SwiGLU, softmax-routed layer's eight experts sum to
    the layer with all of them held, value and gradient, and the router's
    statistics (over all eight) are every share's."""
    whole = dataclasses.replace(OLMOE_TINY, dtype=jnp.float32)
    layer = tfm.init_params(jax.random.PRNGKey(0), whole)["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(1), (64, whole.d_model))

    def share_of(h, first):
        cfg = dataclasses.replace(whole, experts_held=4,
                                  experts_held_from=first)
        mine = dict(layer, **{name: layer[name][first:first + 4]
                              for name in moe.EXPERT_LEAVES})
        assert jax.tree_util.tree_map(jnp.shape, mine) == \
            jax.tree_util.tree_map(
                jnp.shape, tfm.init_abstract(cfg)["layers"][0])
        return moe.moe_ffn(h, mine, cfg)

    def loss(f):
        return lambda h: jnp.sum(jnp.sin(f(h)))

    with jax.default_matmul_precision("highest"):
        want, stats = moe.moe_ffn(h, layer, whole)
        for first in (0, 4):
            np.testing.assert_array_equal(share_of(h, first)[1].counts,
                                          stats.counts)
        both = lambda h: share_of(h, 0)[0] + share_of(h, 4)[0]
        want_g = jax.grad(loss(lambda h: moe.moe_ffn(h, layer, whole)[0]))(h)
        got_g = jax.grad(loss(both))(h)
    assert _rel(both(h), want) <= F32_REL
    assert _rel(got_g, want_g) <= F32_REL
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(OLMOE_TINY, experts_held=4, experts_held_from=6)
