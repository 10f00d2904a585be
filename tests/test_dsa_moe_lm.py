"""Keye-VL-2.0's language block through the normal LM step, at tiny widths
that keep the shape of the thing: grouped heads whose total width is not
the hidden size, QK-norm a head at a time, an indexer beside every
attention layer that chooses each query's keys and learns from its own
loss, and softmax-routed SwiGLU experts of which this chip holds a share;
against the plain reference of ``perfbench/reference/dsa_moe_lm.py``, which
shares no code with the program, and the Pallas kernels of
``horovod_tpu/ops/sparse_attention.py`` in the interpreter against their
``jax.numpy`` forms.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import attention, moe
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import sparse_attention as sa
from perfbench.reference import dsa_moe_lm as reference

F32_REL = 5e-5

# The families every configuration shares, and the table of configurations
# (tests/test_lm_configs.py); those that compile this row's program run
# here, in the row's own file: a file is one worker's chain.
from test_lm_configs import *  # noqa: E402,F401,F403
from test_lm_configs import KEYE_TINY, keye_dims as _dims  # noqa: E402

COSTLY_ROWS = ("keye",)

INDEX_LEAVES = ("index_wq", "index_wk", "index_ww")


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


def test_the_loss_is_the_cross_entropy_plus_the_indexers_kl():
    """Both terms are the reference's: the loss without the indexer's is
    its cross-entropy, and the rest its KL."""
    row = built("keye")
    loss, _ = row.program()
    _, _, stats = row.reference()
    with jax.default_matmul_precision("highest"):
        ce = tfm.loss_fn(row.params, *row.batch(), dataclasses.replace(
            row.cfg, indexer_loss_coef=1e-30))
    assert abs(ce - stats["ce"]) <= F32_REL * abs(ce)
    assert abs((loss - ce) - stats["index_kl"]) <= 1e-4 * stats["index_kl"]


def test_each_loss_reaches_its_own_leaves_alone():
    """The cross-entropy's gradient is zero on the indexer's three
    matrices, and the indexer's loss's on everything else."""
    cfg = KEYE_TINY
    params, (tokens, labels) = _params(cfg), _batch(cfg)

    def total(params, coef):
        return tfm.loss_fn(params, tokens, labels, dataclasses.replace(
            cfg, indexer_loss_coef=coef))

    both = jax.grad(total)(params, 1.0)
    doubled = jax.grad(total)(params, 2.0)
    flat, _ = jax.tree_util.tree_flatten_with_path(both)
    seen = set()
    for (path, g1), g2 in zip(flat, jax.tree_util.tree_leaves(doubled)):
        name = path[-1].key
        if name in INDEX_LEAVES:
            # All of it is the indexer's loss's: it doubles with it.
            assert float(jnp.linalg.norm(g1)) > 0, path
            assert _rel(g2, 2.0 * g1) <= 1e-6, path
            seen.add(name)
        else:
            # None of it: the coefficient changes nothing.
            np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    assert seen == set(INDEX_LEAVES)


def test_the_heads_are_normed_one_at_a_time_and_grouped():
    """``qk_norm_per_head`` scales a head's own 32 dims and no other
    head's; query heads 0, 1 read key-value head 0 and 2, 3 head 1."""
    cfg = dataclasses.replace(
        KEYE_TINY, index_heads=0, index_head_dim=0, index_topk=0,
        indexer_loss_coef=0.0)
    layer = tfm.init_params(jax.random.PRNGKey(0), cfg)["layers"][0]
    assert layer["wq"].shape == (64, 128) and layer["wo"].shape == (128, 64)
    assert layer["wk"].shape == (64, 64)
    assert layer["q_norm_scale"].shape == (32,)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 64))
    q, k, v, wide = attention.qkv_proj(x, layer, cfg, None, jnp.arange(16))
    assert q.shape == (1, 16, 4, 32) and k.shape == (1, 16, 2, 32)
    assert wide == 128
    # Rotation keeps a head's norm, and the norm made it sqrt(32).
    np.testing.assert_allclose(jnp.linalg.norm(q, axis=-1), 32 ** 0.5,
                               rtol=1e-4)
    np.testing.assert_allclose(jnp.linalg.norm(k, axis=-1), 32 ** 0.5,
                               rtol=1e-4)
    # Grouping: a change to key-value head 1 moves query heads 2 and 3.
    mask = jnp.tril(jnp.ones((1, 16, 16), jnp.int8))
    o, _, _ = sa.attention_jnp(q, k, v, mask, 32 ** -0.5)
    o2, _, _ = sa.attention_jnp(q, k, v.at[:, :, 1].add(1.0), mask,
                                32 ** -0.5)
    np.testing.assert_array_equal(o[:, :, :2], o2[:, :, :2])
    assert float(jnp.abs(o[:, :, 2:] - o2[:, :, 2:]).min()) > 0.5


# --- the selection -------------------------------------------------------------

def _scores(seed, b=2, t=128, ties=False):
    s = jax.random.normal(jax.random.PRNGKey(seed), (b, t, t))
    if ties:
        # A handful of values: every row's threshold is shared by many.
        s = jnp.round(s * 2.0) / 2.0
    return s


@pytest.mark.parametrize("ties", (False, True), ids=["distinct", "tied"])
@pytest.mark.parametrize("topk", (32, 48, 128, 200))
def test_the_selection_is_lax_top_ks(topk, ties):
    """The bisection kernel against ``lax.top_k`` on the same scores: rows
    with fewer keys than ``topk`` (all of them), exactly ``topk``, and
    more, with and without equal scores at the threshold (the lower index
    first); the count is the closed form."""
    scores = _scores(topk, ties=ties)
    want = sa.select_jnp(scores, topk)
    mask, lse = sa.select(scores, topk, True)
    got = mask != 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # The rows' log-sum-exp over their own selection, by the same kernel.
    np.testing.assert_allclose(
        lse, jax.nn.logsumexp(jnp.where(want, scores, -jnp.inf), axis=-1),
        rtol=1e-6)
    rows = np.asarray(want).sum(-1)
    np.testing.assert_array_equal(
        rows, np.broadcast_to(np.minimum(np.arange(128) + 1, topk),
                              rows.shape))
    assert int(rows.sum()) == 2 * sa.keys_selected(128, topk)
    if topk < 128:
        # Row topk - 1 selects all its topk keys, row topk its best topk
        # of topk + 1: by lax.top_k on that row alone.
        row = np.asarray(scores[0, topk, :topk + 1])
        _, best = jax.lax.top_k(jnp.asarray(row), topk)
        assert set(np.flatnonzero(np.asarray(got)[0, topk])) == set(
            np.asarray(best).tolist())


def test_the_selection_orders_negative_zero_and_signs():
    """Scores of both signs, zeros and repeated values: the bit-pattern
    order is the numbers' order."""
    base = jnp.asarray([0.0, -1.5, 2.0, 0.0, -0.25, 2.0, 1e-30, -1e-30])
    scores = jnp.tile(base, (1, 128, 16))
    want = sa.select_jnp(scores, 32)
    got = sa.select(scores, 32, True)[0] != 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- the kernels in the interpreter ----------------------------------------------

def _operands(seed=0, b=2, t=128, h=4, hkv=2, d=32, hi=4, di=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        q=jax.random.normal(ks[0], (b, t, h, d)),
        k=jax.random.normal(ks[1], (b, t, hkv, d)),
        v=jax.random.normal(ks[2], (b, t, hkv, d)),
        qi=jax.random.normal(ks[3], (b, t, hi, di)),
        ki=jax.random.normal(ks[4], (b, t, di)),
        w=jax.random.normal(ks[5], (b, t, hi)),
        ct=jax.random.normal(ks[6], (b, t, h, d)))


def _tied_selection(x, topk=32):
    """Scores with ties at every row's threshold, the kernel's selection
    by them and the rows' log-sum-exp over it."""
    scores = jnp.round(sa.index_scores_jnp(
        x["qi"], x["ki"], x["w"], 0.125) * 4.0) / 4.0
    mask, lse_i = sa.select(scores, topk, True)
    return scores, mask, lse_i


@pytest.mark.parametrize("block", (32, 64, 128))
def test_masked_attention_kernels_match_the_masked_softmax(block,
                                                           monkeypatch):
    """Forward, dQ, dK, dV, the rows' KL against the head-mean
    probabilities and the scores' cotangent of the Pallas kernels against
    a ``jax.numpy`` masked softmax and :func:`indexer_kl`, under a
    selection with ties; block 32 puts whole query blocks under ``topk``
    (interior and diagonal tiles) and others over it (masked tiles)."""
    x = _operands()
    scale = 32 ** -0.5
    # A weight a row: the loss's cotangent is not the same for every query.
    rows = jax.random.uniform(jax.random.PRNGKey(3), (2, 128)) + 0.5
    monkeypatch.setattr(sa, "attention_block", lambda t: block)
    with jax.default_matmul_precision("highest"):
        scores, mask, lse_i = _tied_selection(x)
        mask_t = jnp.swapaxes(mask, 1, 2)

        def plain(q, k, v, scores):
            o, _, p = sa.attention_jnp(q, k, v, mask, scale)
            kl = sa.indexer_kl(scores, mask, jax.lax.stop_gradient(p))
            return jnp.sum(o * x["ct"]) + jnp.sum(kl * rows), (p, kl)

        def kernels(q, k, v, scores):
            o, kl = sa.masked_attention(q, k, v, scores, lse_i, mask,
                                        mask_t, 32, scale, True)
            return jnp.sum(o * x["ct"]) + jnp.sum(kl * rows), kl

        args = (x["q"], x["k"], x["v"], scores)
        (want, (want_p, want_kl)), want_g = jax.value_and_grad(
            plain, argnums=(0, 1, 2, 3), has_aux=True)(*args)
        (got, got_kl), got_g = jax.value_and_grad(
            kernels, argnums=(0, 1, 2, 3), has_aux=True)(*args)
    assert abs(got - want) <= 1e-5 * abs(want)
    np.testing.assert_allclose(np.asarray(want_p).sum(-1), 1.0, rtol=1e-5)
    assert float(want_kl.max()) > 0.01
    np.testing.assert_allclose(got_kl, want_kl, atol=1e-5)
    for name, g, w in zip(("q", "k", "v", "scores"), got_g, want_g):
        assert _rel(g, w) <= 1e-5, name
    # The scores' cotangent: nothing off the selection, so nothing above
    # the diagonal, which index_scores demands of it.
    off = np.asarray(mask) == 0
    assert float(np.abs(np.asarray(got_g[3]))[off].max()) == 0.0
    assert off[:, np.triu_indices(128, 1)[0], np.triu_indices(128, 1)[1]].all()
    classes = sa.tile_classes(128, block, block, 32)
    assert sum(classes.values()) == (128 // block) ** 2
    assert classes["masked"] > 0
    assert (classes["interior"] + classes["diagonal"] > 0) == (block == 32)


@pytest.mark.parametrize("topk", (32, 48, 200))
@pytest.mark.parametrize("block", (32, 128))
def test_the_rows_kl_and_its_gradient_are_indexer_kls(block, topk,
                                                      monkeypatch):
    """The loss alone, query blocks under and over ``topk`` and a ``topk``
    no row reaches: the rows' KL of ``dsa_probs`` (with the select
    kernel's normaliser) and the cotangent ``dsa_bwd_dq`` writes, against
    :func:`indexer_kl` and its ``jax.grad`` on the oracle's
    probabilities."""
    x = _operands(3)
    scale = 32 ** -0.5
    rows = jax.random.uniform(jax.random.PRNGKey(4), (2, 128)) + 0.5
    monkeypatch.setattr(sa, "attention_block", lambda t: block)
    with jax.default_matmul_precision("highest"):
        scores, mask, lse_i = _tied_selection(x, topk)
        _, _, p = sa.attention_jnp(x["q"], x["k"], x["v"], mask, scale)
        want_kl, pull = jax.vjp(lambda s: sa.indexer_kl(s, mask, p), scores)
        got_kl, pull_k = jax.vjp(
            lambda s: sa.masked_attention(
                x["q"], x["k"], x["v"], s, lse_i, mask,
                jnp.swapaxes(mask, 1, 2), topk, scale, True)[1], scores)
        (want_g,), (got_g,) = pull(rows), pull_k(rows)
    np.testing.assert_allclose(got_kl, want_kl, atol=1e-5)
    assert float(jnp.linalg.norm(want_g)) > 0.1
    assert _rel(got_g, want_g) <= 1e-5
    assert float(jnp.abs(jnp.where(mask == 0, got_g, 0.0)).max()) == 0.0


def test_indexer_kernels_match_their_jnp_form():
    x = _operands(1)
    causal = jnp.tril(jnp.ones((128, 128)))
    g = jax.random.normal(jax.random.PRNGKey(9), (2, 128, 128)) * causal
    args = (x["qi"], x["ki"], x["w"])
    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(
            lambda *a: sa.index_scores_jnp(*a, 0.125) * causal, *args)
        got, pull_k = jax.vjp(lambda *a: sa.index_scores(*a, 0.125, True),
                              *args)
        # Above the diagonal the kernel's scores mean nothing.
        np.testing.assert_allclose(got * causal, want, atol=1e-5)
        for name, a, b in zip(INDEX_LEAVES, pull_k(g), pull(g)):
            assert _rel(a, b) <= 1e-5, name


def test_the_route_by_kernels_is_the_route_by_jnp(monkeypatch):
    """``dsa_attention`` whole, kernels in the interpreter against the
    ``jax.numpy`` forms: output, the rows' KL, and all six gradients."""
    monkeypatch.setattr(sa, "attention_block", lambda t: 64)
    x = _operands(2)
    args = tuple(x[n] for n in ("q", "k", "v", "qi", "ki", "w"))

    def total(kernels, *a):
        o, kl = sa.dsa_attention(*a, topk=32, index_scale=0.125,
                                 kernels=kernels, interpret=True)
        return jnp.sum(o * x["ct"]) + jnp.sum(kl), kl

    with jax.default_matmul_precision("highest"):
        (want, want_kl), want_g = jax.value_and_grad(
            lambda *a: total(False, *a), argnums=range(6),
            has_aux=True)(*args)
        (got, got_kl), got_g = jax.value_and_grad(
            lambda *a: total(True, *a), argnums=range(6),
            has_aux=True)(*args)
    assert abs(got - want) <= 1e-5 * abs(want)
    assert float(want_kl.min()) >= -1e-6 and float(want_kl.max()) > 0.01
    np.testing.assert_allclose(got_kl, want_kl, atol=1e-5)
    for name, g, w in zip(("q", "k", "v") + INDEX_LEAVES, got_g, want_g):
        assert _rel(g, w) <= 2e-5, name


def test_the_folded_entry_is_the_entry_by_heads_bit_for_bit(monkeypatch):
    """``masked_attention_folded`` on operands in the kernels' layout (as
    ``qk_assemble`` writes them) against ``masked_attention`` on [B, T, H,
    D]: the same kernels on the same bytes, so ``o``, the rows' KL and the
    four gradients are equal, not close."""
    monkeypatch.setattr(sa, "attention_block", lambda t: 64)
    x = _operands(4)
    b, hkv = x["q"].shape[0], x["k"].shape[2]
    scores, mask, lse_i = _tied_selection(x)
    rest = (lse_i, mask, jnp.swapaxes(mask, 1, 2), 32, 32 ** -0.5, True)
    rows = jax.random.uniform(jax.random.PRNGKey(5), (2, 128)) + 0.5

    (o, kl), pull = jax.vjp(
        lambda q, k, v, s: sa.masked_attention(q, k, v, s, *rest),
        x["q"], x["k"], x["v"], scores)
    (of, klf), pull_folded = jax.vjp(
        lambda q, k, v, s: sa.masked_attention_folded(q, k, v, s, *rest),
        sa._fold_q(x["q"], hkv), sa._fold_kv(x["k"]), sa._fold_kv(x["v"]),
        scores)
    assert of.shape == (b * hkv, 2, 128, 32)
    assert bool(jnp.all(sa._unfold_q(of, b) == o))
    assert bool(jnp.all(klf == kl))
    dq, dk, dv, ds = pull((x["ct"], rows))
    dqf, dkf, dvf, dsf = pull_folded((sa._fold_q(x["ct"], hkv), rows))
    assert bool(jnp.all(dqf == sa._fold_q(dq, hkv)))
    assert bool(jnp.all(dkf == sa._fold_kv(dk)))
    assert bool(jnp.all(dvf == sa._fold_kv(dv)))
    assert bool(jnp.all(dsf == ds))
    with pytest.raises(ValueError, match="folded operands"):
        sa.dsa_attention(x["q"], x["k"], x["v"], x["qi"], x["ki"], x["w"],
                         topk=32, index_scale=0.125, kernels=False,
                         folded=True)


def test_heads_born_in_the_kernels_layout_give_the_same_step(monkeypatch):
    """The tiny step at the least width ``qk_assemble`` takes (heads of a
    register, bfloat16) with the sparse route's kernels traced in the
    interpreter: with the heads normed, rotated and laid out by the
    assembly's kernels and handed to the folded entry (named in the
    lowered text beside the seven ``dsa_*`` kernels) the loss and every
    checked leaf are ``qkv_proj``'s lines' through the entry by heads,
    within this table's bfloat16 tolerances (3e-3 the loss; a leaf far
    inside its 0.3)."""
    from horovod_tpu.ops import qk_assemble
    from horovod_tpu.telemetry import scopes

    cfg = dataclasses.replace(KEYE_TINY, head_width=128, dtype=jnp.bfloat16)
    params, (tokens, labels) = _params(cfg), _batch(cfg)
    monkeypatch.setattr(sa, "path", lambda x: "kernel")
    monkeypatch.setattr(sa, "attention_block", lambda t: 64)

    def step():
        return jax.jit(jax.value_and_grad(
            lambda p: tfm.loss_fn(p, tokens, labels, cfg)))

    text = step().lower(params).as_text(debug_info=True)
    for name in (scopes.QK_ASSEMBLE_FWD, scopes.QK_ASSEMBLE_BWD,
                 scopes.DSA_FWD, scopes.DSA_BWD_DQ, scopes.DSA_BWD_DKV):
        assert name in text, name
    got, got_grads = step()(params)
    monkeypatch.setattr(qk_assemble, "takes", lambda *a: False)
    assert scopes.QK_ASSEMBLE_FWD not in step().lower(params).as_text(
        debug_info=True)
    want, want_grads = step()(params)
    assert np.isfinite(float(got)) and _rel(got, want) <= 3e-3
    for name, path in reference.leaf_paths(cfg.n_layers).items():
        assert _rel(reference.leaf(got_grads, path),
                    reference.leaf(want_grads, path)) <= 2e-2, name


def _kernel_calls(jaxpr, found):
    """Every ``pallas_call`` of ``jaxpr`` and of the jaxprs inside it by
    name, and under ``"float32 [B, T, T]"`` the primitives of the
    equations that produce such an array."""
    from jax._src import core

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            found[name] = found.get(name, 0) + 1
        else:
            for sub in core.jaxprs_in_params(eqn.params):
                _kernel_calls(sub, found)
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            if (len(shape) == 3 and shape[1] == shape[2] == 256
                    and var.aval.dtype == jnp.float32):
                found.setdefault("float32 [B, T, T]", set()).add(
                    eqn.params.get("name", eqn.primitive.name))
    return found


def test_the_recomputed_layer_reads_the_probabilities_once_a_direction(
        monkeypatch):
    """The gradient of the model under ``remat`` ``full``, the kernels
    traced in place of their ``jax.numpy`` forms: a layer's recomputation
    runs the scores, the selection and the masked forward again and **not**
    the indexer's loss (nothing the backward pass reads comes out of
    ``dsa_probs``; the gradient's probabilities are the dQ kernel's), and
    the only float32 [B, T, T] arrays of the program are the scores and
    their gradient: no probabilities, and no pass of XLA's over either."""
    # 256 tokens: no other array of the model is [B, 256, 256].
    cfg = dataclasses.replace(KEYE_TINY, max_seq=256)
    monkeypatch.setattr(sa, "path", lambda x: "kernel")
    tokens = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda p, t: tfm.loss_fn(p, t, t, cfg, remat="full")))(
            tfm.init_abstract(cfg), tokens)
    found = _kernel_calls(jaxpr.jaxpr, {})
    layers = cfg.n_layers
    assert found.pop("float32 [B, T, T]") == {
        "dsa_index_fwd", "dsa_bwd_dq", "stop_gradient"}
    found = {k: n for k, n in found.items() if k.startswith("dsa_")}
    assert found == {"dsa_index_fwd": 2 * layers,
                     "dsa_select_rows": 2 * layers, "dsa_fwd": 2 * layers,
                     "dsa_probs": layers, "dsa_bwd_dq": layers,
                     "dsa_bwd_dkv": layers, "dsa_index_bwd": layers}


# --- the expert share -----------------------------------------------------------

def test_the_shares_add_up():
    """What the eight chips of a deployment compute, 16 of 128 experts
    each (here: the four shares of 2 of 8), adds up to the uncut layer:
    the reference's with every expert in its tree."""
    cfg = KEYE_TINY
    k = jax.random.split(jax.random.PRNGKey(7), 5)
    u = jax.random.normal(k[0], (2, 64, 64))
    layer = {"router": jax.random.normal(k[1], (64, 8)) * 0.3}
    experts = {"w_gate": jax.random.normal(k[2], (8, 64, 48)) * 0.1,
               "w_up": jax.random.normal(k[3], (8, 64, 48)) * 0.1,
               "w_down": jax.random.normal(k[4], (8, 48, 64)) * 0.1}
    whole = dataclasses.replace(cfg, experts_held=0, experts_held_from=0)
    with jax.default_matmul_precision("highest"):
        want, rows = reference._moe_part(
            u.reshape(-1, 64), dict(layer, **experts),
            dict(_dims(cfg), held_from=0), None)
        total = jnp.zeros_like(u)
        for first in range(0, 8, 2):
            share = dataclasses.replace(cfg, experts_held=2,
                                        experts_held_from=first)
            held = {n: w[first:first + 2] for n, w in experts.items()}
            total = total + moe.moe_ffn(u, dict(layer, **held), share)[0]
        uncut = moe.moe_ffn(u, dict(layer, **experts), whole)[0]
    assert _rel(total.reshape(-1, 64), want) <= F32_REL
    assert _rel(uncut.reshape(-1, 64), want) <= F32_REL
    assert int(rows.sum()) == 128 * cfg.experts_per_token


# --- the step ---------------------------------------------------------------------


def test_trace_time_series_count_path_tiles_and_share(hvd, monkeypatch):
    from horovod_tpu import telemetry

    cfg = KEYE_TINY
    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
        jax.eval_shape(lambda p, t: tfm.loss_fn(p, t, t, cfg),
                       tfm.init_abstract(cfg), tokens)
        text = telemetry.render_prometheus()
        assert sa.keys_selected(128, 32) == 32 * 33 // 2 + 96 * 32
        for layer in ("0", "1"):
            assert f'hvd_moe_experts_held{{layer="{layer}"}} 4' in text, text
        assert 'hvd_dsa_layers_total{path="jnp"} 2' in text, text
        # Data, not static, on a share: not counted.
        assert "hvd_moe_assignments_total" not in text
        # The kernels' tiles, counted where a kernel is traced.
        x = _operands()
        mask = jnp.tril(jnp.ones((2, 128, 128), jnp.int8))
        monkeypatch.setattr(sa, "attention_block", lambda t: 32)
        scores = jnp.zeros((2, 128, 128))
        jax.eval_shape(lambda q, k, v: jax.vjp(
            lambda *a: sa.masked_attention(
                *a, scores, scores[:, 0], mask, mask, 32, 1.0, True),
            q, k, v)[1]((q, scores[:, 0])), x["q"], x["k"], x["v"])
        text = telemetry.render_prometheus()
        classes = sa.tile_classes(128, 32, 32, 32)
        assert classes == {"skipped": 6, "interior": 0, "diagonal": 1,
                           "masked": 9}
        for name, n in classes.items():
            assert (f'hvd_dsa_tiles_total{{class="{name}",kernel="dsa_fwd"}}'
                    f' {4 * n}') in text, text
        # One pass over the head-mean probabilities a direction.
        for kernel in ("dsa_probs", "dsa_bwd_dq"):
            assert (f'hvd_dsa_probability_passes_total{{kernel="{kernel}"}}'
                    f' 1') in text, text
    finally:
        telemetry.reset_for_tests()


# --- refusals: never a silent fall back ---------------------------------------------

PLAIN = dict(n_experts=0, experts_per_token=0, d_expert=0,
             norm_topk_prob=False, experts_held=0, experts_held_from=0,
             n_kv_heads=0, head_width=0, n_heads=2, d_ff=128)


def test_a_head_width_alone_is_plain_attention_with_wide_heads():
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=1, d_ff=64, max_seq=16,
                                dtype=jnp.float32, head_width=24)
    assert cfg.head_dim == 24 and cfg.attn_width == 48
    assert not cfg.latent_attention and not cfg.sparse_attention
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert params["layers"][0]["wq"].shape == (32, 48)
    assert params["layers"][0]["wo"].shape == (48, 32)
    tokens = jnp.arange(16)[None] % 64
    logits = tfm.forward(params, tokens, cfg, attention="local")
    assert logits.shape == (1, 16, 64) and bool(jnp.isfinite(logits).all())
