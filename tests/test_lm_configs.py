"""What every LM configuration must hold, written once and run over a table
of them: the GPT-2 block and the nine tiny configurations that keep the
shape of OLMoE, Olmo-Hybrid, Nemotron-3, GLM-4.7-Flash, Keye-VL-2.0,
Jamba2, SDAR (whose batch is clean tokens, which are noised and each
block's rate, and whose loss is ``diffusion_loss_fn``'s), Ouro (a
stack run four times on the same weights) and ZAYA1 (compressed
convolutional attention, an MLP router whose state goes from layer to
layer, scaled merges),
each against its plain reference under ``perfbench/reference/``, which
shares no code with the program.

The families: the loss and every checked leaf against the reference; the
reference sees the cell's controls; ``remat`` leaves loss and gradients
alone; the train step takes the gradient of the global batch on 1 and 4
devices; specs and abstract parameters cover every leaf; the trace-time
series; the scopes the per-layer metrics read; the model and sequence
axes, ``packed`` / ``segment_ids``, ``decode_step`` and the pipelined
builder run, or refuse by the argument's name and a field of every part
that does not implement it (and each such part alone still does); and the
config says what its fields cannot mean.  What is particular to a configuration
(a kernel, a router, a recurrence) stays in its own file.

What is costly (parameters, the reference's loss and gradients, the
program's) is built once a configuration (:func:`built`).  The families
that compile a row's program run **in the row's own file**, which imports
them from here (``from test_lm_configs import *``) and names its row in
``COSTLY_ROWS``: under ``--dist loadfile`` a file is one worker's serial
chain, and seven rows in one chain would be the whole suite's wall clock.
The families that only trace run here, on all seven rows, and so do the
costly ones of the rows ``COSTLY_ROWS`` names below.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Callable, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import linear_attention as la
from horovod_tpu.models import transformer as tfm
from perfbench.reference import (bd_moe_lm, cca_moe_lm, dsa_moe_lm,
                                 hybrid_lm, lm, looped_lm, mamba1_lm,
                                 mla_moe_lm, moe_lm, ssm_moe_lm)

__all__ = ["COSTLY", "ROWS", "built", "lm_row", "pytest_generate_tests",
           "rel"]

F32, BF16 = jnp.float32, jnp.bfloat16

GPT2_TINY = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                  n_layers=2, d_ff=64, max_seq=128,
                                  dtype=F32)
OLMOE_TINY = tfm.TransformerConfig(
    vocab_size=128, d_model=64, n_heads=2, n_layers=2, d_ff=0, max_seq=64,
    dtype=F32, positions="rope", qk_norm=True, norm_eps=1e-5,
    tie_embeddings=False, mlp="swiglu", n_experts=8, experts_per_token=2,
    d_expert=32, router_aux_coef=0.01, router_z_coef=0.001)
HYBRID_PATTERN = ("linear_attention",) * 3 + ("full_attention",)
HYBRID_TINY = tfm.TransformerConfig(
    vocab_size=128, d_model=64, n_heads=2, n_layers=4, d_ff=96, max_seq=256,
    dtype=F32, positions="none", qk_norm=True, norm_eps=1e-6,
    tie_embeddings=False, mlp="swiglu", layer_types=HYBRID_PATTERN,
    linear_key_heads=2, linear_value_heads=2, linear_key_head_dim=24,
    linear_value_head_dim=48, linear_conv_kernel=4,
    linear_allow_neg_eigval=True)
KINDS = {"M": "mamba2", "*": "attention", "E": "mlp"}
NEMOTRON_PATTERN = tuple(KINDS[c] for c in "MEMEMEM*EME")
NEMOTRON_TINY = tfm.TransformerConfig(
    vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, n_layers=11,
    d_ff=0, max_seq=128, dtype=F32, positions="none", norm_eps=1e-5,
    tie_embeddings=False, mlp="relu2", n_experts=16, experts_per_token=6,
    d_expert=64, d_latent=32, d_shared=96, routed_scale=5.0, experts_held=4,
    experts_held_from=4, layer_types=NEMOTRON_PATTERN, ssm_heads=4,
    ssm_head_dim=16, ssm_state=32, ssm_groups=2, ssm_conv_kernel=4,
    ssm_chunk=32, mtp_layer_types=(KINDS["*"], KINDS["E"]),
    mtp_loss_coef=0.1)
# 3 heads of 32 on a hidden size of 64 (3 x 32 != 64), 8 of them rotary;
# 1 dense + 2 expert layers + the module; 8 experts top-2, 4 held from 2.
GLM_TINY = tfm.TransformerConfig(
    vocab_size=128, d_model=64, n_heads=3, n_layers=3, d_ff=160, max_seq=128,
    dtype=F32, positions="rope", rope_theta=1e6, norm_eps=1e-5,
    tie_embeddings=False, head_width=32, q_latent_rank=24, kv_latent_rank=16,
    rope_dim=8, mlp="swiglu", n_experts=8, experts_per_token=2, d_expert=48,
    d_shared=48, routed_scale=1.8, experts_held=4, experts_held_from=2,
    dense_layers=1, mtp_layer_types=("full_attention",), mtp_loss_coef=0.1)
# 4 query heads over 2 key-value heads of 32 on a hidden size of 64 (4 x
# 32 = 128, twice the hidden size, as 32 x 128 is of 2048); 4 indexer
# heads of 16, 32 keys a query of 128; 8 experts top-2, 4 held from 2.
KEYE_TINY = tfm.TransformerConfig(
    vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, head_width=32,
    n_layers=2, d_ff=0, max_seq=128, dtype=F32, positions="rope",
    rope_theta=1e7, norm_eps=1e-6, tie_embeddings=False,
    qk_norm_per_head=True, index_heads=4, index_head_dim=16, index_topk=32,
    indexer_loss_coef=1.0, mlp="swiglu", n_experts=8, experts_per_token=2,
    d_expert=48, norm_topk_prob=True, experts_held=4, experts_held_from=2)

# Three Mamba-1 layers around one attention layer of 4 query heads over
# ONE key-value head of 16, every layer with the dense SwiGLU MLP, the
# head tied; 128 inner channels with a state of 4 each, the step from a
# rank of 8.
JAMBA_PATTERN = ("mamba", "mamba", "full_attention", "mamba")
JAMBA_TINY = tfm.TransformerConfig(
    vocab_size=128, d_model=64, n_heads=4, n_kv_heads=1, n_layers=4,
    d_ff=96, max_seq=128, dtype=F32, positions="none", norm_eps=1e-6,
    tie_embeddings=True, mlp="swiglu", layer_types=JAMBA_PATTERN,
    mamba_inner=128, mamba_state=4, mamba_dt_rank=8, mamba_conv_kernel=4)

# Keye's layer without the indexer, under the block-diffusion objective:
# blocks of 4, the mask id the last row of the vocabulary.
SDAR_TINY = tfm.TransformerConfig(
    vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, head_width=32,
    n_layers=2, d_ff=0, max_seq=128, dtype=F32, positions="rope",
    rope_theta=1e6, norm_eps=1e-6, tie_embeddings=False,
    qk_norm_per_head=True, mlp="swiglu", n_experts=8, experts_per_token=2,
    d_expert=48, norm_topk_prob=True, experts_held=4, experts_held_from=2,
    diffusion_block=4, mask_token_id=127)

# One sandwich-normed rotary SwiGLU layer of 2 heads of 32, run four times
# on the same weights (four layer bodies to compile: a second layer would
# double every test's time and show nothing more), the head untied, an
# exit gate after every pass.
OURO_TINY = tfm.TransformerConfig(
    vocab_size=128, d_model=64, n_heads=2, n_layers=1, d_ff=96, max_seq=64,
    dtype=F32, positions="rope", rope_theta=1e6, norm_eps=1e-6,
    tie_embeddings=False, mlp="swiglu", post_norm=True, loops=4,
    exit_entropy_coef=0.05)


# Three layers of compressed convolutional attention (4 query heads on 2
# key-value heads of 8: a latent of 32 + 16 on a hidden size of 64, the
# first 4 dims of a head rotary) and one expert a token of 8 (4 held from
# 2) or the skip under the MLP router of width 16, whose state goes through
# all three; scaled merges; the head tied.
ZAYA_TINY = tfm.TransformerConfig(
    vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, head_width=8,
    n_layers=3, d_ff=0, max_seq=64, dtype=F32, positions="rope",
    rope_theta=5e6, norm_eps=1e-5, tie_embeddings=True, mlp="swiglu",
    n_experts=8, experts_per_token=1, d_expert=32, experts_held=4,
    experts_held_from=2, cca_taps=(2, 2), rotary_dims=4, router_width=16,
    residual_scaling=True)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# --- each row's reference, under one signature ------------------------------
# ``(cfg, params, *batch, **controls) -> (loss, {name: gradient}, what
# else it returns)`` (``batch``: tokens and labels; under block diffusion
# clean tokens, which are noised and the blocks' rates) and ``(tree, cfg)
# -> {name: leaf}``.

def _gpt2_ref(cfg, params, tokens, labels):
    return lm.loss_and_tail_grads(params, tokens, labels, cfg.n_heads) + (
        None,)


def _olmoe_ref(cfg, params, tokens, labels, **kw):
    return moe_lm.loss_and_tail_grads(
        params, tokens, labels, n_heads=cfg.n_heads,
        top_k=cfg.experts_per_token, eps=cfg.norm_eps, theta=cfg.rope_theta,
        aux_coef=cfg.router_aux_coef, z_coef=cfg.router_z_coef, **kw)


def _hybrid_ref(cfg, params, tokens, labels, **kw):
    return hybrid_lm.loss_and_tail_grads(
        params, tokens, labels, n_heads=cfg.n_heads,
        layer_types=cfg.layer_types, linear_heads=cfg.linear_value_heads,
        key_dim=cfg.linear_key_head_dim, eps=cfg.norm_eps,
        neg_eigval=cfg.linear_allow_neg_eigval, **kw)


def nemotron_dims(cfg):
    return {"n_heads": cfg.n_heads, "kv_heads": cfg.kv_heads,
            "ssm_heads": cfg.ssm_heads, "ssm_head_dim": cfg.ssm_head_dim,
            "ssm_state": cfg.ssm_state, "ssm_groups": cfg.ssm_groups,
            "eps": cfg.norm_eps, "top_k": cfg.experts_per_token,
            "routed_scale": cfg.routed_scale,
            "held_from": cfg.experts_held_from}


def _nemotron_ref(cfg, params, tokens, labels, **kw):
    """Every leaf the reference can differentiate, not only the cell's."""
    return ssm_moe_lm.loss_and_tail_grads(
        params, tokens, labels, dims=nemotron_dims(cfg),
        layer_types=cfg.layer_types, mtp_layer_types=cfg.mtp_layer_types,
        mtp_coef=cfg.mtp_loss_coef, names=tuple(ssm_moe_lm.LEAVES), **kw)


def glm_dims(cfg):
    return {"n_heads": cfg.n_heads, "head_dim": cfg.head_dim,
            "rope_dim": cfg.rope_dim, "kv_rank": cfg.kv_latent_rank,
            "eps": cfg.norm_eps, "theta": cfg.rope_theta,
            "top_k": cfg.experts_per_token,
            "routed_scale": cfg.routed_scale,
            "held_from": cfg.experts_held_from}


def _glm_ref(cfg, params, tokens, labels, **kw):
    return mla_moe_lm.loss_and_tail_grads(
        params, tokens, labels, dims=glm_dims(cfg),
        dense_layers=cfg.dense_layers, mtp_coef=cfg.mtp_loss_coef,
        names=tuple(mla_moe_lm.LEAVES), **kw)


def keye_dims(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "index_heads": cfg.index_heads,
            "index_head_dim": cfg.index_head_dim, "topk": cfg.index_topk,
            "eps": cfg.norm_eps, "theta": cfg.rope_theta,
            "top_k": cfg.experts_per_token,
            "held_from": cfg.experts_held_from}


def _keye_ref(cfg, params, tokens, labels, **kw):
    kw.setdefault("index_coef", cfg.indexer_loss_coef)
    return dsa_moe_lm.loss_and_tail_grads(
        params, tokens, labels, dims=keye_dims(cfg),
        names=tuple(dsa_moe_lm.LEAVES), **kw)


def jamba_dims(cfg):
    return {"n_heads": cfg.n_heads, "kv_heads": cfg.kv_heads,
            "state": cfg.mamba_state, "dt_rank": cfg.mamba_dt_rank,
            "eps": cfg.norm_eps}


def _jamba_ref(cfg, params, tokens, labels, **kw):
    """Every leaf of the last Mamba layer, of the attention layer and of
    the last MLP, not only the cell's."""
    return mamba1_lm.loss_and_tail_grads(
        params, tokens, labels, dims=jamba_dims(cfg),
        layer_types=cfg.layer_types, names=tuple(mamba1_lm.LEAVES),
        stats=True, **kw)


def sdar_dims(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "eps": cfg.norm_eps,
            "theta": cfg.rope_theta, "top_k": cfg.experts_per_token,
            "held_from": cfg.experts_held_from,
            "block": cfg.diffusion_block, "mask_id": cfg.mask_token_id}


def _sdar_ref(cfg, params, tokens, masked, rates, **kw):
    """Every leaf, the embedding's among them."""
    return bd_moe_lm.loss_and_tail_grads(
        params, tokens, masked, rates, dims=sdar_dims(cfg),
        names=tuple(bd_moe_lm.LEAVES), **kw)


def ouro_dims(cfg):
    return {"n_heads": cfg.n_heads, "eps": cfg.norm_eps,
            "theta": cfg.rope_theta, "loops": cfg.loops,
            "beta": cfg.exit_entropy_coef}


def _ouro_ref(cfg, params, tokens, labels, **kw):
    """Every leaf, the embedding's and the gate's among them."""
    paths = looped_lm.every_leaf(params)
    return looped_lm.loss_and_grads(
        params, tokens, labels, dims=ouro_dims(cfg), names=tuple(paths),
        paths=paths, **kw)


def zaya_dims(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "rotary_dims": cfg.rotary_dims,
            "eps": cfg.norm_eps, "theta": cfg.rope_theta,
            "n_experts": cfg.n_experts, "held_from": cfg.experts_held_from}


def _zaya_ref(cfg, params, tokens, labels, **kw):
    """Every leaf the loss moves, the embedding's among them."""
    paths = cca_moe_lm.trained_leaves(params)
    return cca_moe_lm.loss_and_grads(
        params, tokens, labels, dims=zaya_dims(cfg), names=tuple(paths),
        paths=paths, **kw)


def _zaya_leaves(tree, cfg):
    return {name: cca_moe_lm.leaf(tree, path)
            for name, path in cca_moe_lm.trained_leaves(tree).items()}


def _zaya_placed(params, cfg):
    """The held experts placed as the benchmark's adapter places them:
    those whose loads add up to a uniform router's rows."""
    tokens = jax.random.randint(jax.random.PRNGKey(3), (256,), 0,
                                cfg.vocab_size)
    placed = cca_moe_lm.level_placement(params, tokens, dims=zaya_dims(cfg))
    return dict(params, layers=[cca_moe_lm.place(layer, found) for
                                layer, found in zip(params["layers"],
                                                    placed)])


def _zaya_rows_and_skips(stats, grads):
    """The held experts of every layer and the skip receive rows; the
    selection biases and the first layer's gamma are not trained."""
    assert stats["rows"].shape == (3, 4) and stats["skips"].shape == (3,)
    assert int(stats["rows"].sum(1).min()) > 0
    assert int(stats["skips"].sum()) > 0
    for layer in grads["layers"]:
        assert float(jnp.abs(layer["router_bias"]).max()) == 0.0
    assert float(jnp.abs(
        grads["layers"][0]["router_state_scale"]).max()) == 0.0
    assert float(jnp.abs(
        grads["layers"][1]["router_state_scale"]).max()) > 0.0


def _every_leaf(tree, cfg):
    return {name: looped_lm.leaf(tree, path)
            for name, path in looped_lm.every_leaf(tree).items()}


def _exit_shares(stats):
    """The four passes' mean exit probabilities sum to one, and every
    pass carries weight."""
    assert stats["p_mean"].shape == stats["l_mean"].shape == (4,)
    assert abs(float(stats["p_mean"].sum()) - 1.0) < 1e-6
    assert float(stats["p_mean"].min()) > 0.05


def _by_paths(reference, paths):
    return lambda tree, cfg: {name: reference.leaf(tree, path)
                              for name, path in paths(cfg).items()}


def _hybrid_gates(gates):
    """The three linear layers' gates, as the reference saw them."""
    assert gates.shape == (3, 6)
    assert (np.asarray(gates[:, 0]) > 0).all()
    assert (np.asarray(gates[:, 4]) <= 1).all()
    assert 1.0 < float(gates[:, 5].max()) <= 2.0


def _rows_and_bias(stats, grads, layers_by_held):
    """Rows per held expert of every expert layer run, and the selection
    bias, which chooses and is not trained."""
    assert stats["rows"].shape == layers_by_held
    assert float(jnp.abs(grads["layers"][1]["router_bias"]).max()) == 0.0


def _jamba_decays(stats):
    """The three Mamba layers' decays and steps, as the reference saw
    them."""
    assert stats["decay"].shape == stats["delta"].shape == (3, 3)
    assert 0.0 < float(stats["decay"].min()) <= float(
        stats["decay"].max()) < 1.0
    assert float(stats["delta"].min()) > 0.0


def _rows_and_masked_share(stats):
    assert stats["rows"].shape == (2, 4)
    assert 0.2 < float(stats["masked_share"]) < 0.8


def _rows_and_kl(stats):
    assert stats["rows"].shape == (2, 4)
    assert float(stats["index_kl"]) > 1e-3


@dataclasses.dataclass(frozen=True)
class Row:
    """One configuration, its reference and what each family expects."""

    cfg: tfm.TransformerConfig
    ref: Callable
    checked: Callable
    seq: int
    # The program's embedding times this, as the benchmark's adapters
    # have it: at 0.02 every token is the same token to a router.
    embed_scale: float = 1.0
    # (id, dtype, attention, the loss's and the gradients' tolerance).
    parity: Tuple = (("float32", F32, "local", 5e-5, 5e-5),)
    # What the reference's third value must hold, after the f32 parity.
    also: Callable = lambda stats, grads: None
    # (id, the reference's keyword arguments, the leaf that must move,
    # how far the loss and that leaf's gradient must).
    controls: Tuple = ()
    remat_rel: float = 1e-5
    # The step's remat and route, and whether its ZeRO form is run too;
    # the sequences of its batch, and how far update / -lr may lie from
    # the reference's gradient.
    step: Tuple = ("full", "local")
    zero: bool = False
    step_batch: int = 4
    step_rel: float = 3e-3
    # Leaves the program's own update cannot be read from (see the step).
    unread: Tuple = ()
    # ``(params, cfg) -> params``, after the embedding's scale.
    prepare: Callable = lambda params, cfg: params
    shapes: Mapping = dataclasses.field(default_factory=dict)
    series: Tuple = ()
    no_series: Tuple = ()
    scopes: Tuple = ()
    no_scopes: Tuple = ()
    # For each argument beyond the data axis: the fields the refusal must
    # name, every one (each part that does not implement it says so, and
    # the prediction module), or nothing where the configuration runs
    # under it.
    refused: Mapping = dataclasses.field(default_factory=dict)
    # (id, fields replaced, argument, field): the configuration cut down
    # to one part that does not implement the argument, which alone must
    # refuse it by its own field.
    alone: Tuple = ()
    rules: Tuple = ()


_NO_EXPERTS = dict(n_experts=0, experts_per_token=0, d_expert=0,
                   experts_held=0, experts_held_from=0)
# What cuts a row down to the one part an ``alone`` case is about.
_GPT2_BUT_THE_LAYERS = dict(positions="learned", qk_norm=False, mlp="gelu",
                            tie_embeddings=True)
_NO_SSM = dict(n_layers=2, layer_types=("attention", "mlp"), ssm_heads=0,
               ssm_head_dim=0, ssm_state=0, ssm_groups=0, ssm_conv_kernel=0,
               ssm_chunk=0)
_LATENT_ALONE = dict(_NO_EXPERTS, d_shared=0, routed_scale=1.0,
                     dense_layers=0, mtp_layer_types=(), mtp_loss_coef=0.0)
_INDEXER_ALONE = dict(_NO_EXPERTS, norm_topk_prob=False, n_kv_heads=0,
                      head_width=0, n_heads=2, d_ff=128)
_INDEXER = ("index_heads", "index_head_dim", "index_topk",
            "indexer_loss_coef")
_GLM_NOT_THE_BLOCKS = ("positions", "head_width", "kv_latent_rank",
                       "n_experts", "dense_layers", "d_shared",
                       "mtp_layer_types")
_KEYE_NOT_THE_BLOCKS = _INDEXER + ("positions", "head_width", "n_kv_heads",
                                   "qk_norm_per_head", "n_experts")
_SDAR_NOT_THE_BLOCKS = ("positions", "head_width", "n_kv_heads",
                        "qk_norm_per_head", "n_experts", "diffusion_block",
                        "mask_token_id")
_OBJECTIVE_ALONE = dict(_NO_EXPERTS, d_ff=96, norm_topk_prob=False,
                        n_kv_heads=0, head_width=0, qk_norm_per_head=False)
BEYOND_NAMES = ("model_axis", "seq_axis", "packed", "segment_ids",
                "decode_step", "pipelined")
_GDN_BLOCKS = 2 * 2 * 256 // la.BLOCK
_NO_MAMBA = dict(layer_types=(), mamba_inner=0, mamba_state=0,
                 mamba_dt_rank=0, mamba_conv_kernel=0)
_LOOP_ALONE = dict(_GPT2_BUT_THE_LAYERS, post_norm=False)
_ZAYA_NOT_THE_BLOCKS = ("positions", "n_kv_heads", "head_width",
                        "n_experts", "cca_taps", "rotary_dims",
                        "router_width", "residual_scaling")
_CCA_ALONE = dict(_NO_EXPERTS, router_width=0, d_ff=96,
                  residual_scaling=False)
_ROUTER_ALONE = dict(cca_taps=(), rotary_dims=0, residual_scaling=False)

ROWS = {
    "gpt2": Row(
        cfg=GPT2_TINY, ref=_gpt2_ref, seq=128,
        checked=lambda tree, cfg: {"ln_f_scale": tree["ln_f_scale"],
                                   "w2_last": tree["layers"][-1]["w2"]},
        step=("none", "local"), zero=True, step_batch=8, step_rel=2e-3,
        shapes={("layers", 0, "w1"): (32, 64), ("pos",): (128, 32)},
        no_series=("hvd_moe_", "hvd_gdn_", "hvd_ssm_", "hvd_dsa_"),
        scopes=("layer_1/attn/qkv", "layer_1/attn/local_attention",
                "layer_1/attn/out", "layer_1/mlp"),
        no_scopes=("/moe_", "/gdn_", "/ssm_", "/mla_", "/dsa_", "/mlp_dense",
                   "/mtp"),
        rules=((dict(positions="alibi"), ValueError, "positions"),
               (dict(mlp="relu"), ValueError, "mlp"),
               (dict(n_experts=8, experts_per_token=2, d_expert=16),
                ValueError, "SwiGLU"),
               (dict(mlp="swiglu", n_experts=4, experts_per_token=5,
                     d_expert=16), ValueError, "experts_per_token"),
               (dict(mlp="swiglu", n_experts=4, experts_per_token=2),
                ValueError, "d_expert"),
               (dict(router_aux_coef=0.01), ValueError, "n_experts"),
               (dict(dense_layers=1), ValueError, "dense_layers"),
               (dict(n_kv_heads=3), ValueError, "n_kv_heads"))),
    "olmoe": Row(
        cfg=OLMOE_TINY, ref=_olmoe_ref, seq=32,
        checked=lambda tree, cfg: {
            "ln_f_scale": tree["ln_f_scale"],
            "w_down_last": tree["layers"][-1]["w_down"],
            "router_last": tree["layers"][-1]["router"]},
        # bfloat16 compute: three digits, and top-2-of-8 choices flip on a
        # few of 128 tokens, which the router's gradient feels most.
        parity=(("float32", F32, "local", 2e-5, 5e-5),
                ("bfloat16", BF16, "local", 3e-3, 0.5)),
        also=lambda counts, grads: np.testing.assert_array_equal(
            counts.sum(1), 4 * 32 * 2),     # every assignment, both layers
        # Tight enough for the precision the block states: the reference
        # with bfloat16 operands and a bfloat16 router softmax misses it.
        controls=(("bfloat16", dict(low_precision=BF16), "w_down_last",
                   2e-5, 5e-5),),
        remat_rel=1e-4, step=("none", "local"), zero=True, step_batch=8,
        step_rel=2e-3,
        shapes={("layers", 1, "w_down"): (8, 32, 64),
                ("layers", 1, "router"): (64, 8), ("head",): (64, 128)},
        series=('hvd_moe_assignments_total{layer="0"} 128',
                'hvd_moe_experts_held{layer="1"} 8',
                'hvd_moe_expert_weight_copy_bytes{layer="1"} 0'),
        no_series=("hvd_gdn_", "hvd_ssm_", "hvd_dsa_"),
        scopes=("layer_1/mlp/moe_router", "layer_1/mlp/moe_dispatch",
                "layer_1/mlp/moe_experts", "layer_1/mlp/moe_combine"),
        no_scopes=("/moe_shared", "/moe_latent", "/mlp_dense", "/gdn_"),
        refused={"model_axis": ("qk_norm", "n_experts"),
                 "decode_step": ("positions", "n_experts"),
                 "pipelined": ("positions", "n_experts", "qk_norm", "mlp",
                               "tie_embeddings")},
        alone=(("experts", dict(qk_norm=False), "model_axis", "n_experts"),
               ("experts", dict(positions="learned"), "decode_step",
                "n_experts")),
        rules=((dict(experts_per_token=9), ValueError, "experts_per_token"),
               (dict(d_expert=0), ValueError, "d_expert"),
               (dict(mlp="gelu"), ValueError, "SwiGLU"),
               (dict(experts_held=4, experts_held_from=6), ValueError,
                "experts_held"),
               (dict(routed_scale=2.0), ValueError, "routed_scale"),
               (dict(d_shared=32), NotImplementedError,
                "no auxiliary loss"))),
    "hybrid": Row(
        cfg=HYBRID_TINY, ref=_hybrid_ref, seq=256,
        checked=lambda tree, cfg: {
            "ln_f_scale": tree["ln_f_scale"],
            "w_down_last": tree["layers"][3]["w_down"],
            "lin_wo_last": tree["layers"][2]["lin_wo"],
            "lin_wa_last": tree["layers"][2]["lin_wa"]},
        # bfloat16 operands: three digits in the loss; over 256 tokens the
        # gradient through the decay (lin_wa) is a small difference of
        # large terms and reads 0.19 where the others read 0.01-0.05.
        parity=(("float32", F32, "local", 5e-5, 2e-4),
                ("bfloat16", BF16, "local", 3e-3, 0.3)),
        also=lambda gates, grads: _hybrid_gates(gates),
        controls=(("bfloat16", dict(low_precision=BF16), "lin_wa_last",
                   5e-5, 2e-4),),
        # The same arithmetic fused otherwise: 7e-5 on the gates' leaves,
        # whose gradient is a small difference of large terms.
        remat_rel=2e-4, step_rel=2e-3,
        shapes={("layers", 0, "lin_wq"): (64, 48),
                ("layers", 0, "lin_conv"): (4, 48 + 48 + 96),
                ("layers", 3, "wq"): (64, 64)},
        # Traced outside shard_map, the kernels run the recurrence (here
        # in the interpreter): batch 2 x 2 heads x 256 / BLOCK blocks.
        series=tuple(f'hvd_gdn_blocks_total{{layer="{i}",path="kernel"}} '
                     f'{_GDN_BLOCKS}' for i in range(3)),
        no_series=('hvd_gdn_blocks_total{layer="3"', "hvd_moe_", "hvd_ssm_"),
        scopes=("layer_0/attn/qkv/gdn_proj", "layer_0/attn/qkv/gdn_conv",
                "layer_2/attn/gdn_scan", "layer_2/attn/out/gdn_gate_norm",
                "layer_2/attn/out/gdn_out", "layer_3/attn/local_attention"),
        no_scopes=("layer_3/attn/gdn_scan", "layer_0/attn/local_attention",
                   "/moe_", "/ssm_"),
        refused={"model_axis": ("qk_norm", "layer_types"),
                 "seq_axis": ("layer_types",), "packed": ("layer_types",),
                 "segment_ids": ("layer_types",),
                 "decode_step": ("positions", "layer_types"),
                 "pipelined": ("positions", "layer_types", "qk_norm",
                               "mlp")},
        alone=(("linear_attention", dict(qk_norm=False), "model_axis",
                "layer_types"),)
        + tuple(("linear_attention", _GPT2_BUT_THE_LAYERS, what,
                 "layer_types") for what in ("decode_step", "pipelined")),
        rules=((dict(layer_types=("linear_attention",)), ValueError,
                "n_layers"),
               (dict(layer_types=("full_attention", "sliding") * 2),
                ValueError, "layer_types"),
               (dict(linear_key_head_dim=0), ValueError,
                "linear_key_head_dim"),
               (dict(linear_value_heads=4), NotImplementedError,
                "linear_value_heads"),
               (dict(layer_types=(), linear_allow_neg_eigval=True),
                ValueError, "linear_"),
               (dict(positions="alibi"), ValueError, "positions"))),
    "nemotron": Row(
        cfg=NEMOTRON_TINY, ref=_nemotron_ref, seq=128, embed_scale=50.0,
        checked=_by_paths(ssm_moe_lm, lambda cfg: ssm_moe_lm.leaf_paths(
            cfg.layer_types)),
        parity=(("float32", F32, "local", 5e-5, 2e-4),
                ("bfloat16", BF16, "local", 5e-3, 0.4)),
        # The selection bias chooses and is not trained.
        also=lambda stats, grads: _rows_and_bias(stats, grads, (6, 4)),
        # From zero momentum the slot holds the gradient itself: the one
        # way to read dt_bias's (values of -7 to -4 beside an update of
        # 1e-6).
        unread=("ssm_dt_bias_last",),
        shapes={("layers", 0, "ssm_w_in"): (64, 64 + 192 + 4),
                ("layers", 1, "w_up"): (4, 32, 64),
                ("layers", 1, "router"): (64, 16),
                ("mtp", "w_eh"): (128, 64)},
        series=('hvd_ssm_chunks_total{layer="0",path="xla"} 32',
                'hvd_moe_experts_held{layer="mtp_1"} 4',
                f'hvd_moe_rows_bound{{layer="1"}} {256 * 4}',
                'hvd_gated_norm_rows_total{layer="9",path="kernel"} 256'),
        # What lands on a share is data, not static: not counted.
        no_series=('hvd_ssm_chunks_total{layer="1"',
                   "hvd_moe_assignments_total", "hvd_gdn_"),
        scopes=("layer_0/attn/qkv/ssm_proj", "layer_0/attn/qkv/ssm_conv",
                "layer_0/attn/ssm_scan", "layer_0/attn/out/ssm_gate_norm",
                "layer_1/mlp/moe_latent", "layer_1/mlp/moe_shared",
                "layer_7/attn/local_attention", "mtp/layer_1/mlp/moe_router"),
        no_scopes=("layer_0/mlp", "layer_1/attn", "/gdn_", "/mlp_dense"),
        refused={"model_axis": ("n_experts", "n_kv_heads", "layer_types",
                                "mtp_layer_types"),
                 "seq_axis": ("layer_types", "mtp_layer_types"),
                 "packed": ("layer_types", "mtp_layer_types"),
                 "segment_ids": ("layer_types", "mtp_layer_types"),
                 "decode_step": ("positions", "n_experts", "n_kv_heads",
                                 "layer_types", "mtp_layer_types"),
                 "pipelined": ("positions", "n_experts", "n_kv_heads",
                               "layer_types", "mtp_layer_types")},
        # Without a recurrent layer the prediction module refuses alone.
        alone=tuple(("prediction_module", _NO_SSM, what, "mtp_layer_types")
                    for what in ("seq_axis", "packed", "segment_ids")),
        rules=((dict(ssm_groups=3), ValueError, "ssm_groups"),
               (dict(ssm_chunk=0), ValueError, "ssm_chunk"),
               (dict(n_kv_heads=3), ValueError, "n_kv_heads"),
               (dict(d_latent=0), ValueError, "d_latent"),
               (dict(experts_held_from=14), ValueError, "experts_held"),
               (dict(router_aux_coef=0.01), NotImplementedError,
                "auxiliary"),
               (dict(mtp_loss_coef=0.0), ValueError, "mtp_loss_coef"),
               (dict(mtp_layer_types=("sliding",)), ValueError,
                "mtp_layer_types"),
               (dict(mlp="swiglu"), ValueError, "relu2"),
               (dict(layer_types=("attention", "mlp") * 5 + ("mlp",)),
                ValueError, "ssm_"))),
    "glm": Row(
        cfg=GLM_TINY, ref=_glm_ref, seq=128, embed_scale=50.0,
        checked=_by_paths(mla_moe_lm, lambda cfg: mla_moe_lm.leaf_paths(
            cfg.n_layers)),
        parity=(("local", F32, "local", 5e-5, 5e-5),
                ("flash", F32, "flash", 5e-5, 5e-5)),
        # Two expert layers and the module's; the dense layer routes
        # nothing.
        also=lambda stats, grads: _rows_and_bias(stats, grads, (3, 4)),
        # The three references that the cell's check must refuse are
        # other functions at this size too.
        controls=(
            ("no_shared_expert", dict(shared_expert=False),
             "w_shared_down_last", 1e-4, 0.02),
            ("k_r_unrotated", dict(rotate_shared_key=False), "w_kvb_last",
             1e-4, 0.02),
            ("float8", dict(low_precision=jnp.float8_e4m3fn), "wo_last",
             1e-4, 0.02)),
        shapes={("layers", 0, "w_down"): (160, 64),
                ("layers", 1, "w_down"): (4, 48, 64),
                ("layers", 1, "router"): (64, 8),
                ("layers", 1, "w_qb"): (24, 96),
                ("layers", 1, "w_kva"): (64, 16 + 8),
                ("layers", 1, "w_kvb"): (16, 3 * (24 + 32)),
                ("layers", 1, "wo"): (96, 64),
                ("mtp", "layers", 0, "w_shared_gate"): (64, 48)},
        series=tuple(f'hvd_moe_experts_held{{layer="{i}"}} 4'
                     for i in ("1", "2", "mtp_0")) + (
            f'hvd_moe_rows_bound{{layer="1"}} {256 * 2}',),
        # The dense layer holds no expert.
        no_series=('hvd_moe_experts_held{layer="0"}',
                   "hvd_moe_assignments_total"),
        # What perfbench/mla_reduce.py reads.
        scopes=("layer_1/attn/qkv/mla_q", "layer_1/attn/qkv/mla_kv",
                "layer_1/attn/qkv/mla_rope", "layer_0/mlp/mlp_dense",
                "layer_1/mlp/moe_router", "layer_1/mlp/moe_shared",
                "mtp/layer_0/attn/qkv/mla_kv", "mtp/layer_0/mlp/moe_experts"),
        # (The module's own layer_0 is an expert layer.)
        no_scopes=("layer_1/mlp/mlp_dense", ")/layer_0/mlp/moe_router"),
        refused={"model_axis": ("head_width", "kv_latent_rank", "n_experts",
                                "dense_layers", "mtp_layer_types"),
                 "seq_axis": ("head_width", "kv_latent_rank",
                              "mtp_layer_types"),
                 "packed": ("mtp_layer_types",),
                 "segment_ids": ("mtp_layer_types",),
                 "decode_step": _GLM_NOT_THE_BLOCKS,
                 "pipelined": _GLM_NOT_THE_BLOCKS},
        # Without experts or a module to refuse beside it.
        alone=tuple(("latent_attention", _LATENT_ALONE, what, "head_width")
                    for what in ("model_axis", "seq_axis")),
        rules=((dict(head_width=0), ValueError,
                "latent attention needs head_width"),
               (dict(rope_dim=40), ValueError,
                "rope_dim=40 is wider than head_width"),
               (dict(qk_norm_per_head=True), NotImplementedError,
                "qk_norm_per_head"),
               (dict(rope_dim=0), ValueError, "come together"),
               (dict(positions="none"), ValueError, "positions='rope'"),
               (dict(rope_dim=7), ValueError, "even rope_dim"),
               (dict(qk_norm=True), NotImplementedError, "qk_norm"),
               (dict(n_kv_heads=1), NotImplementedError, "n_kv_heads"),
               (dict(dense_layers=4), ValueError,
                "dense_layers=4 must lie in 0..n"),
               (dict(dense_layers=1, mlp="relu2", d_latent=16),
                NotImplementedError, "leading dense MLP is SwiGLU"),
               (dict(_NO_EXPERTS, d_shared=0, routed_scale=1.0), ValueError,
                "dense_layers"),
               (dict(_NO_EXPERTS, dense_layers=0, routed_scale=1.0),
                ValueError, "d_shared is the shared expert"),
               (dict(d_shared=0), ValueError, "routed_scale"),
               (dict(d_latent=8), ValueError, "d_latent means nothing"),
               (dict(router_aux_coef=0.01), NotImplementedError,
                "no auxiliary loss"),
               (dict(norm_topk_prob=True), NotImplementedError,
                "renormalises"))),
    "keye": Row(
        cfg=KEYE_TINY, ref=_keye_ref, seq=128, embed_scale=50.0,
        checked=_by_paths(dsa_moe_lm, lambda cfg: dsa_moe_lm.leaf_paths(
            cfg.n_layers)),
        # Sparse attention is a route of its own, whatever is asked.
        parity=(("float32", F32, "local", 5e-5, 5e-5),),
        also=lambda stats, grads: _rows_and_kl(stats),
        # The four references that the cell's check must refuse.
        controls=(
            ("float8", dict(low_precision=jnp.float8_e4m3fn), "wo_last",
             1e-4, 0.02),
            ("half_the_keys", dict(topk=16), "wk_last", 1e-4, 0.02),
            ("no_selection", dict(select=False), "wk_last", 1e-4, 0.02),
            ("no_indexer_loss", dict(index_coef=0.0), "index_wq_last", 1e-4,
             0.02)),
        step=("full", "flash"),
        shapes={("layers", 1, "index_wq"): (64, 4 * 16),
                ("layers", 1, "index_wk"): (64, 16),
                ("layers", 1, "index_ww"): (64, 4),
                ("layers", 1, "q_norm_scale"): (32,),
                ("layers", 1, "wk"): (64, 64),
                ("layers", 1, "w_down"): (4, 48, 64),
                ("layers", 1, "router"): (64, 8)},
        series=('hvd_moe_experts_held{layer="0"} 4',
                'hvd_moe_experts_held{layer="1"} 4',
                'hvd_dsa_layers_total{path="jnp"} 2'),
        no_series=("hvd_moe_assignments_total", "hvd_ssm_"),
        # What perfbench/dsa_reduce.py reads.
        scopes=("layer_0/attn/qkv/qk_head_norm_rope",
                "layer_0/attn/qkv/dsa_index_proj",
                "layer_1/attn/flash_attention/dsa_index_scores",
                "layer_1/attn/flash_attention/dsa_select",
                "layer_1/attn/flash_attention/dsa_flash",
                "layer_1/attn/flash_attention/dsa_index_loss",
                "layer_1/mlp/moe_router", "layer_1/mlp/moe_experts"),
        no_scopes=("/mla_", "/moe_shared", "attn/local_attention"),
        refused={"model_axis": _INDEXER + ("n_experts", "n_kv_heads",
                                           "head_width", "qk_norm_per_head"),
                 "seq_axis": _INDEXER + ("head_width", "qk_norm_per_head"),
                 "packed": ("index_topk",), "segment_ids": ("index_topk",),
                 "decode_step": _KEYE_NOT_THE_BLOCKS,
                 "pipelined": _KEYE_NOT_THE_BLOCKS},
        # Without experts or grouped heads of a width of their own.
        alone=tuple(("indexer", _INDEXER_ALONE, what, "index_heads")
                    for what in ("model_axis", "seq_axis", "decode_step",
                                 "pipelined"))
        + (("head_width", dict(
            _INDEXER_ALONE, index_heads=0, index_head_dim=0, index_topk=0,
            indexer_loss_coef=0.0, qk_norm_per_head=False, head_width=48),
            "pipelined", "head_width"),),
        rules=((dict(index_topk=0), ValueError,
                "come together.*sparse attention"),
               (dict(indexer_loss_coef=0.0), ValueError, "come together"),
               (dict(index_head_dim=15), ValueError, "even index_head_dim"),
               (dict(positions="none"), ValueError, "positions='rope'"),
               (dict(qk_norm=True), ValueError, "one of them"),
               (dict(head_width=31), ValueError, "even head_dim"),
               (dict(q_latent_rank=8, kv_latent_rank=8, rope_dim=8),
                NotImplementedError, "qk_norm_per_head"),
               (dict(q_latent_rank=8, kv_latent_rank=8, rope_dim=8,
                     n_kv_heads=0, qk_norm_per_head=False),
                NotImplementedError, "indexer beside latent attention"))),
    "sdar": Row(
        cfg=SDAR_TINY, ref=_sdar_ref, seq=64, embed_scale=50.0,
        checked=_by_paths(bd_moe_lm, lambda cfg: bd_moe_lm.leaf_paths(
            cfg.n_layers)),
        # The dense form of the mask, and the flash kernels under it in
        # the interpreter (blocks of 64: one masked tile a quadrant).
        parity=(("float32", F32, "local", 5e-5, 5e-5),
                ("float32-flash", F32, "flash", 5e-5, 5e-5)),
        also=lambda stats, grads: _rows_and_masked_share(stats),
        # The seven references that the cell's check must refuse.
        controls=(
            ("causal_mask", dict(rule="causal"), "wk_last", 1e-4, 0.02),
            ("own_clean_block", dict(rule="own_clean_block"), "wk_last",
             1e-4, 0.02),
            ("noised_causal", dict(rule="noised_causal"), "wk_last", 1e-4,
             0.02),
            ("running_positions", dict(running_positions=True), "wk_last",
             1e-4, 0.02),
            ("unweighted", dict(weighted=False), "ln_f_scale", 1e-2, 0.02),
            ("shifted_labels", dict(shift=1), "ln_f_scale", 1e-4, 0.02),
            ("float8", dict(low_precision=jnp.float8_e4m3fn), "wo_last",
             1e-4, 0.02)),
        step=("full", "flash"), zero=True,
        shapes={("layers", 1, "q_norm_scale"): (32,),
                ("layers", 1, "wq"): (64, 128),
                ("layers", 1, "wk"): (64, 64),
                ("layers", 1, "w_down"): (4, 48, 64),
                ("layers", 1, "router"): (64, 8),
                ("head",): (64, 128)},
        # Both halves go through every expert layer: 2 x 2 x 64 positions.
        series=('hvd_moe_experts_held{layer="0"} 4',
                'hvd_moe_experts_held{layer="1"} 4'),
        no_series=("hvd_moe_assignments_total", "hvd_ssm_", "hvd_dsa_"),
        # What perfbench/bd_reduce.py reads.
        scopes=("embed/diffusion_assemble",
                "layer_0/attn/qkv/qk_head_norm_rope",
                "layer_1/attn/local_attention",
                "layer_1/mlp/moe_router", "layer_1/mlp/moe_experts"),
        no_scopes=("/dsa_", "/mla_", "/moe_shared", "/mtp"),
        refused={"model_axis": ("n_experts", "n_kv_heads", "head_width",
                                "qk_norm_per_head", "diffusion_block"),
                 "seq_axis": ("head_width", "qk_norm_per_head",
                              "diffusion_block"),
                 "packed": ("diffusion_block",),
                 "segment_ids": ("diffusion_block",),
                 "decode_step": _SDAR_NOT_THE_BLOCKS,
                 "pipelined": _SDAR_NOT_THE_BLOCKS},
        # Without experts or grouped heads of a width of their own: the
        # objective alone refuses what it does not run under.
        alone=tuple(("objective", _OBJECTIVE_ALONE, what, "diffusion_block")
                    for what in BEYOND_NAMES),
        rules=((dict(mask_token_id=-1), ValueError, "come together"),
               (dict(diffusion_block=0), ValueError, "come together"),
               (dict(mask_token_id=128), ValueError, "no row of a vocab"),
               (dict(diffusion_block=-4), ValueError, "come together"),
               (dict(positions="learned"), NotImplementedError,
                "positions='learned'"),
               (dict(n_layers=1, mtp_layer_types=("full_attention",),
                     mtp_loss_coef=0.1), NotImplementedError,
                "mtp_layer_types"),
               (dict(layer_types=("full_attention", "mlp")),
                NotImplementedError, "no mixer"),
               (dict(index_heads=4, index_head_dim=16, index_topk=32,
                     indexer_loss_coef=1.0), NotImplementedError,
                "sparse_attention"))),
    "jamba": Row(
        cfg=JAMBA_TINY, ref=_jamba_ref, seq=128, embed_scale=5.0,
        checked=_by_paths(mamba1_lm, lambda cfg: mamba1_lm.leaf_paths(
            cfg.layer_types)),
        # The one key-value head under four query heads, through the
        # plain route and through the flash kernels.
        parity=(("local", F32, "local", 5e-5, 2e-4),
                ("flash", F32, "flash", 5e-5, 2e-4)),
        also=lambda stats, grads: _jamba_decays(stats),
        # The six references that the cell's check must refuse are other
        # functions at this size too.
        controls=(
            ("float8", dict(low_precision=jnp.float8_e4m3fn), "w_down_last",
             1e-4, 0.02),
            ("state_reset", dict(reset_every=16), "mamba_a_log_last", 1e-4,
             0.02),
            ("one_decay", dict(one_decay=True), "mamba_a_log_last", 1e-4,
             0.02),
            ("no_inner_norms", dict(inner_norms=False), "mamba_w_dt_last",
             1e-4, 0.02),
            ("no_skip", dict(skip=False), "mamba_d_last", 1e-4, 0.02),
            ("independent_kv", dict(independent_kv=True), "wk_attn", 1e-4,
             0.02)),
        remat_rel=1e-4,
        # Values of -7 to -2 beside an update of 1e-6, as Nemotron's.
        unread=("mamba_dt_bias_last", "mamba_a_log_last"),
        shapes={("layers", 0, "mamba_w_in"): (64, 256),
                ("layers", 0, "mamba_w_x"): (128, 8 + 4 + 4),
                ("layers", 0, "mamba_w_dt"): (8, 128),
                ("layers", 0, "mamba_a_log"): (128, 4),
                ("layers", 0, "w_down"): (96, 64),
                ("layers", 2, "wk"): (64, 16)},
        series=('hvd_mamba_scan_tokens_total{layer="0",path="xla"} 256',
                'hvd_mamba_saved_state_bytes{layer="3"} '
                f'{2 * 128 * 4 * 4}',
                'hvd_short_conv_rows_total{layer="1",path="kernel"} 256',
                'hvd_mamba_gate_rows_total{layer="3",path="xla"} 256'),
        no_series=('hvd_mamba_scan_tokens_total{layer="2"',
                   'hvd_mamba_gate_rows_total{layer="2"', "hvd_ssm_",
                   "hvd_gdn_", "hvd_moe_"),
        # What perfbench/mamba1_reduce.py reads.
        scopes=("layer_0/attn/qkv/mamba_proj", "layer_0/attn/qkv/mamba_conv",
                "layer_0/attn/qkv/mamba_dt_bc", "layer_0/attn/mamba_scan",
                "layer_3/attn/out/mamba_gate", "layer_3/attn/out/mamba_out",
                "layer_0/mlp", "layer_2/attn/local_attention"),
        no_scopes=("layer_2/attn/mamba_scan", "/ssm_", "/gdn_", "/moe_"),
        refused={"model_axis": ("n_kv_heads", "layer_types"),
                 "seq_axis": ("layer_types",), "packed": ("layer_types",),
                 "segment_ids": ("layer_types",),
                 "decode_step": ("positions", "n_kv_heads", "layer_types",
                                 "mamba_inner"),
                 "pipelined": ("positions", "n_kv_heads", "layer_types",
                               "mamba_inner", "mlp")},
        alone=tuple(("mamba1", dict(n_kv_heads=0), what, "layer_types")
                    for what in ("model_axis", "seq_axis", "segment_ids")),
        rules=((dict(mamba_state=0), ValueError, "mamba_state"),
               (dict(mamba_dt_rank=0), ValueError, "mamba_dt_rank"),
               (dict(layer_types=("full_attention",) * 4), ValueError,
                "mamba_"),
               (dict(_NO_MAMBA, mamba_inner=128), ValueError,
                "mean nothing without a 'mamba' entry"),
               (dict(layer_types=("mamba", "mamba1") * 2), ValueError,
                "layer_types"))),
    "ouro": Row(
        cfg=OURO_TINY, ref=_ouro_ref, seq=64, checked=_every_leaf,
        parity=(("local", F32, "local", 5e-5, 5e-5),
                ("flash", F32, "flash", 5e-5, 5e-5)),
        also=lambda stats, grads: _exit_shares(stats),
        # The nine references that the cell's check must refuse.  A cut
        # between the passes leaves the loss alone (-1: any gap passes).
        controls=(
            ("three_passes", dict(loops=3), "layers.0.wk", 1e-4, 0.02),
            ("cut_passes", dict(cut_passes=True), "layers.0.wk", -1.0,
             0.02),
            ("norm_at_readouts", dict(norm_carried=False), "layers.0.wk",
             1e-4, 0.02),
            ("no_post_norms", dict(post_norms=False),
             "layers.0.ln2_post_scale", 1e-4, 0.02),
            ("uniform_exit", dict(uniform_exit=True), "exit_gate_w", 1e-4,
             0.02),
            ("no_entropy", dict(entropy=False), "exit_gate_w", 1e-4, 0.02),
            ("last_unnormalised", dict(last_takes_rest=False),
             "exit_gate_w", 1e-4, 0.02),
            ("last_pass_only", dict(last_pass_only=True), "layers.0.wk",
             1e-4, 0.02),
            ("float8", dict(low_precision=jnp.float8_e4m3fn),
             "layers.0.w_down", 1e-4, 0.02)),
        step=("full", "flash"), zero=True,
        shapes={("exit_gate_w",): (64, 1), ("exit_gate_b",): (1,),
                ("layers", 0, "ln1_post_scale"): (64,),
                ("layers", 0, "ln2_post_scale"): (64,),
                ("layers", 0, "w_down"): (96, 64), ("head",): (64, 128)},
        series=("hvd_lm_loops 4",),
        no_series=("hvd_moe_", "hvd_gdn_", "hvd_ssm_", "hvd_dsa_"),
        # What perfbench/loop_reduce.py reads.
        scopes=("loop_3/layer_0/attn/qkv", "layer_0/attn/out/post_norm",
                "layer_0/mlp/post_norm", "loop_norm", "head/exit_gate",
                "loss/exit_mix"),
        no_scopes=("/moe_", "/mtp", "/mla_"),
        # The loop touches neither axis nor packing: they run
        # (tests/test_looped_lm.py holds them to the single-device step).
        refused={"decode_step": ("positions", "loops"),
                 "pipelined": ("positions", "loops", "mlp",
                               "tie_embeddings")},
        # The GPT-2 block run four times: the loop alone refuses.
        alone=tuple(("loop", _LOOP_ALONE, what, "loops")
                    for what in ("decode_step", "pipelined")),
        rules=((dict(loops=0), ValueError, "loops=0"),
               (dict(loops=1), ValueError, "means nothing without loops"),
               (dict(mtp_layer_types=("full_attention",),
                     mtp_loss_coef=0.1), NotImplementedError,
                "loops=4.*mtp_layer_types"),
               (dict(diffusion_block=4, mask_token_id=127),
                NotImplementedError, "loops=4.*diffusion_block"),
               (dict(n_experts=8, experts_per_token=2, d_expert=32),
                NotImplementedError, "post_norm=True.*softmax-routed"),
               (dict(head_width=32, q_latent_rank=24, kv_latent_rank=16,
                     rope_dim=8), NotImplementedError,
                "post_norm=True.*latent attention"),
               (dict(layer_types=("mamba2",), ssm_heads=4,
                     ssm_head_dim=16, ssm_state=32, ssm_groups=2,
                     ssm_conv_kernel=4, ssm_chunk=32), NotImplementedError,
                "post_norm=True.*Mamba-2"))),
    "zaya": Row(
        cfg=ZAYA_TINY, ref=_zaya_ref, seq=64, embed_scale=20.0,
        checked=_zaya_leaves, prepare=_zaya_placed,
        parity=(("local", F32, "local", 5e-5, 5e-5),
                ("flash", F32, "flash", 5e-5, 5e-5)),
        also=_zaya_rows_and_skips,
        # The thirteen references that the cell's check must refuse.  The
        # gradient cut between the routers leaves the loss alone (-1: any
        # gap passes).
        controls=(
            ("no_mix", dict(mix=False), "layers.0.wq", 1e-4, 0.02),
            ("no_mean", dict(mean=False), "layers.0.wq", 1e-4, 0.02),
            ("no_value_shift", dict(value_shift=False), "layers.0.wv_prev",
             1e-4, 0.02),
            ("no_l2_norm", dict(l2_norm=False), "layers.2.k_temp", 1e-4,
             0.02),
            ("rotary_whole", dict(rotary_whole=True), "layers.0.wk", 1e-4,
             0.02),
            ("cut_state", dict(cut_state=True), "layers.0.router_down",
             -1.0, 0.02),
            ("gamma_zero", dict(carry=False), "layers.0.router_down", 1e-4,
             0.02),
            ("skip_nothing", dict(skip_term=False),
             "layers.2.merge2_out_scale", 1e-4, 0.02),
            ("no_skip_choice", dict(skip_choice=False),
             "layers.2.router_w3", 1e-4, 0.02),
            ("bias_weighs", dict(bias_weighs=True), "layers.2.router_w3",
             1e-4, 0.02),
            ("unweighted", dict(weighted=False), "layers.2.router_w3",
             1e-4, 0.02),
            ("plain_add", dict(scaled_merge=False),
             "layers.2.merge2_out_scale", 1e-4, 0.02),
            ("float8", dict(low_precision=jnp.float8_e4m3fn),
             "layers.2.w_down", 1e-4, 0.02)),
        step=("full", "flash"),
        shapes={("layers", 0, "wq"): (64, 32), ("layers", 0, "wk"): (64, 16),
                ("layers", 0, "wv_prev"): (64, 8),
                ("layers", 0, "cca_dw_w"): (2, 48),
                ("layers", 0, "cca_gw_w"): (2, 6, 8, 8),
                ("layers", 0, "k_temp"): (2,),
                ("layers", 1, "router_down"): (64, 16),
                ("layers", 1, "router_w3"): (16, 9),
                ("layers", 1, "router_bias"): (9,),
                ("layers", 1, "w_down"): (4, 32, 64),
                ("layers", 2, "merge2_out_scale"): (64,)},
        series=('hvd_cca_rows_total{layer="0"} 128',
                'hvd_moe_router_state_width{layer="2"} 16',
                'hvd_moe_router_choices_total{layer="1",path="argmax"} 1',
                'hvd_moe_experts_held{layer="0"} 4',
                'hvd_moe_rows_bound{layer="0"} 128',
                'hvd_moe_rows_prefix{layer="0"} 128'),
        no_series=("hvd_moe_assignments_total", "hvd_ssm_", "hvd_dsa_",
                   'path="top_k"'),
        # What perfbench/cca_reduce.py reads.
        scopes=("layer_0/attn/qkv/cca_mix", "layer_0/attn/qkv/cca_norm_rope",
                "layer_1/mlp/moe_router/router_state",
                "layer_1/mlp/moe_router/router_mlp", "layer_1/mlp/moe_skip",
                "layer_2/attn/out/res_scale", "layer_2/mlp/res_scale",
                "layer_2/mlp/moe_experts", "layer_2/attn/local_attention"),
        no_scopes=("/mla_", "/dsa_", "/moe_shared", "/mtp",
                   "qk_head_norm_rope"),
        refused={"model_axis": ("cca_taps", "n_experts"),
                 "seq_axis": ("cca_taps",), "packed": ("cca_taps",),
                 "segment_ids": ("cca_taps",),
                 "decode_step": _ZAYA_NOT_THE_BLOCKS,
                 "pipelined": _ZAYA_NOT_THE_BLOCKS + ("mlp",)},
        # The mixer without the experts, and the MLP router under plain
        # attention: each alone refuses by its own field.
        alone=tuple(("cca", _CCA_ALONE, what, "cca_taps")
                    for what in BEYOND_NAMES)
        + tuple(("router", _ROUTER_ALONE, what, "router_width")
                for what in ("decode_step", "pipelined")),
        rules=((dict(cca_taps=(3, 2)), NotImplementedError, "two taps"),
               (dict(rotary_dims=5), ValueError, "even rotary_dims"),
               (dict(positions="none"), ValueError, "positions='rope'"),
               (dict(n_kv_heads=1), ValueError, "must be even"),
               (dict(cca_taps=(), residual_scaling=False), ValueError,
                "means nothing without cca_taps"),
               (dict(qk_norm_per_head=True), NotImplementedError,
                "norms its heads itself"),
               (dict(experts_per_token=2), NotImplementedError,
                "one choice a token"),
               (dict(loops=2, exit_entropy_coef=0.05), NotImplementedError,
                "carried state.*loops=2"),
               (dict(mtp_layer_types=("full_attention",), mtp_loss_coef=0.1),
                NotImplementedError, "carried state.*mtp_layer_types"),
               (dict(d_shared=32), NotImplementedError,
                "carried state.*d_shared"),
               (dict(router_width=0), NotImplementedError,
                "residual_scaling=True.*softmax_experts"),
               (dict(cca_taps=(), rotary_dims=0), NotImplementedError,
                "residual_scaling=True.*not for attention"),
               (dict(post_norm=True), NotImplementedError,
                "post_norm=True.*compressed convolutional"),
               (dict(mlp="gelu"), ValueError, "SwiGLU"))),
}


# --- what is built once a row -----------------------------------------------

def _loss_fn(cfg):
    return tfm.diffusion_loss_fn if cfg.diffusion_block else tfm.loss_fn


class Built:
    """A row's parameters, batch, reference values and program values,
    each computed on first use and kept for the process."""

    def __init__(self, name):
        self.name, self.row = name, ROWS[name]
        self.cfg = self.row.cfg

    @functools.cached_property
    def params(self):
        params = tfm.init_params(jax.random.PRNGKey(0), self.cfg)
        params["embed"] = params["embed"] * self.row.embed_scale
        return self.row.prepare(params, self.cfg)

    @functools.cache
    def batch(self, sequences=4):
        """``(tokens, labels)``, or under block diffusion ``(tokens,
        masked, rates)`` with no token the mask's; four sequences is the
        least the step splits over four devices."""
        if self.cfg.diffusion_block:
            toks = jax.random.randint(
                jax.random.PRNGKey(1), (sequences, self.row.seq), 0,
                self.cfg.mask_token_id)
            return (toks,) + tfm.diffusion_noise(
                jax.random.PRNGKey(2), sequences, self.row.seq,
                self.cfg.diffusion_block)
        toks = jax.random.randint(jax.random.PRNGKey(1),
                                  (sequences, self.row.seq + 1), 0,
                                  self.cfg.vocab_size)
        return toks[:, :-1], toks[:, 1:]

    @functools.cache
    def reference(self, sequences=4, **controls):
        """``(loss, {name: gradient}, what else)`` of the float32
        reference on the batch of ``sequences``."""
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda *a: self.row.ref(
                self.cfg, *a, **controls))(self.params,
                                           *self.batch(sequences))

    @functools.cache
    def program(self, remat="none", dtype=F32, attention="local"):
        """``(loss, gradients)`` of the program's loss."""
        cfg = dataclasses.replace(self.cfg, dtype=dtype)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(lambda p, *batch: _loss_fn(
                cfg)(p, *batch, cfg, attention=attention, remat=remat)))(
                    self.params, *self.batch())

    def checked(self, tree):
        return self.row.checked(tree, self.cfg)


built = functools.cache(Built)


@pytest.fixture(scope="module")
def lm_row(request):
    return built(request.param)


# --- the families -----------------------------------------------------------

COSTLY = []        # the families a row's own file runs


def family(cases, costly=False):
    """A test of ``(lm_row, case)`` run on ``cases(row)`` of every row."""
    def mark(test):
        test.cases = cases
        if costly:
            COSTLY.append(test.__name__)
            __all__.append(test.__name__)
        return test
    return mark


# The rows whose costly families this file runs: the one without a file
# of its own, and the one whose file is the suite's longest chain as it is
# (tests/test_ssm_moe_lm.py: ~430 s of kernels, shares and prefixes).
COSTLY_ROWS = ("gpt2", "nemotron")


def pytest_generate_tests(metafunc):
    cases = getattr(metafunc.function, "cases", None)
    if cases is None:
        return
    names = (metafunc.module.COSTLY_ROWS
             if metafunc.function.__name__ in COSTLY else tuple(ROWS))
    found = [(name,) + tuple(case) for name in names
             for case in cases(ROWS[name])]
    metafunc.parametrize(
        "lm_row,case", [(name, case) for name, _, case in found],
        ids=[f"{name}-{ident}" if ident else name
             for name, ident, _ in found],
        indirect=["lm_row"], scope="module")


@family(lambda row: [(v[0], v) for v in row.parity], costly=True)
def test_loss_and_every_checked_leaf_match_the_reference(lm_row, case):
    """The program against the float32 reference: the total loss, every
    term of it, and the gradient of every leaf the reference returns."""
    ident, dtype, attention, loss_rtol, grad_rel = case
    loss, grads = lm_row.program(dtype=dtype, attention=attention)
    want, want_g, stats = lm_row.reference()
    assert abs(loss - want) <= loss_rtol * abs(want)
    got_g = lm_row.checked(grads)
    assert set(want_g) <= set(got_g)
    worst = 0.0
    for name, g in want_g.items():
        assert float(jnp.linalg.norm(g)) > 0, name
        assert rel(got_g[name], g) <= grad_rel, name
        worst = max(worst, rel(got_g[name], g))
    if dtype == F32:
        lm_row.row.also(stats, grads)
    else:
        # The float32 tolerance is not met by chance.
        assert worst > lm_row.row.parity[0][4]


@family(lambda row: [(c[0], c) for c in row.controls], costly=True)
def test_the_oracle_sees_what_the_cells_controls_change(lm_row, case):
    """The references that the cell's check must refuse are other
    functions at this size too."""
    ident, control, moved, loss_gap, grad_gap = case
    want, want_g, _ = lm_row.reference()
    off, off_g, _ = lm_row.reference(**control)
    assert abs(off - want) > loss_gap * abs(want)
    assert rel(off_g[moved], want_g[moved]) > grad_gap


@family(lambda row: [("dots", "dots"), ("full", "full")], costly=True)
def test_remat_leaves_loss_and_gradients_alone(lm_row, case):
    attention = lm_row.row.parity[0][2]
    loss, grads = lm_row.program(remat=case, attention=attention)
    want, want_g = lm_row.program(attention=attention)
    assert abs(loss - want) <= 1e-6 * abs(want)
    for (path, g), got in zip(jax.tree_util.tree_leaves_with_path(want_g),
                              jax.tree_util.tree_leaves(grads)):
        if float(jnp.linalg.norm(g)):      # a selection bias's is zero
            assert rel(got, g) <= lm_row.row.remat_rel, path


@family(lambda row: [("1", (1, False)), ("4", (4, False))]
        + [("4-zero", (4, True))] * row.zero, costly=True)
def test_train_step_takes_the_gradient_of_the_global_batch(hvd, lm_row,
                                                           case):
    """Through ``make_train_step``, on one device and on a four-device
    data mesh: loss = the reference's on the whole batch; the momentum
    slot after one step from zero = the reference's gradient of the
    **global** batch mean, and so is update / -lr (a step N times too
    large, PR 21's bug, reads N - 1; a load-balancing loss taken per
    shard instead of over the batch reads ~1e-2 on the router)."""
    from horovod_tpu.topology import build_mesh

    devices, shard_optimizer = case
    row, lr = lm_row.row, 0.1
    mesh = build_mesh(axes=("data",), devices=jax.devices()[:devices])
    optimizer = optax.sgd(lr, momentum=0.9)
    step, _, _ = tfm.make_train_step(
        lm_row.cfg, optimizer, mesh, remat=row.step[0],
        attention=row.step[1], donate=False,
        shard_optimizer=shard_optimizer)
    params = lm_row.params
    opt_state = (step.init if shard_optimizer else optimizer.init)(params)
    new, opt_state, loss = step(params, opt_state,
                                *lm_row.batch(row.step_batch))
    want, want_g, _ = lm_row.reference(row.step_batch)
    assert abs(loss - want) <= row.parity[0][3] * abs(want)
    after, before = lm_row.checked(new), lm_row.checked(params)
    momentum = (None if shard_optimizer     # flat shards, not the tree
                else lm_row.checked(opt_state[0].trace))
    for name, g in want_g.items():
        if momentum:
            assert rel(momentum[name], g) <= row.parity[0][4], name
        if name not in row.unread:
            # (after - before) / -lr loses three digits to the
            # subtraction; from zero momentum it is the gradient, which
            # is what the ZeRO form is read by (its slots are flat shards).
            assert rel((after[name] - before[name]) / -lr,
                       g) <= row.step_rel, name


@family(lambda row: [(remat, remat) for remat in ("none", "dots", "full")])
def test_the_tiny_rows_keep_qkv_projs_own_lines(lm_row, case, monkeypatch):
    """Every row's heads are narrower than a register, so plain attention's
    assembly kernels (``ops/qk_assemble.py``) refuse them whatever else
    holds (bfloat16, the flash route, no model axis): the rows' programs
    are ``qkv_proj``'s own lines in their own order (PR 50 lowered
    ``make_train_step`` of every row under the three ``remat`` at the
    parent and at the change: the text hashed equal, PERF.md)."""
    from horovod_tpu.ops import qk_assemble

    def refuse(*args, **kwargs):
        raise AssertionError("the assembly's kernels on a tiny row")

    monkeypatch.setattr(qk_assemble, "qk_assemble", refuse)
    cfg = dataclasses.replace(lm_row.cfg, dtype=BF16)
    assert cfg.head_dim % 128
    jax.eval_shape(
        lambda p, *batch: _loss_fn(cfg)(p, *batch, cfg, attention="flash",
                                        remat=case),
        tfm.init_abstract(cfg), *lm_row.batch())


def one(row):
    return [("", None)]


@family(one)
def test_specs_and_abstract_params_cover_every_leaf(lm_row, case):
    """Every leaf has a spec and an abstract twin of its shape and dtype,
    and a layer's leaves are those of the parts it holds."""
    from jax.sharding import PartitionSpec

    cfg = lm_row.cfg
    params, abstract = lm_row.params, tfm.init_abstract(cfg)
    specs = tfm.param_specs(cfg, None)
    paths = lambda tree, **kw: sorted(
        jax.tree_util.keystr(p)
        for p, _ in jax.tree_util.tree_flatten_with_path(tree, **kw)[0])
    assert paths(params) == paths(abstract) == paths(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(abstract)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    for i, layer in enumerate(params["layers"]):
        held = [part for part in tfm.layer_parts(cfg, i) if part]
        assert set(layer) == set().union(
            *(part.specs(cfg, None) for part in held)), i
        assert sum(name in ("ln1_scale", "ln2_scale")
                   for name in layer) == len(held)
    for path, shape in lm_row.row.shapes.items():
        leaf = functools.reduce(lambda tree, key: tree[key], path, params)
        assert leaf.shape == shape, path


def _traced(lm_row):
    """``(loss, abstract parameters, abstract batch)`` on two sequences."""
    cfg, tokens = lm_row.cfg, jax.ShapeDtypeStruct((2, lm_row.row.seq),
                                                   jnp.int32)
    batch = (tokens, tokens)
    if cfg.diffusion_block:
        batch = (tokens, jax.ShapeDtypeStruct(tokens.shape, bool),
                 jax.ShapeDtypeStruct(
                     (2, lm_row.row.seq // cfg.diffusion_block), F32))
    return (lambda p, *b: _loss_fn(cfg)(p, *b, cfg, attention="local"),
            tfm.init_abstract(cfg), batch)


@family(one)
def test_trace_time_series_count_what_was_traced(hvd, lm_row, case):
    """What a trace of the loss on two sequences books, by layer: each
    part's own series, and none of a part the row does not hold."""
    from horovod_tpu import telemetry

    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        loss, params, batch = _traced(lm_row)
        jax.eval_shape(loss, params, *batch)
        text = telemetry.render_prometheus()
        for series in lm_row.row.series:
            assert series in text, (series, text)
        for series in lm_row.row.no_series:
            assert series not in text, (series, text)
    finally:
        telemetry.reset_for_tests()


@family(one)
def test_scopes_name_the_parts(hvd, lm_row, case):
    """The lowered loss carries the scopes the per-layer metrics read
    (``perfbench/*_reduce.py``), and none of a part the row does not
    hold."""
    loss, params, batch = _traced(lm_row)
    text = jax.jit(loss).lower(params, *batch).as_text(debug_info=True)
    for scope in lm_row.row.scopes:
        assert scope in text, scope
    for scope in lm_row.row.no_scopes:
        assert scope not in text, scope


BEYOND = BEYOND_NAMES


def _beyond_the_data_axis(what, cfg, seq):
    """Build (never run) ``cfg`` under ``what``."""
    from horovod_tpu.topology import build_mesh

    tokens = jnp.zeros((2, seq), jnp.int32)
    if what == "segment_ids":
        return jax.eval_shape(lambda p: tfm.forward(
            p, tokens, cfg, attention="local", segment_ids=tokens),
            tfm.init_abstract(cfg))
    if what == "decode_step":
        return jax.eval_shape(
            lambda p, c: tfm.decode_step(p, tokens[:, 0], c, 0, cfg),
            tfm.init_abstract(cfg), tfm.init_kv_cache(cfg, 2, 8))
    if what == "packed":
        mesh = build_mesh(axes=("data",), devices=jax.devices()[:2])
        return tfm.make_train_step(cfg, optax.sgd(0.1), mesh, packed=True)
    axis = {"model_axis": "model", "seq_axis": "seq", "pipelined": "pipe"}[
        what]
    mesh = build_mesh(axes=("data", axis), shape=(2, 2),
                      devices=jax.devices()[:4])
    if what == "pipelined":
        return tfm.make_train_step_pipelined(cfg, optax.sgd(0.1), mesh)
    return tfm.make_train_step(cfg, optax.sgd(0.1), mesh, **{what: axis})


def _refused_by_name(what, cfg, seq, fields):
    """``cfg`` under ``what`` is refused by the argument's name and every
    one of ``fields``, in whatever order."""
    argument = {"pipelined": "make_train_step_pipelined"}.get(what, what)
    with pytest.raises(NotImplementedError, match=f"^{argument}") as refusal:
        _beyond_the_data_axis(what, cfg, seq)
    for field in fields:
        assert f"TransformerConfig.{field}=" in str(refusal.value), (
            field, refusal.value)


@family(lambda row: [(what, what) for what in BEYOND])
def test_beyond_the_data_axis_a_row_runs_or_is_refused_by_name(hvd, lm_row,
                                                               case):
    """Never a silent fall back: what a part does not implement is refused
    by the argument's name and a field of **every** part (and of the
    prediction module) that does not implement it, so that no part's
    refusal can go unnoticed behind another's; everything else builds."""
    fields = lm_row.row.refused.get(case)
    if fields is None:
        _beyond_the_data_axis(case, lm_row.cfg, lm_row.row.seq)
    else:
        _refused_by_name(case, lm_row.cfg, lm_row.row.seq, fields)


@family(lambda row: [(f"{ident}-{what}", (fields, what, field))
                     for ident, fields, what, field in row.alone])
def test_a_part_alone_refuses_by_its_own_field(hvd, lm_row, case):
    """The row cut down to one part that does not implement the argument:
    with nothing else to refuse first, it still does."""
    fields, what, field = case
    _refused_by_name(what, dataclasses.replace(lm_row.cfg, **fields),
                     lm_row.row.seq, (field,))


@family(lambda row: [(re.sub(r"\W+", "_", rule[2])[:40], rule)
                     for rule in row.rules])
def test_config_says_what_its_fields_cannot_mean(lm_row, case):
    fields, error, message = case
    with pytest.raises(error, match=message):
        dataclasses.replace(lm_row.cfg, **fields)
