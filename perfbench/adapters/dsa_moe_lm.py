"""Adapter of kind ``dsa_moe_lm``: a Keye-VL-2.0-style decoder (grouped
query heads whose total width is not the hidden size, QK-norm a head at a
time, an indexer beside every attention layer that chooses the keys each
query reads and learns from its own loss, every layer's feed-forward part
SwiGLU experts under a softmax router, of which this chip holds a share)
trained through ``horovod_tpu.models.transformer.make_train_step``, the
step builder every LM kind uses.

The configuration file holds the published sizes under their published
(Hugging Face ``KeyeVL2`` text config) keys.  ``num_experts`` is what this
chip holds, from ``experts_held_from`` on; ``num_local_experts`` is the
published count, which the router scores.  The traffic mix holds
everything about the job.  All of it reaches the step builder as data.

What this kind asks of "Adding things" (``perfbench/README.md``), as new
files only: this adapter with ``build`` and :func:`train_flops`;
``reference/dsa_moe_lm.py``; ``kernel_cost_dsa.py``; ``dsa_reduce.py`` and
the ten readers ``layer_metrics/{dsa,softmax_moe}_*.py``;
``tests/test_{reference,flops,harness,chip_compile}_dsa_moe_lm.py``.  The
grouped matmuls' cost is ``kernel_cost_moe``'s as it stands.

At set-up, outside the window, the weights' program chooses which experts
of each layer this chip holds, a level share of the first batch's
assignments (the configuration's ``assumed``, ``expert_placement``;
``reference.level_placement``), and :func:`build`'s reference hook prints
how long the reference took, both terms of its loss, per layer the rows
each held expert receives, and for the first layer the keys the program's
own selection keeps against the closed form and against the float32
reference's selection.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models import moe, transformer as tfm
from horovod_tpu.ops import sparse_attention
from horovod_tpu.telemetry import scopes
from perfbench import kernel_cost_dsa, kernel_cost_moe
from perfbench.adapters.lm import _optimizer as lm_optimizer
from perfbench.adapters.moe_lm import _defined, zipf_tokens
from perfbench.cell import Cell, on_first_device, seeded
from perfbench.reference import dsa_moe_lm as reference

MIX_KEYS = {"seq_len", "batch_per_chip", "mesh_axes", "optimizer",
            "attention", "remat", "shard_optimizer", "packed",
            "token_distribution"}
# The checked leaves whose gradient is read from the momentum slot.
FROM_MOMENTUM = ("wk_last", "index_wq_last")
# The out projections that the adapter shrinks by the published depth.
OUT_PROJECTIONS = ("wo", "w_down")
# Instructions of one kernel name that :func:`defined` can tell apart
# (``mla_moe_lm``'s count: XLA numbers the clones inside a share's
# conditionals ``2n .. 3n - 1``).
KERNEL_INSTANCES = 512


def defined(*kernel_names):
    return _defined(*kernel_names, instances=KERNEL_INSTANCES)


def matmul_parameters(config: dict) -> dict:
    """Parameters that are matmul operands **for one token**, by where
    they sit: attention (``W_q`` and ``W_o`` of ``heads x head_dim``,
    ``W_k`` and ``W_v`` of ``kv heads x head_dim``), the indexer (``W_qI``
    of ``indexer heads x indexer head dim``, ``W_kI`` of one head, ``W_w``
    of a number a head), a layer's experts (the router over the published
    count and the routed experts a token passes through **on this chip**:
    of its ``num_experts_per_tok``, the expected ``num_experts /
    num_local_experts``) and the untied head.  The norms multiply no
    matrix."""
    d, hd = config["hidden_size"], config["head_dim"]
    sa = config["sa_config"]
    here = (config["num_experts_per_tok"] * config["num_experts"]
            / config["num_local_experts"])
    return {
        "attention": (2 * d * config["num_attention_heads"] * hd
                      + 2 * d * config["num_key_value_heads"] * hd),
        "indexer": d * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                        + sa["indexer_num_kv_heads"] * sa["indexer_head_dim"]
                        + sa["indexer_num_heads"]),
        "experts": (d * config["num_local_experts"]
                    + here * 3 * d * config["moe_intermediate_size"]),
        "head": d * config["vocab_size"]}


def train_flops(config: dict, seq_len: int, global_batch: int) -> float:
    """Model FLOPs of one training step, term by term; never
    recomputation, never a masked-out pair.

    * matmul parameters a token uses (:func:`matmul_parameters`): ``6 *
      tokens * N`` (PaLM appendix B: 2 forward, 4 backward) for attention,
      experts and head; ``4 * tokens * N`` for the indexer, whose input
      carries no gradient (2 forward, 2 for the weights');
    * attention over the **selected** keys: ``pairs = batch * sum_t min(t +
      1, topk)`` a layer, ``4 * head_dim`` a pair a head forward (QK^T,
      PV) and twice that backward: ``12 * pairs * heads * head_dim``;
    * the indexer's scores: ``2 * indexer heads * indexer head dim`` a
      **causal** pair forward (every earlier key is scored before any is
      chosen), and backward over the selected pairs only (dqI and dkI: two
      such terms)."""
    n = matmul_parameters(config)
    sa = config["sa_config"]
    layers = config["num_hidden_layers"]
    tokens = global_batch * seq_len
    heads, hd = config["num_attention_heads"], config["head_dim"]
    pairs = global_batch * kernel_cost_dsa.selected_pairs(seq_len,
                                                         sa["topk"])
    causal = global_batch * seq_len * (seq_len + 1) // 2
    index_width = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    weights = (6.0 * tokens * (layers * (n["attention"] + n["experts"])
                               + n["head"])
               + 4.0 * tokens * layers * n["indexer"])
    attention = 12.0 * pairs * heads * hd * layers
    indexer = (2.0 * index_width * causal
               + 2 * 2.0 * index_width * pairs) * layers
    return weights + attention + indexer


def model_config(config: dict, seq_len: int) -> tfm.TransformerConfig:
    """The published keys as the program's config."""
    sa = config["sa_config"]
    if (config["hidden_act"] != "silu" or config["attention_bias"]
            or not config["norm_topk_prob"]
            or config["decoder_sparse_step"] != 1
            or config["mlp_only_layers"]
            or config["tie_word_embeddings"]
            or config["use_sliding_window"]
            or config["rope_scaling"]["rope_type"] != "default"
            or sa["indexer_num_kv_heads"] != 1):
        raise NotImplementedError(
            "dsa_moe_lm adapter: silu, no bias, every layer an expert "
            "layer under the renormalised softmax router, an untied head, "
            "no sliding window, the default rotary embedding (text "
            "positions: mrope's three streams are the token index) and an "
            "indexer with one key head are what the program runs for this "
            "kind")
    return tfm.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_width=config["head_dim"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        max_seq=max(seq_len, config["max_position_embeddings"]),
        dtype=jnp.bfloat16, positions="rope",
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"], tie_embeddings=False,
        qk_norm_per_head=True, index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        indexer_loss_coef=config["indexer_loss_coef"], mlp="swiglu",
        n_experts=config["num_local_experts"],
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"], norm_topk_prob=True,
        experts_held=config["num_experts"],
        experts_held_from=config["experts_held_from"])


def reference_dims(cfg: tfm.TransformerConfig) -> dict:
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "index_heads": cfg.index_heads,
            "index_head_dim": cfg.index_head_dim, "topk": cfg.index_topk,
            "eps": cfg.norm_eps, "theta": cfg.rope_theta,
            "top_k": cfg.experts_per_token,
            "held_from": cfg.experts_held_from}


def selection_report(cfg: tfm.TransformerConfig, params, tokens):
    """``(keys the program's selection keeps, pairs it shares with the
    float32 reference's)`` in the first layer of the first sequence: the
    program's own projections, scores and selection (the kernels, on a
    chip) from the embedded tokens, against ``reference.selection`` on
    float32 scores of the same weights."""
    layer = params["layers"][0]
    x = params["embed"][tokens[:1]]
    u = tfm._rmsnorm(x.astype(cfg.dtype), layer["ln1_scale"], cfg.norm_eps)
    qi, ki, w = tfm._indexer_proj(u, layer, cfg, jnp.arange(tokens.shape[1]))
    scale = (cfg.index_heads * cfg.index_head_dim) ** -0.5
    _, mask = sparse_attention.indexer_selection(
        qi, ki, w, topk=cfg.index_topk, index_scale=scale)
    ours = mask[0] != 0
    theirs = reference.selected(x[0], layer, reference_dims(cfg))
    return jnp.sum(ours), jnp.sum(ours & theirs)


def build(config: dict, mix: dict, mesh) -> Cell:
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"dsa_moe_lm adapter: unknown mix keys "
                         f"{sorted(unknown)}")
    draw = mix["token_distribution"]
    if draw["name"] != "zipf":
        raise ValueError(f"token_distribution {draw['name']!r}: the "
                         f"dsa_moe_lm adapter knows 'zipf'")
    if mix["shard_optimizer"]:
        raise NotImplementedError(
            "shard_optimizer=true: this adapter's check reads a gradient "
            "from the whole momentum slot (checked)")
    data_axis = mix["mesh_axes"][0]
    seq_len, per_chip = mix["seq_len"], mix["batch_per_chip"]
    global_batch = per_chip * int(mesh.shape[data_axis])
    cfg = model_config(config, seq_len)
    # The lm kind's optimizers (SGD today; AdamW: ROADMAP R10).  A packed
    # mix is refused by the step builder, by name.
    optimizer = lm_optimizer(mix["optimizer"])
    step, specs, opt_specs = tfm.make_train_step(
        cfg, optimizer, mesh, data_axis=data_axis,
        attention=mix["attention"], remat=mix["remat"],
        shard_optimizer=mix["shard_optimizer"], packed=mix["packed"],
        steps_per_call=1)

    def named(tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), tree,
            is_leaf=lambda x: isinstance(x, P))

    init_opt = step.init if mix["shard_optimizer"] else optimizer.init
    data_sharding = NamedSharding(mesh, P(data_axis))
    shrink = (2 * config["published"]["num_hidden_layers"]) ** -0.5
    dims = reference_dims(cfg)

    def make_arrays(key, pool):
        k_params, k_data = jax.random.split(key)
        params = tfm.init_params(k_params, cfg)
        # The configuration's embedding scale ("assumed").
        params["embed"] = config["embedding_init_std"] * jax.random.normal(
            jax.random.fold_in(k_params, 1), params["embed"].shape,
            jnp.float32)
        for layer in params["layers"]:
            for name in OUT_PROJECTIONS:
                layer[name] = layer[name] * shrink
        toks = zipf_tokens(k_data, (pool, global_batch, seq_len + 1),
                           cfg.vocab_size, draw["exponent"])
        batches = [(toks[i, :, :-1], toks[i, :, 1:]) for i in range(pool)]
        # Which experts of each layer this chip holds: those the first
        # batch's first sequence loads as a balanced router loads every
        # expert (the configuration's "assumed", expert_placement).
        for layer, perm in zip(params["layers"], reference.level_placement(
                params, toks[0, 0, :-1], dims=dims)):
            layer["router"] = layer["router"][:, perm]
        return (params, init_opt(params)), batches

    make, state_shapes, batch_shapes = seeded(
        make_arrays, (named(specs), named(opt_specs)),
        (data_sharding, data_sharding))

    ref = jax.jit(functools.partial(
        reference.loss_and_tail_grads, dims=dims,
        index_coef=cfg.indexer_loss_coef))
    report = jax.jit(functools.partial(selection_report, cfg))
    tokens_per_chip = per_chip * seq_len
    bound = moe.rows_bound(tokens_per_chip, cfg.experts_per_token,
                           cfg.held_experts)
    prefix = moe.rows_prefix(tokens_per_chip, cfg.experts_per_token,
                             cfg.held_experts, cfg.n_experts)
    expected = (global_batch * seq_len * cfg.experts_per_token
                / cfg.n_experts)
    closed_form = kernel_cost_dsa.selected_pairs(seq_len, cfg.index_topk)

    def run_reference(state, batch):
        # On one device, reading the replicated weights in place.
        params, (tokens, labels) = on_first_device((state[0], batch), mesh)
        start = time.perf_counter()
        loss, grads, stats = jax.block_until_ready(
            ref(params, tokens, labels))
        print(f"reference: float32 at precision highest, its own "
              f"selection by lax.top_k, attention over {tokens.size} "
              f"tokens a block of query rows at a time, the held experts "
              f"one after another: {time.perf_counter() - start:.1f} s "
              f"(compile included where the cache did not hold it); "
              f"cross-entropy {float(stats['ce']):.6f}, indexer KL "
              f"{float(stats['index_kl']):.6f} a token summed over "
              f"{cfg.n_layers} layers (x {cfg.indexer_loss_coef})",
              flush=True)
        kept, shared = (int(v) for v in report(params, tokens))
        print(f"selection, first layer, first sequence: the program keeps "
              f"{kept} keys for {seq_len} queries, the closed form sum_t "
              f"min(t + 1, {cfg.index_topk}) is {closed_form} "
              f"({'equal' if kept == closed_form else 'NOT EQUAL'}); "
              f"{shared} of them ({100.0 * shared / closed_form:.3f}%) are "
              f"the float32 reference's too (path "
              f"{sparse_attention.path(tokens)})", flush=True)
        for i, rows in enumerate(np.asarray(stats["rows"])):
            # The reference's own routing, not the program's.
            print(f"held experts, first batch, layer {i} (float32 "
                  f"reference routing): rows per held expert min "
                  f"{rows.min()} / mean {rows.mean():.1f} / max "
                  f"{rows.max()} against the expected {expected:.0f} "
                  f"(tokens x {cfg.experts_per_token} / {cfg.n_experts}); "
                  f"{rows.sum()} rows, on the prefix of {prefix} "
                  f"{'(inside it)' if rows.sum() <= prefix else '(PAST it)'}"
                  f", for a buffer of {bound} = tokens x "
                  f"min({cfg.experts_per_token}, {cfg.held_experts}), "
                  f"which no routing can exceed: dropped 0 by the bound",
                  flush=True)
        if kept != closed_form:
            raise SystemExit("the program's selection does not keep the "
                             "closed form's count of keys")
        return loss, grads

    paths = reference.leaf_paths(cfg.n_layers)
    grad_per_delta = -1.0 / mix["optimizer"]["learning_rate"]

    def checked(state):
        """The leaves check (b) recovers a gradient from.  A parameter's
        change is -lr x its gradient; the leaves of ``FROM_MOMENTUM`` sit
        behind a softmax and their updates are lost in the float32
        rounding of ``new - old`` (``W_k`` reads 0.028-0.037 that way and
        0.013-0.017 from the slot: PERF.md, PR 39): they are read from the
        momentum slot,
        which after one step from zero holds the gradient itself, rounded
        to bf16 once, and handed over divided by ``grad_per_delta`` so
        that the harness's product gives it back (``mla_moe_lm``'s way
        with ``W_kvb``)."""
        params, opt_state = state
        momentum = next(s.trace for s in opt_state if hasattr(s, "trace"))
        return {name: (reference.leaf(momentum, paths[name]).astype(
                           jnp.float32) / grad_per_delta
                       if name in FROM_MOMENTUM
                       else reference.leaf(params, paths[name]))
                for name in reference.CHECKED}

    kernels = {
        "dsa_flash": dict(
            {k: v * cfg.n_layers for k, v in
             kernel_cost_dsa.sparse_attention_train(
                 per_chip, cfg.n_heads, cfg.kv_heads, seq_len, cfg.head_dim,
                 cfg.index_topk).items()},
            match=defined(scopes.DSA_FWD, scopes.DSA_BWD_DQ,
                          scopes.DSA_BWD_DKV)),
        "dsa_index": dict(
            {k: v * cfg.n_layers for k, v in
             kernel_cost_dsa.indexer_scores_train(
                 per_chip, cfg.index_heads, cfg.index_head_dim, seq_len,
                 cfg.index_topk).items()},
            match=defined(scopes.DSA_INDEX_FWD, scopes.DSA_INDEX_BWD)),
        # Booked by the parts they run under (dsa_reduce): named here so
        # that xla_ms_per_step does not count them as XLA's.
        "dsa_select": dict(flops=0.0, bytes=0.0,
                           match=defined(scopes.DSA_SELECT_KERNEL)),
        "dsa_probs": dict(flops=0.0, bytes=0.0,
                          match=defined(scopes.DSA_PROBS)),
        # The rows a uniform router sends to the held experts: what lands
        # here is data (the reference prints the first batch's).
        "moe_gmm": dict(
            kernel_cost_moe.expert_matmuls_train(
                tokens_per_chip * cfg.experts_per_token * cfg.held_experts
                // cfg.n_experts, cfg.d_model, cfg.d_expert,
                cfg.held_experts, cfg.n_layers),
            match=defined(scopes.MOE_GMM, scopes.MOE_GMM_NT,
                          scopes.MOE_TGMM)),
    }
    return Cell(
        step=step, state_shapes=state_shapes, batch_shapes=batch_shapes,
        make=make,
        flops_per_step=train_flops(config, seq_len, global_batch),
        item="tokens", items_per_step=global_batch * seq_len,
        grad_per_delta=grad_per_delta, checked=checked,
        reference=run_reference, kernels=kernels)
