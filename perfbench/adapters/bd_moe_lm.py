"""Adapter of kind ``bd_moe_lm``: an SDAR-style decoder (the Qwen3-MoE
layer: grouped query heads whose total width is not the hidden size,
QK-norm a head at a time, every layer's feed-forward part SwiGLU experts
under a softmax router, of which this chip holds a share) trained on the
**block-diffusion objective** through
``horovod_tpu.models.transformer.make_train_step``, the step builder every
LM kind uses: the step takes clean tokens, which of them are noised and
each block's rate, and runs the stack once over the clean sequence and its
noised copy under the mask of ``ops.flash_attention.BlockDiffusion``.

The configuration file holds the published sizes under their published
(Hugging Face ``sdar_moe``, which are ``Qwen3MoeConfig``'s) keys.
``num_experts`` is what this chip holds, from ``experts_held_from`` on;
``published.num_experts`` is the published count, which the router scores.
``block_length`` is the diffusion block's (``assumed``).  The traffic mix
holds everything about the job, the noise's distribution included.  All of
it reaches the step builder as data.

What this kind asks of "Adding things" (``perfbench/README.md``), as new
files only: this adapter with ``build`` and :func:`train_flops`;
``reference/bd_moe_lm.py``; ``kernel_cost_bd.py``; ``bd_reduce.py`` and
the eight readers ``layer_metrics/bd_*.py``; ``controls_bd_moe_lm.py``;
``tests/test_{reference,flops,harness,chip_compile}_bd_moe_lm.py``.  The
grouped matmuls' cost is ``kernel_cost_moe``'s as it stands.

At set-up, outside the window, the weights' program chooses which experts
of each layer this chip holds, a level share of the first batch's doubled
stream (the configuration's ``assumed``, ``expert_placement``), and
:func:`build`'s reference hook prints how long the reference took, the
share of the noised copy that is the mask id, per layer the rows each held
expert receives, and what the flash kernels compute over what the mask
needs.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models import moe, transformer as tfm
from horovod_tpu.ops import flash_attention
from horovod_tpu.telemetry import scopes
from perfbench import kernel_cost_bd, kernel_cost_moe
from perfbench.adapters.dsa_moe_lm import defined
from perfbench.adapters.lm import _optimizer as lm_optimizer
from perfbench.adapters.moe_lm import zipf_tokens
from perfbench.cell import Cell, on_first_device, seeded
from perfbench.reference import bd_moe_lm as reference

MIX_KEYS = {"seq_len", "batch_per_chip", "mesh_axes", "optimizer",
            "attention", "remat", "shard_optimizer", "packed",
            "token_distribution", "noise"}
# The checked leaves whose gradient is read from the momentum slot.
FROM_MOMENTUM = ("wk_last",)
# The out projections that the adapter shrinks by the published depth.
OUT_PROJECTIONS = ("wo", "w_down")


def matmul_parameters(config: dict) -> dict:
    """Parameters that are matmul operands **for one position**, by where
    they sit: attention (``W_q`` and ``W_o`` of ``heads x head_dim``,
    ``W_k`` and ``W_v`` of ``kv heads x head_dim``), a layer's experts (the
    router over the published count and the routed experts a position
    passes through **on this chip**: of its ``num_experts_per_tok``, the
    expected ``num_experts / published.num_experts``; ``dsa_moe_lm``'s
    convention) and the untied head.  The norms multiply no matrix."""
    d, hd = config["hidden_size"], config["head_dim"]
    published = config["published"]["num_experts"]
    here = (config["num_experts_per_tok"] * config["num_experts"]
            / published)
    return {
        "attention": (2 * d * config["num_attention_heads"] * hd
                      + 2 * d * config["num_key_value_heads"] * hd),
        "experts": (d * published
                    + here * 3 * d * config["moe_intermediate_size"]),
        "head": d * config["vocab_size"]}


def train_flops(config: dict, seq_len: int, global_batch: int) -> float:
    """Model FLOPs of one training step on ``global_batch`` sequences of
    ``seq_len`` clean tokens, term by term; never recomputation, never a
    pair the mask hides, never a row that feeds no loss's head.

    * the layers' matmul parameters (:func:`matmul_parameters`) over the
      ``2 * seq_len`` positions the objective runs, the clean sequence and
      its noised copy: ``6 * 2 L * N`` (PaLM appendix B: 2 forward, 4
      backward);
    * the head over the ``seq_len`` positions of the noised copy alone:
      ``6 * L * N_head`` (a program that sends the clean half through the
      head too is credited nothing for it);
    * attention over the pairs the mask needs,
      ``kernel_cost_bd.needed_pairs``: ``L ** 2 + L * block`` a head a
      layer (a quarter of ``(2 L) ** 2``), ``4 * head_dim`` a pair forward
      (QK^T, PV) and twice that backward: ``12 * pairs * heads *
      head_dim``."""
    n = matmul_parameters(config)
    layers = config["num_hidden_layers"]
    tokens = global_batch * seq_len
    pairs = global_batch * kernel_cost_bd.needed_pairs(
        seq_len, config["block_length"])
    weights = 6.0 * (2 * tokens * layers * (n["attention"] + n["experts"])
                     + tokens * n["head"])
    attention = (12.0 * pairs * config["num_attention_heads"]
                 * config["head_dim"] * layers)
    return weights + attention


def model_config(config: dict, seq_len: int) -> tfm.TransformerConfig:
    """The published keys as the program's config."""
    if (config["hidden_act"] != "silu" or config["attention_bias"]
            or not config["norm_topk_prob"]
            or config["decoder_sparse_step"] != 1
            or config["mlp_only_layers"]
            or config["tie_word_embeddings"]
            or config["use_sliding_window"]
            or config["rope_scaling"] is not None):
        raise NotImplementedError(
            "bd_moe_lm adapter: silu, no bias, every layer an expert layer "
            "under the renormalised softmax router, an untied head, no "
            "sliding window and the plain rotary embedding are what the "
            "program runs for this kind")
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
        qk_norm_per_head=True, mlp="swiglu",
        n_experts=config["published"]["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"], norm_topk_prob=True,
        experts_held=config["num_experts"],
        experts_held_from=config["experts_held_from"],
        diffusion_block=config["block_length"],
        # The last row of the vocabulary slice this chip holds; the
        # traffic draws no token from it ("assumed", mask_id).
        mask_token_id=config["vocab_size"] - 1)


def reference_dims(cfg: tfm.TransformerConfig) -> dict:
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "eps": cfg.norm_eps,
            "theta": cfg.rope_theta, "top_k": cfg.experts_per_token,
            "held_from": cfg.experts_held_from,
            "block": cfg.diffusion_block, "mask_id": cfg.mask_token_id}


def make_arrays(key, pool, *, cfg, config, draw, noise, global_batch,
                seq_len, init_opt):
    """``(state, [batch] * pool)`` from ``key``: the weights as the
    configuration's ``assumed`` says, and batches ``(tokens [B, L] clean
    ids, masked [B, L] bool, rates [B, L / block])``: Zipf tokens over the
    rows that are not the mask's, the noise by the program's own
    ``diffusion_noise``."""
    k_params, k_data = jax.random.split(key)
    k_tokens, k_noise = jax.random.split(k_data)
    params = tfm.init_params(k_params, cfg)
    params["embed"] = config["embedding_init_std"] * jax.random.normal(
        jax.random.fold_in(k_params, 1), params["embed"].shape, jnp.float32)
    shrink = (2 * config["published"]["num_hidden_layers"]) ** -0.5
    for layer in params["layers"]:
        for name in OUT_PROJECTIONS:
            layer[name] = layer[name] * shrink
    toks = zipf_tokens(k_tokens, (pool, global_batch, seq_len),
                       cfg.vocab_size - 1, draw["exponent"])
    batches = [(toks[i],) + tfm.diffusion_noise(
        jax.random.fold_in(k_noise, i), global_batch, seq_len,
        cfg.diffusion_block, noise["t_min"]) for i in range(pool)]
    # Which experts of each layer this chip holds: those the first
    # batch's first sequence, doubled as the step doubles it, loads as a
    # balanced router loads every expert ("assumed", expert_placement).
    first = reference.stream_ids(batches[0][0][0], batches[0][1][0],
                                 cfg.mask_token_id)
    for layer, perm in zip(params["layers"], reference.level_placement(
            params, first, dims=reference_dims(cfg))):
        layer["router"] = layer["router"][:, perm]
    return (params, init_opt(params)), batches


def build(config: dict, mix: dict, mesh) -> Cell:
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"bd_moe_lm adapter: unknown mix keys "
                         f"{sorted(unknown)}")
    draw, noise = mix["token_distribution"], mix["noise"]
    if draw["name"] != "zipf" or noise["name"] != "uniform_per_block":
        raise ValueError(
            f"token_distribution {draw['name']!r}, noise {noise['name']!r}: "
            f"the bd_moe_lm adapter knows 'zipf' and 'uniform_per_block'")
    if mix["shard_optimizer"]:
        raise NotImplementedError(
            "shard_optimizer=true: this adapter's check reads a gradient "
            "from the whole momentum slot (checked)")
    data_axis = mix["mesh_axes"][0]
    seq_len, per_chip = mix["seq_len"], mix["batch_per_chip"]
    global_batch = per_chip * int(mesh.shape[data_axis])
    cfg = model_config(config, seq_len)
    optimizer = lm_optimizer(mix["optimizer"])
    # A packed mix is refused by the step builder, by name.
    step, specs, opt_specs = tfm.make_train_step(
        cfg, optimizer, mesh, data_axis=data_axis,
        attention=mix["attention"], remat=mix["remat"],
        shard_optimizer=mix["shard_optimizer"], packed=mix["packed"],
        steps_per_call=1)

    def named(tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), tree,
            is_leaf=lambda x: isinstance(x, P))

    data_sharding = NamedSharding(mesh, P(data_axis))
    dims = reference_dims(cfg)
    make, state_shapes, batch_shapes = seeded(
        functools.partial(
            make_arrays, cfg=cfg, config=config, draw=draw, noise=noise,
            global_batch=global_batch, seq_len=seq_len,
            init_opt=optimizer.init),
        (named(specs), named(opt_specs)), (data_sharding,) * 3)

    ref = jax.jit(functools.partial(reference.loss_and_tail_grads,
                                    dims=dims))
    # Positions a chip runs through every layer: both halves.
    positions_per_chip = per_chip * 2 * seq_len
    bound = moe.rows_bound(positions_per_chip, cfg.experts_per_token,
                           cfg.held_experts)
    prefix = moe.rows_prefix(positions_per_chip, cfg.experts_per_token,
                             cfg.held_experts, cfg.n_experts)
    expected = (global_batch * 2 * seq_len * cfg.experts_per_token
                / cfg.n_experts)
    # What the kernels will be traced with: the auto blocks of one half.
    block = flash_attention._auto_block(seq_len, cfg.head_dim)
    classes, causal = (flash_attention.block_classes(
        2 * seq_len, block, block, mask) for mask in (
            flash_attention.BlockDiffusion(seq_len, cfg.diffusion_block),
            True))

    def run_reference(state, batch):
        # On one device, reading the replicated weights in place.
        params, (tokens, masked, rates) = on_first_device(
            (state[0], batch), mesh)
        start = time.perf_counter()
        loss, grads, stats = jax.block_until_ready(
            ref(params, tokens, masked, rates))
        print(f"reference: float32 at precision highest, its own mask from "
              f"the four rules (noised copy first), attention over "
              f"{2 * tokens.size} positions a block of query rows at a "
              f"time, the held experts one after another: "
              f"{time.perf_counter() - start:.1f} s (compile included "
              f"where the cache did not hold it); "
              f"{100.0 * float(stats['masked_share']):.2f}% of the noised "
              f"copy is the mask id ({cfg.mask_token_id}), which is "
              f"{50.0 * float(stats['masked_share']):.2f}% of all "
              f"positions on ONE embedding row", flush=True)
        print(f"flash kernels under BlockDiffusion({seq_len}, "
              f"{cfg.diffusion_block}), blocks of {block}: a head's grid "
              f"steps skipped {classes['skipped']} / interior "
              f"{classes['interior']} / masked {classes['diagonal']}; "
              f"score elements computed {classes['computed']} over needed "
              f"{classes['needed']} = "
              f"{classes['computed'] / classes['needed']:.4f} (each of the "
              f"three kernels; a causal call over the {2 * seq_len} "
              f"positions would compute "
              f"{causal['computed'] / classes['needed']:.4f}"
              f" x)", flush=True)
        for i, rows in enumerate(np.asarray(stats["rows"])):
            # The reference's own routing, not the program's.
            print(f"held experts, first batch, layer {i} (float32 "
                  f"reference routing): rows per held expert min "
                  f"{rows.min()} / mean {rows.mean():.1f} / max "
                  f"{rows.max()} against the expected {expected:.0f} "
                  f"(positions x {cfg.experts_per_token} / "
                  f"{cfg.n_experts}); {rows.sum()} rows, on the prefix of "
                  f"{prefix} "
                  f"{'(inside it)' if rows.sum() <= prefix else '(PAST it)'}"
                  f", for a buffer of {bound} = positions x "
                  f"min({cfg.experts_per_token}, {cfg.held_experts}), "
                  f"which no routing can exceed: dropped 0 by the bound",
                  flush=True)
        return loss, grads

    paths = reference.leaf_paths(cfg.n_layers)
    grad_per_delta = -1.0 / mix["optimizer"]["learning_rate"]

    def checked(state):
        """The leaves check (b) recovers a gradient from.  A parameter's
        change is -lr x its gradient; the leaves of ``FROM_MOMENTUM`` sit
        behind the masked softmax and their updates are lost in the
        float32 rounding of ``new - old`` (``dsa_moe_lm``'s finding for
        ``W_k``: PERF.md, PR 39): they are read from the momentum slot,
        which after one step from zero holds the gradient itself, rounded
        to bf16 once, and handed over divided by ``grad_per_delta`` so
        that the harness's product gives it back."""
        params, opt_state = state
        momentum = next(s.trace for s in opt_state if hasattr(s, "trace"))
        return {name: (reference.leaf(momentum, paths[name]).astype(
                           jnp.float32) / grad_per_delta
                       if name in FROM_MOMENTUM
                       else reference.leaf(params, paths[name]))
                for name in reference.CHECKED}

    kernels = {}
    if mix["attention"] in ("flash", "ring_flash"):
        kernels["flash"] = dict(
            {k: v * cfg.n_layers for k, v in
             kernel_cost_bd.block_diffusion_attention_train(
                 per_chip, cfg.n_heads, seq_len, cfg.diffusion_block,
                 cfg.head_dim).items()},
            match=defined(scopes.FLASH_FWD, scopes.FLASH_BWD_DQ,
                          scopes.FLASH_BWD_DKV))
    # The rows a uniform router sends to the held experts: what lands
    # here is data (the reference prints the first batch's).
    kernels["moe_gmm"] = dict(
        kernel_cost_moe.expert_matmuls_train(
            positions_per_chip * cfg.experts_per_token * cfg.held_experts
            // cfg.n_experts, cfg.d_model, cfg.d_expert, cfg.held_experts,
            cfg.n_layers),
        match=defined(scopes.MOE_GMM, scopes.MOE_GMM_NT, scopes.MOE_TGMM))
    return Cell(
        step=step, state_shapes=state_shapes, batch_shapes=batch_shapes,
        make=make,
        flops_per_step=train_flops(config, seq_len, global_batch),
        # The clean tokens: what a user counts.
        item="tokens", items_per_step=global_batch * seq_len,
        grad_per_delta=grad_per_delta, checked=checked,
        reference=run_reference, kernels=kernels)
