"""Adapter of kind ``mla_moe_lm``: a GLM-4.7-Flash-style decoder (latent
attention whose heads are wider than ``hidden_size / heads`` and end in a
rotary part, leading dense SwiGLU layers, then layers of SwiGLU experts
under a sigmoid router beside a shared expert, of which this chip holds a
share; a multi-token-prediction module of one such layer) trained through
``horovod_tpu.models.transformer.make_train_step``, the step builder every
LM kind uses.

The configuration file holds the published sizes under their published
(Hugging Face ``glm4_moe_lite``) keys.  ``n_routed_experts`` is what this
chip holds, from ``experts_held_from`` on; ``router_width`` is the
published count, which the router scores.  The traffic mix holds
everything about the job.  All of it reaches the step builder as data.

What this kind asks of "Adding things" (``perfbench/README.md``), as new
files only: this adapter with ``build``, :func:`train_flops` and
:func:`defined` (the kernels' instructions by number, however many a step
holds); ``reference/mla_moe_lm.py``; ``mla_reduce.py`` and the nine
readers ``layer_metrics/{mla,sigmoid_moe,dense_mlp,mtp_module}_*.py``;
``tests/test_{reference,flops,harness,chip_compile}_mla_moe_lm.py``.  The
flash kernels' cost is ``kernel_cost.causal_attention_train`` at the
head's own width, the grouped matmuls' ``kernel_cost_moe``'s, both as they
stand.

At set-up, outside the window, the weights' program chooses which experts
of each layer this chip holds, a level share of the first batch's
assignments (the configuration's ``assumed``, ``expert_placement``;
``reference.level_placement``), and :func:`build`'s reference hook prints
how
long the reference took and per expert layer the rows each held expert
receives against the buffer's bound and the prefix the layer works on, as
the float32 reference routes them.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models import moe, transformer as tfm
from horovod_tpu.telemetry import scopes
from perfbench import kernel_cost, kernel_cost_moe
from perfbench.adapters.lm import _optimizer as lm_optimizer
from perfbench.adapters.moe_lm import _defined, zipf_tokens
from perfbench.cell import Cell, on_first_device, seeded
from perfbench.reference import mla_moe_lm as reference

MIX_KEYS = {"seq_len", "batch_per_chip", "mesh_axes", "optimizer",
            "attention", "remat", "shard_optimizer", "packed",
            "token_distribution"}
# The checked leaves whose gradient is read from the momentum slot.
FROM_MOMENTUM = ("w_kvb_last",)
# The out projections that the adapter shrinks by the published depth.
OUT_PROJECTIONS = ("wo", "w_down", "w_shared_down")
# Instructions of one kernel name that :func:`defined` can tell apart.
# XLA numbers the clones of ``n`` instructions inside the conditionals of
# a share's expert layer ``2n .. 3n - 1`` (PERF.md, PR 36): the 11 expert
# layers of ``glm47flash_t8192`` hold 132 forward grouped matmuls, read
# ``.264`` to ``.395`` in the compiled step
# (perfbench/tests/test_chip_compile_mla_moe_lm.py holds every kernel
# instruction of the step to this list).
KERNEL_INSTANCES = 512


def defined(*kernel_names):
    """``moe_lm._defined`` with room for every instruction a step of this
    kind holds: a kernel that the list misses is read as
    ``xla_ms_per_step``."""
    return _defined(*kernel_names, instances=KERNEL_INSTANCES)


def matmul_parameters(config: dict) -> dict:
    """Parameters that are matmul operands **for one token**, by where
    they sit: latent attention (``W_qa``, ``W_qb``, ``W_kva`` to the
    latent and the rotary key, ``W_kvb`` to every head's rotary-free key
    and value, ``W_o``), the dense MLP, an expert layer's feed-forward
    part (router over the published count, the shared expert, and the
    routed experts a token passes through **on this chip**: of its
    ``num_experts_per_tok``, the expected ``n_routed_experts /
    router_width``), the untied head, and the prediction module's
    combining matrix.  The norms multiply no matrix."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    expert = 3 * d * config["moe_intermediate_size"]
    here = (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["router_width"])
    return {
        "attention": (d * config["q_lora_rank"]
                      + config["q_lora_rank"] * heads * qk
                      + d * (config["kv_lora_rank"]
                             + config["qk_rope_head_dim"])
                      + config["kv_lora_rank"] * heads
                      * (config["qk_nope_head_dim"] + config["v_head_dim"])
                      + heads * config["v_head_dim"] * d),
        "dense": 3 * d * config["intermediate_size"],
        "experts": (d * config["router_width"]
                    + config["n_shared_experts"] * expert + here * expert),
        "head": d * config["vocab_size"],
        "mtp_combine": 2 * d * d}


def train_flops(config: dict, seq_len: int, global_batch: int) -> float:
    """Model FLOPs of one training step, PaLM appendix B with **active**
    parameters: ``6 * tokens * N`` over every matmul parameter a token
    uses (:func:`matmul_parameters`; the head twice, once for the
    prediction module, whose layers and combining matrix count too; the
    embedding look-ups are not matmuls), plus causal attention ``6 * B *
    T^2 * heads * head width`` per layer, the module's included (the
    ``lm`` kind's convention at the width the scores and the values
    have).  Never recomputation."""
    n = matmul_parameters(config)
    layers = config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    module = config["num_nextn_predict_layers"]
    tokens = global_batch * seq_len
    weights = ((layers + module) * n["attention"] + dense * n["dense"]
               + (layers - dense + module) * n["experts"]
               + (1 + module) * n["head"] + module * n["mtp_combine"])
    wide = config["num_attention_heads"] * config["v_head_dim"]
    return (6.0 * weights * tokens
            + 6.0 * global_batch * seq_len * seq_len * wide
            * (layers + module))


def model_config(config: dict, seq_len: int) -> tfm.TransformerConfig:
    """The published keys as the program's config."""
    head = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    if (config["hidden_act"] != "silu" or config["attention_bias"]
            or config["topk_method"] != "noaux_tc"
            or not config["norm_topk_prob"]
            or (config["n_group"], config["topk_group"]) != (1, 1)
            or config["n_shared_experts"] != 1
            or config["num_nextn_predict_layers"] != 1
            or config["tie_word_embeddings"]
            or config["rope_scaling"] is not None
            or config["partial_rotary_factor"] != 1
            or config["num_key_value_heads"] != config["num_attention_heads"]
            or config["v_head_dim"] != head):
        raise NotImplementedError(
            "mla_moe_lm adapter: silu, no bias, the noaux_tc router "
            "renormalised without group limiting, one shared expert, one "
            "prediction module, an untied head, no rope scaling, every "
            "rotary dim turned, a key a head and value heads as wide as "
            "the query's are what the program runs for this kind")
    return tfm.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        max_seq=max(seq_len, config["max_position_embeddings"]),
        dtype=jnp.bfloat16, positions="rope",
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"], tie_embeddings=False,
        head_width=head, q_latent_rank=config["q_lora_rank"],
        kv_latent_rank=config["kv_lora_rank"],
        rope_dim=config["qk_rope_head_dim"], mlp="swiglu",
        n_experts=config["router_width"],
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=(config["n_shared_experts"]
                  * config["moe_intermediate_size"]),
        routed_scale=float(config["routed_scaling_factor"]),
        experts_held=config["n_routed_experts"],
        experts_held_from=config["experts_held_from"],
        dense_layers=config["first_k_dense_replace"],
        mtp_layer_types=(tfm.FULL_ATTENTION,)
        * config["num_nextn_predict_layers"],
        mtp_loss_coef=config["mtp_loss_coef"])


def reference_dims(cfg: tfm.TransformerConfig) -> dict:
    return {"n_heads": cfg.n_heads, "head_dim": cfg.head_dim,
            "rope_dim": cfg.rope_dim, "kv_rank": cfg.kv_latent_rank,
            "eps": cfg.norm_eps, "theta": cfg.rope_theta,
            "top_k": cfg.experts_per_token,
            "routed_scale": cfg.routed_scale,
            "held_from": cfg.experts_held_from}


def build(config: dict, mix: dict, mesh) -> Cell:
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"mla_moe_lm adapter: unknown mix keys "
                         f"{sorted(unknown)}")
    draw = mix["token_distribution"]
    if draw["name"] != "zipf":
        raise ValueError(f"token_distribution {draw['name']!r}: the "
                         f"mla_moe_lm adapter knows 'zipf'")
    if mix["shard_optimizer"]:
        raise NotImplementedError(
            "shard_optimizer=true: this adapter's check reads a gradient "
            "from the whole momentum slot (checked)")
    data_axis = mix["mesh_axes"][0]
    seq_len, per_chip = mix["seq_len"], mix["batch_per_chip"]
    global_batch = per_chip * int(mesh.shape[data_axis])
    cfg = model_config(config, seq_len)
    expert_layers = ([str(i) for i in range(cfg.dense_layers, cfg.n_layers)]
                     + [f"mtp_{i}" for i in range(len(cfg.mtp_layer_types))])
    # The lm kind's optimizers (SGD today; AdamW: ROADMAP R10).  A packed
    # mix is refused by the step builder, by name (ROADMAP R11).
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

    def make_arrays(key, pool):
        k_params, k_data = jax.random.split(key)
        params = tfm.init_params(k_params, cfg)
        # The configuration's embedding scale ("assumed").
        params["embed"] = config["embedding_init_std"] * jax.random.normal(
            jax.random.fold_in(k_params, 1), params["embed"].shape,
            jnp.float32)
        for layer in params["layers"] + params["mtp"]["layers"]:
            for name in OUT_PROJECTIONS:
                if name in layer:
                    layer[name] = layer[name] * shrink
        toks = zipf_tokens(k_data, (pool, global_batch, seq_len + 1),
                           cfg.vocab_size, draw["exponent"])
        batches = [(toks[i, :, :-1], toks[i, :, 1:]) for i in range(pool)]
        # Which experts of each layer this chip holds: those the first
        # batch's first sequence loads as a balanced router loads every
        # expert (the configuration's "assumed", expert_placement).
        stack, module = reference.level_placement(
            params, toks[0, 0, :-1], toks[0, 0, 1:],
            dims=reference_dims(cfg), dense_layers=cfg.dense_layers)
        for layer, perm in zip(
                params["layers"][cfg.dense_layers:]
                + params["mtp"]["layers"], stack + module):
            layer["router"] = layer["router"][:, perm]
        return (params, init_opt(params)), batches

    make, state_shapes, batch_shapes = seeded(
        make_arrays, (named(specs), named(opt_specs)),
        (data_sharding, data_sharding))

    ref = jax.jit(functools.partial(
        reference.loss_and_tail_grads, dims=reference_dims(cfg),
        dense_layers=cfg.dense_layers, mtp_coef=cfg.mtp_loss_coef))
    tokens_per_chip = per_chip * seq_len
    bound = moe.rows_bound(tokens_per_chip, cfg.experts_per_token,
                           cfg.held_experts)
    prefix = moe.rows_prefix(tokens_per_chip, cfg.experts_per_token,
                             cfg.held_experts, cfg.n_experts)
    expected = (global_batch * seq_len * cfg.experts_per_token
                / cfg.n_experts)

    def run_reference(state, batch):
        # On one device, reading the replicated weights in place.
        params, (tokens, labels) = on_first_device((state[0], batch), mesh)
        start = time.perf_counter()
        loss, grads, stats = jax.block_until_ready(
            ref(params, tokens, labels))
        print(f"reference: float32 at precision highest, attention over "
              f"{tokens.size} tokens a block of query rows at a time, the "
              f"held experts one after another: "
              f"{time.perf_counter() - start:.1f} s (compile included "
              f"where the cache did not hold it)", flush=True)
        for name, rows in zip(expert_layers, np.asarray(stats["rows"])):
            # The reference's own routing, not the program's: what the
            # program does with a full buffer is a tier-1 test
            # (tests/test_mla_moe_lm.py, the adversarial router).
            print(f"held experts, first batch, layer {name} (float32 "
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
        return loss, grads

    paths = reference.leaf_paths(cfg.n_layers)
    grad_per_delta = -1.0 / mix["optimizer"]["learning_rate"]

    def checked(state):
        """The leaves check (b) recovers a gradient from.  A parameter's
        change is -lr x its gradient.  ``W_kvb``'s is not read that way:
        behind the final norm of a residual stream four times the unit
        scale its update is ~1e-7 beside values of 0.04, and the float32
        rounding of ``new - old`` alone reads 0.025-0.032 (PERF.md, PR
        37); it is read from the momentum slot, which after one step from
        zero holds the gradient itself, rounded to bf16 once, and is
        handed over divided by ``grad_per_delta`` so that the harness's
        product gives it back (``ssm_moe_lm``'s way with ``A_log``)."""
        params, opt_state = state
        momentum = next(s.trace for s in opt_state if hasattr(s, "trace"))
        return {name: (reference.leaf(momentum, paths[name]).astype(
                           jnp.float32) / grad_per_delta
                       if name in FROM_MOMENTUM
                       else reference.leaf(params, paths[name]))
                for name in reference.CHECKED}

    kernels = {}
    if mix["attention"] in ("flash", "ring_flash"):
        flash = kernel_cost.causal_attention_train(
            per_chip, cfg.n_heads, seq_len, cfg.head_dim)
        kernels["flash"] = dict(
            {k: v * (cfg.n_layers + len(cfg.mtp_layer_types))
             for k, v in flash.items()},
            match=defined(scopes.FLASH_FWD, scopes.FLASH_BWD_DQ,
                          scopes.FLASH_BWD_DKV))
    # The rows a uniform router sends to the held experts: what lands
    # here is data (the reference prints the first batch's).
    kernels["moe_gmm"] = dict(
        kernel_cost_moe.expert_matmuls_train(
            tokens_per_chip * cfg.experts_per_token * cfg.held_experts
            // cfg.n_experts, cfg.d_model, cfg.d_expert, cfg.held_experts,
            len(expert_layers)),
        match=defined(scopes.MOE_GMM, scopes.MOE_GMM_NT, scopes.MOE_TGMM))
    return Cell(
        step=step, state_shapes=state_shapes, batch_shapes=batch_shapes,
        make=make,
        flops_per_step=train_flops(config, seq_len, global_batch),
        item="tokens", items_per_step=global_batch * seq_len,
        grad_per_delta=grad_per_delta, checked=checked, reference=run_reference, kernels=kernels)
