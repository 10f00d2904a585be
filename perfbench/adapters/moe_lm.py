"""Adapter of kind ``moe_lm``: an OLMoE-style decoder (rotary positions,
QK-norm, untied head, a dropless top-k mixture of SwiGLU experts in every
layer) trained through ``horovod_tpu.models.transformer.make_train_step``,
the step builder the ``lm`` kind uses.

The configuration file holds the published sizes under their published
(Hugging Face OLMoE) keys plus the two router-loss coefficients; the
traffic mix holds everything about the job (sequence length, batch per
chip, mesh axes, optimizer, ``attention``, ``remat``, ``shard_optimizer``,
``packed``, ``token_distribution``).  All of it reaches the step builder
as data.

What this kind asks of "Adding things" (``perfbench/README.md``), as new
files only: this adapter with ``build``, :func:`train_flops` and the batch
generator; ``reference/moe_lm.py``; ``kernel_cost_moe.py``;
``moe_reduce.py`` and the four ``layer_metrics/moe_*.py`` readers;
``tests/test_reference_moe_lm.py``, ``test_flops_moe_lm.py`` and
``test_harness_moe_lm.py``.

At set-up, outside the window, :func:`build`'s reference hook prints the
expert load of the first batch per layer (max/mean and min/mean tokens per
expert, experts with no token) as the float32 reference routes it, and
"dropped 0": the program has no capacity, every assignment is computed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models import transformer as tfm
from horovod_tpu.telemetry import scopes
from perfbench import kernel_cost, kernel_cost_moe
from perfbench.adapters.lm import _optimizer as lm_optimizer
from perfbench.cell import Cell, on_first_device, seeded
from perfbench.reference import moe_lm as reference

MIX_KEYS = {"seq_len", "batch_per_chip", "mesh_axes", "optimizer",
            "attention", "remat", "shard_optimizer", "packed",
            "token_distribution"}


def train_flops(config: dict, seq_len: int, global_batch: int) -> float:
    """Model FLOPs of one training step, PaLM appendix B with **active**
    parameters: ``6 * tokens * [layers * (4 d^2 + k * 3 d f + d E) + d V]``
    (attention projections, the ``k`` experts a token uses of width ``f``,
    the router, and the untied head once: the embedding look-up is not a
    matmul) plus causal attention ``6 * B * T^2 * d * layers``.  No
    recomputation, no auxiliary-loss arithmetic."""
    d, f, v = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    layers, experts, k = (config["num_hidden_layers"],
                          config["num_experts"],
                          config["num_experts_per_tok"])
    active = layers * (4 * d * d + k * 3 * d * f + d * experts) + d * v
    tokens = global_batch * seq_len
    return (6.0 * active * tokens
            + 6.0 * global_batch * seq_len * seq_len * d * layers)


def zipf_tokens(key, shape, vocab: int, exponent: float):
    """``shape`` int32 tokens, rank ``r`` of a seeded permutation of the
    vocabulary drawn with probability proportional to ``r ** -exponent``:
    by inverse CDF (``searchsorted`` of uniforms in the cumulative
    weights), which holds ``samples + vocab`` numbers where
    ``jax.random.categorical`` would hold ``samples x vocab``."""
    k_perm, k_draw = jax.random.split(key)
    weights = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -exponent
    cdf = jnp.cumsum(weights) / jnp.sum(weights)
    ranks = jnp.searchsorted(cdf, jax.random.uniform(k_draw, shape))
    permutation = jax.random.permutation(k_perm, vocab)
    return permutation[jnp.minimum(ranks, vocab - 1)].astype(jnp.int32)


def _defined(*kernel_names, instances: int = 64):
    """Substrings that pick out the trace events of the kernels named (by
    their ``pallas_call``'s ``name=``).  An event's text is the whole
    instruction, operands by name included, and ``trace_reduce`` matches
    substrings: ``%moe_gmm.`` alone would also match the fusion that
    *reads* ``%moe_gmm.7`` (measured: 65 ms a step for 38 of kernels).
    Only the instruction itself holds ``%<name>.<n> = ``; XLA numbers the
    instances of one name from a small offset (18 here), so 64 is ample.
    Not by custom-call target: flash and the grouped matmuls share it."""
    return [f"%{name}{suffix} = " for name in kernel_names
            for suffix in [""] + [f".{n}" for n in range(instances)]]


def model_config(config: dict, seq_len: int) -> tfm.TransformerConfig:
    """The published keys as the program's config."""
    if (config["num_key_value_heads"] != config["num_attention_heads"]
            or config["hidden_act"] != "silu" or config["attention_bias"]
            or config["clip_qkv"] is not None
            or config["rope_scaling"] is not None):
        raise NotImplementedError(
            "moe_lm adapter: full multi-head attention, silu, no bias, no "
            "clip_qkv and no rope scaling are what the program runs")
    return tfm.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"], d_ff=0,
        max_seq=max(seq_len, config["max_position_embeddings"]),
        dtype=jnp.bfloat16, positions="rope",
        rope_theta=float(config["rope_theta"]), qk_norm=True,
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"], mlp="swiglu",
        n_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        router_aux_coef=config["router_aux_loss_coef"],
        router_z_coef=config["router_z_loss_coef"])


def build(config: dict, mix: dict, mesh) -> Cell:
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"moe_lm adapter: unknown mix keys "
                         f"{sorted(unknown)}")
    if mix["packed"]:
        raise NotImplementedError(
            "packed=true needs a document-length generator in this "
            "adapter (ROADMAP R11)")
    draw = mix["token_distribution"]
    if draw["name"] != "zipf":
        raise ValueError(f"token_distribution {draw['name']!r}: the "
                         f"moe_lm adapter knows 'zipf'")
    data_axis = mix["mesh_axes"][0]
    seq_len, per_chip = mix["seq_len"], mix["batch_per_chip"]
    global_batch = per_chip * int(mesh.shape[data_axis])
    cfg = model_config(config, seq_len)
    # The lm kind's optimizers (SGD today; AdamW: ROADMAP R10).
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

    def make_arrays(key, pool):
        k_params, k_data = jax.random.split(key)
        params = tfm.init_params(k_params, cfg)
        # The configuration's embedding scale ("assumed": at the
        # program's 0.02 every token takes the same 8 experts).
        params["embed"] = config["embedding_init_std"] * jax.random.normal(
            jax.random.fold_in(k_params, 1), params["embed"].shape,
            jnp.float32)
        toks = zipf_tokens(k_data, (pool, global_batch, seq_len + 1),
                           cfg.vocab_size, draw["exponent"])
        batches = [(toks[i, :, :-1], toks[i, :, 1:]) for i in range(pool)]
        return (params, init_opt(params)), batches

    make, state_shapes, batch_shapes = seeded(
        make_arrays, (named(specs), named(opt_specs)),
        (data_sharding, data_sharding))

    ref = jax.jit(functools.partial(
        reference.loss_and_tail_grads, n_heads=cfg.n_heads,
        top_k=cfg.experts_per_token, eps=cfg.norm_eps, theta=cfg.rope_theta,
        aux_coef=cfg.router_aux_coef, z_coef=cfg.router_z_coef))

    def run_reference(state, batch):
        # On one device, reading the replicated weights in place.
        params, (tokens, labels) = on_first_device((state[0], batch), mesh)
        loss, grads, assignments = ref(params, tokens, labels)
        load = np.asarray(assignments)
        for i, per_expert in enumerate(load):
            mean = per_expert.mean()
            print(f"expert load, first batch, layer {i} (float32 "
                  f"reference routing): {int(per_expert.sum())} "
                  f"assignments of {tokens.size} tokens x "
                  f"{cfg.experts_per_token}; tokens per expert max/mean "
                  f"{per_expert.max() / mean:.3f}, min/mean "
                  f"{per_expert.min() / mean:.3f}, experts with no token "
                  f"{int((per_expert == 0).sum())}; dropped 0 (no "
                  f"capacity: every assignment is computed)", flush=True)
        return loss, grads

    def checked(state):
        params = state[0]
        last = params["layers"][-1]
        return {"ln_f_scale": params["ln_f_scale"],
                "w_down_last": last["w_down"],
                "router_last": last["router"]}

    kernels = {}
    if mix["attention"] in ("flash", "ring_flash"):
        flash = kernel_cost.causal_attention_train(
            per_chip, cfg.n_heads, seq_len, cfg.head_dim)
        kernels["flash"] = dict(
            {k: v * cfg.n_layers for k, v in flash.items()},
            match=_defined(scopes.FLASH_FWD, scopes.FLASH_BWD_DQ,
                           scopes.FLASH_BWD_DKV))
    kernels["moe_gmm"] = dict(
        kernel_cost_moe.expert_matmuls_train(
            per_chip * seq_len * cfg.experts_per_token, cfg.d_model,
            cfg.d_expert, cfg.n_experts, cfg.n_layers),
        match=_defined(scopes.MOE_GMM, scopes.MOE_GMM_NT, scopes.MOE_TGMM))
    return Cell(
        step=step, state_shapes=state_shapes, batch_shapes=batch_shapes,
        make=make,
        flops_per_step=train_flops(config, seq_len, global_batch),
        item="tokens", items_per_step=global_batch * seq_len,
        grad_per_delta=-1.0 / mix["optimizer"]["learning_rate"],
        checked=checked, reference=run_reference, kernels=kernels)
