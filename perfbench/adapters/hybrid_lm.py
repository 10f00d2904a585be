"""Adapter of kind ``hybrid_lm``: an Olmo-Hybrid-style decoder (layers of
gated-delta-rule linear attention and of full attention in the published
pattern, QK-norm, SwiGLU, untied head) trained through
``horovod_tpu.models.transformer.make_train_step``, the step builder the
``lm`` and ``moe_lm`` kinds use.

The configuration file holds the published sizes under their published
(Hugging Face ``olmo_hybrid``) keys, ``layer_types`` whole: the model runs
its first ``num_hidden_layers`` entries.  The traffic mix holds everything
about the job (sequence length, batch per chip, mesh axes, optimizer,
``attention`` for the full layers, ``remat``, ``shard_optimizer``,
``packed``, ``token_distribution``).  All of it reaches the step builder
as data.

What this kind asks of "Adding things" (``perfbench/README.md``), as new
files only: this adapter with ``build`` and :func:`train_flops`;
``reference/hybrid_lm.py``; ``kernel_cost_gdn.py``; ``gdn_reduce.py`` and
the four readers ``layer_metrics/gdn_*.py`` and
``full_attn_ms_per_step.py``; ``tests/test_reference_hybrid_lm.py``,
``test_flops_hybrid_lm.py``, ``test_harness_hybrid_lm.py`` and
``test_chip_compile_hybrid_lm.py``.

At set-up, outside the window, :func:`build`'s reference hook prints how
long the reference took and, per linear layer, the spread of ``alpha``
over the first batch's tokens and heads as the float32 reference computes
it (the gates' initialisation is the configuration's, ``gate_init``).
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models import transformer as tfm
from horovod_tpu.telemetry import scopes
from perfbench import kernel_cost, kernel_cost_gdn
from perfbench.adapters.lm import _optimizer as lm_optimizer
from perfbench.adapters.moe_lm import _defined, zipf_tokens
from perfbench.cell import Cell, on_first_device, seeded
from perfbench.reference import hybrid_lm as reference

MIX_KEYS = {"seq_len", "batch_per_chip", "mesh_axes", "optimizer",
            "attention", "remat", "shard_optimizer", "packed",
            "token_distribution"}
LINEAR, FULL = "linear_attention", "full_attention"


def layer_types(config: dict):
    """The layers the model runs: the head of the published pattern."""
    return tuple(config["layer_types"][:config["num_hidden_layers"]])


def matmul_parameters(config: dict) -> dict:
    """Parameters that are matmul operands, by where they sit: one linear
    layer's mixer (Wq, Wk of ``heads x key_dim``; Wv, Wz, Wo of ``heads x
    value_dim``; Wa, Wb of ``heads``), one full layer's (four ``d x d``),
    one SwiGLU MLP, the untied head.  The convolution, the norms,
    ``A_log`` and ``dt_bias`` multiply no matrix."""
    d = config["hidden_size"]
    heads = config["linear_num_value_heads"]
    keys = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    values = heads * config["linear_value_head_dim"]
    return {LINEAR: d * (2 * keys + 3 * values + 2 * heads),
            FULL: 4 * d * d,
            "mlp": 3 * d * config["intermediate_size"],
            "head": d * config["vocab_size"]}


def train_flops(config: dict, seq_len: int, global_batch: int) -> float:
    """Model FLOPs of one training step, PaLM appendix B: ``6 * tokens *
    N`` over every matmul parameter (:func:`matmul_parameters`; the
    embedding look-up is not a matmul, the untied head is), plus causal
    attention ``6 * B * T^2 * d`` per **full** layer (the ``lm`` kind's
    convention), plus the recurrence **in its recurrent form** per linear
    layer: ``S^T k``, the rank-one update and ``S^T q`` are ``6 * d_k *
    d_v`` per head and token forward, three times that trained.  Never
    the chunked algorithm's extra work, never recomputation."""
    n = matmul_parameters(config)
    kinds = layer_types(config)
    linear, full = kinds.count(LINEAR), kinds.count(FULL)
    tokens = global_batch * seq_len
    weights = (linear * n[LINEAR] + full * n[FULL]
               + len(kinds) * n["mlp"] + n["head"])
    recurrence = (3 * 6 * config["linear_key_head_dim"]
                  * config["linear_value_head_dim"]
                  * config["linear_num_value_heads"])
    return (6.0 * weights * tokens
            + 6.0 * global_batch * seq_len * seq_len
            * config["hidden_size"] * full
            + float(recurrence) * tokens * linear)


def model_config(config: dict, seq_len: int) -> tfm.TransformerConfig:
    """The published keys as the program's config."""
    if (config["num_key_value_heads"] != config["num_attention_heads"]
            or config["hidden_act"] != "silu" or config["attention_bias"]
            or config["rope_parameters"]["rope_theta"] is not None):
        raise NotImplementedError(
            "hybrid_lm adapter: full multi-head attention, silu, no bias "
            "and no rotary embedding (rope_theta null) are what the "
            "program runs for this kind")
    return tfm.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        max_seq=max(seq_len, config["max_position_embeddings"]),
        dtype=jnp.bfloat16, positions="none", qk_norm=True,
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"], mlp="swiglu",
        layer_types=layer_types(config),
        linear_key_heads=config["linear_num_key_heads"],
        linear_value_heads=config["linear_num_value_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel=config["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=config["linear_allow_neg_eigval"])


def draw_gates(key, heads: int, gate_init: dict):
    """``(A_log, dt_bias)`` of one linear layer from the configuration's
    ``gate_init``: the rate ``A`` uniform and the step ``dt`` log-uniform
    in its ranges, stored as ``log A`` and ``softplus^-1(dt)`` (the
    published parametrisation; only the ranges are the configuration's)."""
    k_a, k_dt = jax.random.split(key)
    a = jax.random.uniform(k_a, (heads,), jnp.float32, *gate_init["a_range"])
    dt = jnp.exp(jax.random.uniform(
        k_dt, (heads,), jnp.float32,
        *(math.log(x) for x in gate_init["dt_range"])))
    return jnp.log(a), dt + jnp.log(-jnp.expm1(-dt))


def build(config: dict, mix: dict, mesh) -> Cell:
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"hybrid_lm adapter: unknown mix keys "
                         f"{sorted(unknown)}")
    draw = mix["token_distribution"]
    if draw["name"] != "zipf":
        raise ValueError(f"token_distribution {draw['name']!r}: the "
                         f"hybrid_lm adapter knows 'zipf'")
    data_axis = mix["mesh_axes"][0]
    seq_len, per_chip = mix["seq_len"], mix["batch_per_chip"]
    global_batch = per_chip * int(mesh.shape[data_axis])
    cfg = model_config(config, seq_len)
    kinds = cfg.layer_types
    linear = [i for i, kind in enumerate(kinds) if kind == LINEAR]
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

    def make_arrays(key, pool):
        k_params, k_data = jax.random.split(key)
        params = tfm.init_params(k_params, cfg)
        # The configuration's gates ("assumed": alpha spread over
        # (0.9, 1), as a trained layer's).
        for i in linear:
            a_log, dt_bias = draw_gates(
                jax.random.fold_in(k_params, 100 + i),
                cfg.linear_value_heads, config["gate_init"])
            params["layers"][i].update(lin_a_log=a_log, lin_dt_bias=dt_bias)
        toks = zipf_tokens(k_data, (pool, global_batch, seq_len + 1),
                           cfg.vocab_size, draw["exponent"])
        batches = [(toks[i, :, :-1], toks[i, :, 1:]) for i in range(pool)]
        return (params, init_opt(params)), batches

    make, state_shapes, batch_shapes = seeded(
        make_arrays, (named(specs), named(opt_specs)),
        (data_sharding, data_sharding))

    ref = jax.jit(functools.partial(
        reference.loss_and_tail_grads, n_heads=cfg.n_heads,
        layer_types=kinds, linear_heads=cfg.linear_value_heads,
        key_dim=cfg.linear_key_head_dim, eps=cfg.norm_eps,
        neg_eigval=cfg.linear_allow_neg_eigval))

    def run_reference(state, batch):
        # On one device, reading the replicated weights in place.
        params, (tokens, labels) = on_first_device((state[0], batch), mesh)
        start = time.perf_counter()
        loss, grads, gates = jax.block_until_ready(
            ref(params, tokens, labels))
        print(f"reference: float32 at precision highest, the recurrence "
              f"token by token over {tokens.size} tokens: "
              f"{time.perf_counter() - start:.1f} s (compile included "
              f"where the cache did not hold it)", flush=True)
        for i, row in zip(linear, np.asarray(gates)):
            print(f"gates, first batch, layer {i} (float32 reference): "
                  f"alpha min {row[0]:.4f}, 1% {row[1]:.4f}, median "
                  f"{row[2]:.4f}, 99% {row[3]:.5f}, max {row[4]:.6f}; "
                  f"beta max {row[5]:.3f}", flush=True)
        return loss, grads

    def checked(state):
        params = state[0]
        return {"ln_f_scale": params["ln_f_scale"],
                "w_down_last": params["layers"][-1]["w_down"],
                "lin_wo_last": params["layers"][linear[-1]]["lin_wo"],
                "lin_wa_last": params["layers"][linear[-1]]["lin_wa"]}

    kernels = {}
    if mix["attention"] in ("flash", "ring_flash"):
        flash = kernel_cost.causal_attention_train(
            per_chip, cfg.n_heads, seq_len, cfg.head_dim)
        kernels["flash"] = dict(
            {k: v * kinds.count(FULL) for k, v in flash.items()},
            match=_defined(scopes.FLASH_FWD, scopes.FLASH_BWD_DQ,
                           scopes.FLASH_BWD_DKV))
    # The recurrence is jax.numpy that XLA compiles, not a Pallas kernel:
    # no trace event to match (its time is read by scope,
    # perfbench/gdn_reduce.py).  The entry carries its roofline's
    # numerator; a kernel PR adds its name here.
    kernels["gdn_scan"] = dict(
        kernel_cost_gdn.gated_delta_rule_train(
            per_chip * seq_len, cfg.linear_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            kinds.count(LINEAR), recompute=mix["remat"] == "full"),
        match=[])
    return Cell(
        step=step, state_shapes=state_shapes, batch_shapes=batch_shapes,
        make=make,
        flops_per_step=train_flops(config, seq_len, global_batch),
        item="tokens", items_per_step=global_batch * seq_len,
        grad_per_delta=-1.0 / mix["optimizer"]["learning_rate"],
        checked=checked, reference=run_reference, kernels=kernels)
