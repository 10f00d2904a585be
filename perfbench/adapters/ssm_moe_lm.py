"""Adapter of kind ``ssm_moe_lm``: a Nemotron-3-style decoder (layers that
are one part each, in the published pattern: Mamba-2 state-space mixers,
grouped-query attention, a latent mixture of experts with a shared expert
of which this chip holds a share; a multi-token-prediction module) trained
through ``horovod_tpu.models.transformer.make_train_step``, the step
builder every LM kind uses.

The configuration file holds the published sizes under their published
(Hugging Face ``nemotron_h``) keys, ``hybrid_override_pattern`` whole: the
model runs its first ``num_hidden_layers`` entries.  ``n_routed_experts``
is what this chip holds, from ``experts_held_from`` on; ``router_width``
is the published count, which the router scores.  The traffic mix holds
everything about the job.  All of it reaches the step builder as data.

What this kind asks of "Adding things" (``perfbench/README.md``), as new
files only: this adapter with ``build`` and :func:`train_flops`;
``reference/ssm_moe_lm.py``; ``kernel_cost_ssm.py``; ``ssm_reduce.py`` and
the eight readers ``layer_metrics/{ssm,latent_moe,gqa_attn,mtp}_*.py``;
``tests/test_{reference,flops,harness,chip_compile}_ssm_moe_lm.py``.

At set-up, outside the window, :func:`build`'s reference hook prints how
long the reference took, per Mamba-2 layer the spread of the decay
``a_t`` over the first batch's tokens and heads, and per expert layer the
rows each held expert receives against the buffer's bound, all as the
float32 reference computes them.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models import moe, transformer as tfm
from horovod_tpu.telemetry import scopes
from perfbench import kernel_cost_ssm
from perfbench.adapters.lm import _optimizer as lm_optimizer
from perfbench.adapters.moe_lm import _defined, zipf_tokens
from perfbench.cell import Cell, on_first_device, seeded
from perfbench.reference import ssm_moe_lm as reference

MIX_KEYS = {"seq_len", "batch_per_chip", "mesh_axes", "optimizer",
            "attention", "remat", "shard_optimizer", "packed",
            "token_distribution"}
# The pattern's letters as the program's layer types.
KINDS = {"M": "mamba2", "*": "attention", "E": "mlp"}
MAMBA2, ATTENTION, EXPERTS = KINDS["M"], KINDS["*"], KINDS["E"]
# The checked leaves whose gradient is read from the momentum slot.
FROM_MOMENTUM = ("ssm_a_log_last",)
# The out projections that rescale_prenorm_residual shrinks.
OUT_PROJECTIONS = ("ssm_w_out", "wo", "w_latent_out", "w_shared_down")


def layer_types(config: dict):
    """The layers the model runs: the head of the published pattern."""
    pattern = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    return tuple(KINDS[letter] for letter in pattern)


def mtp_layer_types(config: dict):
    return tuple(KINDS[letter]
                 for letter in config["mtp_hybrid_override_pattern"])


def matmul_parameters(config: dict) -> dict:
    """Parameters that are matmul operands **for one token**, by where
    they sit: a Mamba-2 mixer (``W_in`` to z, xBC and dt; ``W_out``), an
    attention layer (``Wq``, ``Wo`` of ``d x d``; ``Wk``, ``Wv`` of ``d x
    kv_heads head_dim``), an expert layer (router over the published
    count, the two latent projections, the shared expert, and the routed
    experts a token passes through **on this chip**: of its
    ``num_experts_per_tok``, the expected ``n_routed_experts /
    router_width``), the untied head, and the prediction module's
    combining matrix.  The convolution, the norms, ``A_log``, ``dt_bias``
    and ``D`` multiply no matrix."""
    d = config["hidden_size"]
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    conv = inner + 2 * config["n_groups"] * config["ssm_state_size"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    latent = config["moe_latent_size"]
    here = (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["router_width"])
    return {
        MAMBA2: d * (inner + conv + config["mamba_num_heads"]) + inner * d,
        ATTENTION: 2 * d * d + 2 * d * kv,
        EXPERTS: (d * config["router_width"] + 2 * d * latent
                  + 2 * d * config["moe_shared_expert_intermediate_size"]
                  + here * 2 * latent * config["moe_intermediate_size"]),
        "head": d * config["vocab_size"],
        "mtp_combine": 2 * d * d}


def train_flops(config: dict, seq_len: int, global_batch: int) -> float:
    """Model FLOPs of one training step, PaLM appendix B with **active**
    parameters: ``6 * tokens * N`` over every matmul parameter a token
    uses (:func:`matmul_parameters`; the head twice, once for the
    prediction module, whose layers and combining matrix count too; the
    embedding look-ups are not matmuls), plus causal attention ``6 * B *
    T^2 * d`` per attention layer, the module's included (the ``lm``
    kind's convention).  Never recomputation; and not the state-space
    recurrence, whose recurrent form is 1% of this
    (``kernel_cost_ssm``)."""
    n = matmul_parameters(config)
    kinds = layer_types(config) + mtp_layer_types(config)
    tokens = global_batch * seq_len
    weights = (sum(n[kind] for kind in kinds) + 2 * n["head"]
               + n["mtp_combine"])
    return (6.0 * weights * tokens
            + 6.0 * global_batch * seq_len * seq_len
            * config["hidden_size"] * kinds.count(ATTENTION))


def model_config(config: dict, seq_len: int) -> tfm.TransformerConfig:
    """The published keys as the program's config."""
    if (config["mlp_hidden_act"] != "relu2"
            or config["mamba_hidden_act"] != "silu"
            or not config["use_conv_bias"] or not config["norm_topk_prob"]
            or (config["n_group"], config["topk_group"]) != (1, 1)
            or config["n_shared_experts"] != 1
            or config["num_nextn_predict_layers"] != 1
            or config["tie_word_embeddings"]
            or any(config[k] for k in ("attention_bias", "mlp_bias",
                                       "use_bias", "mamba_proj_bias"))
            or config["sliding_window"] is not None
            or config["num_attention_heads"] * config["head_dim"]
            != config["hidden_size"]):
        raise NotImplementedError(
            "ssm_moe_lm adapter: relu2 experts, one shared expert, "
            "renormalised top-k without group limiting, a convolution "
            "with bias and no other, one prediction module, an untied "
            "head and heads x head_dim = hidden_size are what the program "
            "runs for this kind")
    return tfm.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        n_layers=config["num_hidden_layers"], d_ff=0,
        max_seq=max(seq_len, config["max_position_embeddings"]),
        dtype=jnp.bfloat16, positions="none",
        norm_eps=config["layer_norm_epsilon"], tie_embeddings=False,
        mlp="relu2", n_experts=config["router_width"],
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_latent=config["moe_latent_size"],
        d_shared=config["moe_shared_expert_intermediate_size"],
        routed_scale=float(config["routed_scaling_factor"]),
        experts_held=config["n_routed_experts"],
        experts_held_from=config["experts_held_from"],
        layer_types=layer_types(config),
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssm_state=config["ssm_state_size"], ssm_groups=config["n_groups"],
        ssm_conv_kernel=config["conv_kernel"],
        ssm_chunk=config["chunk_size"],
        mtp_layer_types=mtp_layer_types(config),
        mtp_loss_coef=config["mtp_loss_coef"])


def reference_dims(cfg: tfm.TransformerConfig) -> dict:
    return {"n_heads": cfg.n_heads, "kv_heads": cfg.kv_heads,
            "ssm_heads": cfg.ssm_heads, "ssm_head_dim": cfg.ssm_head_dim,
            "ssm_state": cfg.ssm_state, "ssm_groups": cfg.ssm_groups,
            "eps": cfg.norm_eps, "top_k": cfg.experts_per_token,
            "routed_scale": cfg.routed_scale,
            "held_from": cfg.experts_held_from}


def draw_decay(key, heads: int, config: dict):
    """``(A_log, dt_bias)`` of one Mamba-2 layer from the configuration:
    the rate ``A`` uniform in ``a_init_range`` and the step log-uniform
    in [``time_step_min``, ``time_step_max``] floored at
    ``time_step_floor``, stored as ``log A`` and ``softplus^-1(dt)``."""
    k_a, k_dt = jax.random.split(key)
    a = jax.random.uniform(k_a, (heads,), jnp.float32,
                           *config["a_init_range"])
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        k_dt, (heads,), jnp.float32, math.log(config["time_step_min"]),
        math.log(config["time_step_max"]))), config["time_step_floor"])
    return jnp.log(a), dt + jnp.log(-jnp.expm1(-dt))


def build(config: dict, mix: dict, mesh) -> Cell:
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"ssm_moe_lm adapter: unknown mix keys "
                         f"{sorted(unknown)}")
    draw = mix["token_distribution"]
    if mix["shard_optimizer"]:
        raise NotImplementedError(
            "shard_optimizer=true: this adapter's check reads a gradient "
            "from the whole momentum slot (checked)")
    if draw["name"] != "zipf":
        raise ValueError(f"token_distribution {draw['name']!r}: the "
                         f"ssm_moe_lm adapter knows 'zipf'")
    data_axis = mix["mesh_axes"][0]
    seq_len, per_chip = mix["seq_len"], mix["batch_per_chip"]
    global_batch = per_chip * int(mesh.shape[data_axis])
    cfg = model_config(config, seq_len)
    kinds, mtp_kinds = cfg.layer_types, cfg.mtp_layer_types
    mamba = [i for i, kind in enumerate(kinds) if kind == MAMBA2]
    expert_layers = ([str(i) for i, kind in enumerate(kinds)
                      if kind == EXPERTS]
                     + [f"mtp_{i}" for i, kind in enumerate(mtp_kinds)
                        if kind == EXPERTS])
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
    shrink = ((2 * config["published"]["num_hidden_layers"]) ** -0.5
              if config["rescale_prenorm_residual"] else 1.0)

    def make_arrays(key, pool):
        k_params, k_data = jax.random.split(key)
        params = tfm.init_params(k_params, cfg)
        # The configuration's embedding scale ("departures").
        params["embed"] = config["embedding_init_std"] * jax.random.normal(
            jax.random.fold_in(k_params, 1), params["embed"].shape,
            jnp.float32)
        for i in mamba:
            a_log, dt_bias = draw_decay(
                jax.random.fold_in(k_params, 100 + i), cfg.ssm_heads, config)
            params["layers"][i].update(ssm_a_log=a_log, ssm_dt_bias=dt_bias)
        for layer in params["layers"] + params["mtp"]["layers"]:
            for name in OUT_PROJECTIONS:
                if name in layer:
                    layer[name] = layer[name] * shrink
        toks = zipf_tokens(k_data, (pool, global_batch, seq_len + 1),
                           cfg.vocab_size, draw["exponent"])
        batches = [(toks[i, :, :-1], toks[i, :, 1:]) for i in range(pool)]
        return (params, init_opt(params)), batches

    make, state_shapes, batch_shapes = seeded(
        make_arrays, (named(specs), named(opt_specs)),
        (data_sharding, data_sharding))

    ref = jax.jit(functools.partial(
        reference.loss_and_tail_grads, dims=reference_dims(cfg),
        layer_types=kinds, mtp_layer_types=mtp_kinds,
        mtp_coef=cfg.mtp_loss_coef))
    bound = moe.rows_bound(per_chip * seq_len, cfg.experts_per_token,
                           cfg.held_experts)
    expected = (global_batch * seq_len * cfg.experts_per_token
                / cfg.n_experts)

    def run_reference(state, batch):
        # On one device, reading the replicated weights in place.
        params, (tokens, labels) = on_first_device((state[0], batch), mesh)
        start = time.perf_counter()
        loss, grads, stats = jax.block_until_ready(
            ref(params, tokens, labels))
        print(f"reference: float32 at precision highest, the recurrence "
              f"token by token over {tokens.size} tokens, the held "
              f"experts one after another: "
              f"{time.perf_counter() - start:.1f} s (compile included "
              f"where the cache did not hold it)", flush=True)
        for i, row in zip(mamba, np.asarray(stats["decay"])):
            print(f"decay, first batch, layer {i} (float32 reference): "
                  f"a_t 1% {row[0]:.4f}, median {row[1]:.4f}, 99% "
                  f"{row[2]:.5f}", flush=True)
        for name, rows in zip(expert_layers, np.asarray(stats["rows"])):
            # The reference's own routing, not the program's: what the
            # program does with a full buffer is a tier-1 test
            # (tests/test_ssm_moe_lm.py, the adversarial router).
            print(f"held experts, first batch, layer {name} (float32 "
                  f"reference routing): rows per held expert min "
                  f"{rows.min()} / mean {rows.mean():.1f} / max "
                  f"{rows.max()} against the expected {expected:.0f} "
                  f"(tokens x {cfg.experts_per_token} / {cfg.n_experts}); "
                  f"{rows.sum()} rows for a buffer of {bound} = tokens x "
                  f"min({cfg.experts_per_token}, {cfg.held_experts}), "
                  f"which no routing can exceed: dropped 0 by the bound",
                  flush=True)
        return loss, grads

    paths = reference.leaf_paths(kinds)
    grad_per_delta = -1.0 / mix["optimizer"]["learning_rate"]

    def checked(state):
        """The leaves check (b) recovers a gradient from.  A parameter's
        change is -lr x its gradient.  ``A_log``'s cannot be read that
        way (an update of 4e-7 beside values of up to 2.8 in float32: the
        rounding of ``new - old`` alone reads 0.12-0.22): it is read from
        the momentum slot, which after one step from zero holds the
        gradient itself, rounded to bf16 once (2e-3), and is handed over
        divided by ``grad_per_delta`` so that the harness's product gives
        it back."""
        params, opt_state = state
        momentum = next(s.trace for s in opt_state if hasattr(s, "trace"))
        return {name: (reference.leaf(momentum, paths[name]).astype(
                           jnp.float32) / grad_per_delta
                       if name in FROM_MOMENTUM
                       else reference.leaf(params, paths[name]))
                for name in reference.CHECKED}

    # Named so that the trace books them as kernels.  No roofline of
    # theirs is read in this kind's cells: the accepted flash_* and
    # moe_experts_roofline entries list their cells and a model_config PR
    # cannot append to them (PERF.md section 7), and the grouped matmuls
    # see a few hundred rows a group (section 4).
    kernels = {}
    if mix["attention"] in ("flash", "ring_flash"):
        kernels["flash"] = dict(match=_defined(
            scopes.FLASH_FWD, scopes.FLASH_BWD_DQ, scopes.FLASH_BWD_DKV))
    kernels["moe_gmm"] = dict(
        match=_defined(scopes.MOE_GMM, scopes.MOE_GMM_NT, scopes.MOE_TGMM))
    # The recurrence is jax.numpy that XLA compiles, not a Pallas kernel:
    # no trace event to match (its time is read by scope,
    # perfbench/ssm_reduce.py).  The entry carries its roofline's
    # numerator; a kernel PR adds its name here.
    kernels["ssm_scan"] = dict(
        kernel_cost_ssm.state_space_train(
            per_chip * seq_len, cfg.ssm_heads, cfg.ssm_head_dim,
            cfg.ssm_state, cfg.ssm_groups, kinds.count(MAMBA2),
            recompute=mix["remat"] == "full"),
        match=[])
    return Cell(
        step=step, state_shapes=state_shapes, batch_shapes=batch_shapes,
        make=make,
        flops_per_step=train_flops(config, seq_len, global_batch),
        item="tokens", items_per_step=global_batch * seq_len,
        grad_per_delta=grad_per_delta, checked=checked,
        reference=run_reference, kernels=kernels)
