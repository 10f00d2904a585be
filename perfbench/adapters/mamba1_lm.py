"""Adapter of kind ``mamba1_lm``: a Jamba-style decoder (Mamba-1 state-space
mixers with one multi-query attention layer a period, every layer followed
by the dense SwiGLU MLP, the head tied to the embedding) trained through
``horovod_tpu.models.transformer.make_train_step``, the step builder every
LM kind uses.

The configuration file holds the published sizes under their published
(Hugging Face ``jamba``) keys; layer ``i`` is attention where ``i %
attn_layer_period == attn_layer_offset`` and Mamba otherwise, and the
model runs the first ``num_hidden_layers`` of them.  The traffic mix holds
everything about the job.  All of it reaches the step builder as data.

What this kind asks of "Adding things" (``perfbench/README.md``), as new
files only: this adapter with ``build`` and :func:`train_flops`;
``reference/mamba1_lm.py``; ``kernel_cost_mamba1.py``;
``mamba1_reduce.py`` and the six readers
``layer_metrics/{mamba1_*,mqa_attn_ms_per_step}.py``;
``tests/test_{reference,flops,harness,chip_compile}_mamba1_lm.py``; and
``controls_mamba1_lm.py``, which puts each control of the reference in the
program's place under the harness's own comparison.

At set-up, outside the window, :func:`build`'s reference hook prints how
long the reference took.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models import transformer as tfm
from horovod_tpu.telemetry import scopes
from perfbench import kernel_cost, kernel_cost_mamba1
from perfbench.adapters.lm import _optimizer as lm_optimizer
from perfbench.adapters.moe_lm import _defined, zipf_tokens
from perfbench.cell import Cell, on_first_device, seeded
from perfbench.reference import mamba1_lm as reference

MIX_KEYS = {"seq_len", "batch_per_chip", "mesh_axes", "optimizer",
            "attention", "remat", "shard_optimizer", "packed",
            "token_distribution"}
MAMBA, ATTENTION = reference.MAMBA, reference.ATTENTION
# The checked leaves whose gradient is read from the momentum slot.
FROM_MOMENTUM = ("mamba_a_log_last", "mamba_d_last", "mamba_w_dt_last",
                 "wk_attn")


def layer_types(config: dict):
    """The layers the model runs: attention where the published period
    and offset say, Mamba everywhere else."""
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return tuple(ATTENTION if i % period == offset else MAMBA
                 for i in range(config["num_hidden_layers"]))


def inner_width(config: dict) -> int:
    return config["mamba_expand"] * config["hidden_size"]


def matmul_parameters(config: dict) -> dict:
    """Parameters that are matmul operands for one token, by where they
    sit: a Mamba-1 mixer (in_proj to ``xs`` and ``z``, x_proj, dt_proj,
    out_proj), the attention mixer (``Wq``, ``Wo`` of ``d x d``; ``Wk``,
    ``Wv`` of ``d x kv_heads head_dim``), a layer's SwiGLU MLP, and the
    tied head.  The convolution, the norms, ``A_log``, ``dt_bias`` and
    ``D`` multiply no matrix."""
    d, inner = config["hidden_size"], inner_width(config)
    rank, state = config["mamba_dt_rank"], config["mamba_d_state"]
    kv = (config["num_key_value_heads"]
          * (d // config["num_attention_heads"]))
    return {
        MAMBA: (d * 2 * inner + inner * (rank + 2 * state) + rank * inner
                + inner * d),
        ATTENTION: 2 * d * d + 2 * d * kv,
        "mlp": 3 * d * config["intermediate_size"],
        "head": d * config["vocab_size"]}


def train_flops(config: dict, seq_len: int, global_batch: int) -> float:
    """Model FLOPs of one training step, PaLM appendix B: ``6 * tokens *
    N`` over every matmul parameter a token uses
    (:func:`matmul_parameters`; the embedding look-up is no matmul, the
    tied head is), plus causal attention ``6 * B * T^2 * d`` per
    attention layer (the ``lm`` kind's convention), plus the selective
    scan in its recurrent form (``kernel_cost_mamba1``: 12 x channels x
    state a token and Mamba layer).  Never recomputation."""
    n = matmul_parameters(config)
    kinds = layer_types(config)
    tokens = global_batch * seq_len
    weights = (sum(n[kind] + n["mlp"] for kind in kinds) + n["head"])
    scan = kernel_cost_mamba1.selective_scan_train(
        tokens, inner_width(config), config["mamba_d_state"],
        kinds.count(MAMBA), recompute=False)["flops"]
    return (6.0 * weights * tokens
            + 6.0 * global_batch * seq_len * seq_len
            * config["hidden_size"] * kinds.count(ATTENTION) + scan)


def model_config(config: dict, seq_len: int) -> tfm.TransformerConfig:
    """The published keys as the program's config."""
    if (config["hidden_act"] != "silu" or config["num_experts"] != 1
            or not config["mamba_conv_bias"] or config["mamba_proj_bias"]
            or not config["tie_word_embeddings"]
            or config["sliding_window"] is not None):
        raise NotImplementedError(
            "mamba1_lm adapter: SwiGLU without experts, a convolution "
            "with bias and projections without, a tied head and no "
            "sliding window are what the program runs for this kind")
    return tfm.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        max_seq=max(seq_len, config["max_position_embeddings"]),
        dtype=jnp.bfloat16, positions="none",
        norm_eps=config["rms_norm_eps"], tie_embeddings=True, mlp="swiglu",
        layer_types=layer_types(config), mamba_inner=inner_width(config),
        mamba_state=config["mamba_d_state"],
        mamba_dt_rank=config["mamba_dt_rank"],
        mamba_conv_kernel=config["mamba_d_conv"])


def reference_dims(cfg: tfm.TransformerConfig) -> dict:
    return {"n_heads": cfg.n_heads, "kv_heads": cfg.kv_heads,
            "state": cfg.mamba_state, "dt_rank": cfg.mamba_dt_rank,
            "eps": cfg.norm_eps}


def build(config: dict, mix: dict, mesh) -> Cell:
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"mamba1_lm adapter: unknown mix keys "
                         f"{sorted(unknown)}")
    draw = mix["token_distribution"]
    if mix["shard_optimizer"]:
        raise NotImplementedError(
            "shard_optimizer=true: this adapter's check reads a gradient "
            "from the whole momentum slot (checked)")
    if draw["name"] != "zipf":
        raise ValueError(f"token_distribution {draw['name']!r}: the "
                         f"mamba1_lm adapter knows 'zipf'")
    data_axis = mix["mesh_axes"][0]
    seq_len, per_chip = mix["seq_len"], mix["batch_per_chip"]
    global_batch = per_chip * int(mesh.shape[data_axis])
    cfg = model_config(config, seq_len)
    kinds = cfg.layer_types
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

    data_sharding = NamedSharding(mesh, P(data_axis))

    def make_arrays(key, pool):
        k_params, k_data = jax.random.split(key)
        params = tfm.init_params(k_params, cfg)
        # The configuration's embedding scale ("assumed").
        params["embed"] = config["embedding_init_std"] * jax.random.normal(
            jax.random.fold_in(k_params, 1), params["embed"].shape,
            jnp.float32)
        toks = zipf_tokens(k_data, (pool, global_batch, seq_len + 1),
                           cfg.vocab_size, draw["exponent"])
        batches = [(toks[i, :, :-1], toks[i, :, 1:]) for i in range(pool)]
        return (params, optimizer.init(params)), batches

    make, state_shapes, batch_shapes = seeded(
        make_arrays, (named(specs), named(opt_specs)),
        (data_sharding, data_sharding))

    ref = jax.jit(functools.partial(
        reference.loss_and_tail_grads, dims=reference_dims(cfg),
        layer_types=kinds))

    def run_reference(state, batch):
        # On one device, reading the replicated weights in place.
        params, (tokens, labels) = on_first_device((state[0], batch), mesh)
        start = time.perf_counter()
        loss, grads, _ = jax.block_until_ready(ref(params, tokens, labels))
        print(f"reference: float32 at precision highest, the selective "
              f"scan token by token over {tokens.size} tokens: "
              f"{time.perf_counter() - start:.1f} s (compile included "
              f"where the cache did not hold it)", flush=True)
        return loss, grads

    paths = reference.leaf_paths(kinds)
    grad_per_delta = -1.0 / mix["optimizer"]["learning_rate"]

    def checked(state):
        """The leaves check (b) recovers a gradient from.  A parameter's
        change is -lr x its gradient.  ``A_log``'s and ``D``'s cannot be
        read that way (updates of 1e-7 beside values of 1 to 2.8 in
        float32: ``D`` reads 0.10 from the change and 0.039 from the
        slot, PERF.md, PR 43): they are read from the momentum slot, which
        after one step from zero holds the gradient itself, rounded to
        bf16 once, and are handed over divided by ``grad_per_delta`` so
        that the harness's product gives it back (``ssm_moe_lm``'s
        way)."""
        params, opt_state = state
        momentum = next(s.trace for s in opt_state if hasattr(s, "trace"))
        return {name: (reference.leaf(momentum, paths[name]).astype(
                           jnp.float32) / grad_per_delta
                       if name in FROM_MOMENTUM
                       else reference.leaf(params, paths[name]))
                for name in reference.CHECKED}

    # Every kernel the step holds, named so that the trace books it as a
    # kernel and xla_ms_per_step means what its name says.  A step of one
    # device holds no conditionals, so XLA numbers the instances of a name
    # from a small offset (moe_lm._defined's 64 is ample for 13 layers x
    # 3 passes; perfbench/tests/test_chip_compile_mamba1_lm.py holds every
    # kernel instruction of the step to this list).
    kernels = {}
    if mix["attention"] in ("flash", "ring_flash"):
        flash = kernel_cost.causal_attention_train(
            per_chip, cfg.n_heads, seq_len, cfg.head_dim)
        kernels["flash"] = dict(
            {k: v * kinds.count(ATTENTION) for k, v in flash.items()},
            match=_defined(scopes.FLASH_FWD, scopes.FLASH_BWD_DQ,
                           scopes.FLASH_BWD_DKV))
    kernels["mamba_scan"] = dict(
        kernel_cost_mamba1.selective_scan_train(
            per_chip * seq_len, cfg.mamba_inner, cfg.mamba_state,
            kinds.count(MAMBA), recompute=mix["remat"] == "full"),
        match=_defined("mamba_scan_fwd", "mamba_scan_bwd"))
    # Booked by the part it runs under (mamba1_reduce): named here so that
    # xla_ms_per_step does not count it as XLA's.
    kernels["short_conv"] = dict(
        flops=0.0, bytes=0.0,
        match=_defined(scopes.SHORT_CONV_FWD, scopes.SHORT_CONV_BWD))
    return Cell(
        step=step, state_shapes=state_shapes, batch_shapes=batch_shapes,
        make=make,
        flops_per_step=train_flops(config, seq_len, global_batch),
        item="tokens", items_per_step=global_batch * seq_len,
        grad_per_delta=grad_per_delta, checked=checked,
        reference=run_reference, kernels=kernels)
