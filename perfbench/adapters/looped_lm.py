"""Adapter of kind ``looped_lm``: an Ouro-style decoder (a stack of
sandwich-normed rotary SwiGLU layers run ``total_ut_steps`` times on the
same weights, the final norm carried from pass to pass, the untied head and
an exit gate read after every pass, the entropy-regularised expected-exit
loss over the readouts) trained through
``horovod_tpu.models.transformer.make_train_step``, the step builder every
LM kind uses.

The configuration file holds the published sizes under their published
(Hugging Face ``ouro``) keys; the traffic mix holds everything about the
job.  All of it reaches the step builder as data.

What this kind asks of "Adding things" (``perfbench/README.md``), as new
files only: this adapter with ``build`` and :func:`train_flops`;
``reference/looped_lm.py``; ``kernel_cost_loop.py``; ``loop_reduce.py``
and the nine readers ``layer_metrics/loop_*.py``;
``tests/test_{reference,flops,harness,chip_compile}_looped_lm.py``; and
``controls_looped_lm.py``, which puts each control of the reference in the
program's place under the harness's own comparison.

At set-up, outside the window, :func:`build`'s reference hook prints how
long the reference took and the means over tokens of ``p_t`` and ``l_t`` a
pass.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models import transformer as tfm
from horovod_tpu.telemetry import scopes
from perfbench import kernel_cost_loop
from perfbench.adapters.lm import _optimizer as lm_optimizer
from perfbench.adapters.moe_lm import _defined, zipf_tokens
from perfbench.cell import Cell, on_first_device, seeded
from perfbench.reference import looped_lm as reference

MIX_KEYS = {"seq_len", "batch_per_chip", "mesh_axes", "optimizer",
            "attention", "remat", "shard_optimizer", "packed",
            "token_distribution"}


def matmul_parameters(config: dict) -> dict:
    """Parameters that are matmul operands for one token, by where they
    sit: a layer (``Wq``, ``Wk``, ``Wv``, ``Wo`` of ``d x d``; the SwiGLU
    MLP's three of ``d x f``), the untied head, the gate's ``d x 1``.
    The norms' scales and the gate's bias multiply no matrix."""
    d, f = config["hidden_size"], config["intermediate_size"]
    return {"layer": 4 * d * d + 3 * d * f,
            "head": d * config["vocab_size"], "gate": d}


def train_flops(config: dict, seq_len: int, global_batch: int) -> float:
    """Model FLOPs of one training step, PaLM appendix B, **every matmul
    parameter once a use**: a layer's weights are used ``total_ut_steps``
    times a token, and so are the head's and the gate's (one readout a
    pass): ``loops x 6 x tokens x (N x layer + head + gate)``, plus causal
    attention ``6 x B x T^2 x d`` a layer and pass (the ``lm`` kind's
    convention).  The embedding look-up is no matmul; recomputation is
    never counted."""
    n = matmul_parameters(config)
    layers, loops = config["num_hidden_layers"], config["total_ut_steps"]
    tokens = global_batch * seq_len
    return loops * (
        6.0 * tokens * (layers * n["layer"] + n["head"] + n["gate"])
        + 6.0 * global_batch * seq_len * seq_len * config["hidden_size"]
        * layers)


def model_config(config: dict, seq_len: int) -> tfm.TransformerConfig:
    """The published keys as the program's config."""
    if (config["num_key_value_heads"] != config["num_attention_heads"]
            or config["hidden_act"] != "silu"
            or config["rope_scaling"] is not None
            or config["use_sliding_window"]
            or config["tie_word_embeddings"]
            or set(config["layer_types"]) != {"full_attention"}
            or config["head_dim"] * config["num_attention_heads"]
            != config["hidden_size"]):
        raise NotImplementedError(
            "looped_lm adapter: full multi-head attention whose heads "
            "make up the hidden size, silu, no rope scaling, no sliding "
            "window, every layer full and an untied head are what the "
            "program runs for this kind")
    return tfm.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        max_seq=max(seq_len, config["max_position_embeddings"]),
        dtype=jnp.bfloat16, positions="rope",
        rope_theta=config["rope_theta"], norm_eps=config["rms_norm_eps"],
        tie_embeddings=False, mlp="swiglu", post_norm=True,
        loops=config["total_ut_steps"],
        exit_entropy_coef=config["exit_entropy_coef"])


def reference_dims(cfg: tfm.TransformerConfig) -> dict:
    return {"n_heads": cfg.n_heads, "eps": cfg.norm_eps,
            "theta": cfg.rope_theta, "loops": cfg.loops,
            "beta": cfg.exit_entropy_coef}


def build(config: dict, mix: dict, mesh) -> Cell:
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"looped_lm adapter: unknown mix keys "
                         f"{sorted(unknown)}")
    draw = mix["token_distribution"]
    if mix["shard_optimizer"]:
        raise NotImplementedError(
            "shard_optimizer=true: this adapter's check reads a gradient "
            "from whole leaves (checked)")
    if draw["name"] != "zipf":
        raise ValueError(f"token_distribution {draw['name']!r}: the "
                         f"looped_lm adapter knows 'zipf'")
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

    data_sharding = NamedSharding(mesh, P(data_axis))

    def make_arrays(key, pool):
        k_params, k_data = jax.random.split(key)
        # Matrices N(0, 1 / fan_in), the embedding N(0, 0.02^2), every
        # norm's scale 1, the gate's weight N(0, 1 / d) and its bias 0
        # (the configuration's "assumed"): the gate's pre-activation has a
        # standard deviation near 1 around a mean of its own a seed, and
        # every pass carries weight in the check (p_1's mean over tokens
        # read 0.23-0.78 over nineteen seeds, p_4's 0.014-0.43: PERF.md).
        params = tfm.init_params(k_params, cfg)
        toks = zipf_tokens(k_data, (pool, global_batch, seq_len + 1),
                           cfg.vocab_size, draw["exponent"])
        batches = [(toks[i, :, :-1], toks[i, :, 1:]) for i in range(pool)]
        return (params, optimizer.init(params)), batches

    make, state_shapes, batch_shapes = seeded(
        make_arrays, (named(specs), named(opt_specs)),
        (data_sharding, data_sharding))

    ref = jax.jit(functools.partial(reference.loss_and_grads,
                                    dims=reference_dims(cfg)))

    def run_reference(state, batch):
        # On one device, reading the replicated weights in place.
        params, (tokens, labels) = on_first_device((state[0], batch), mesh)
        start = time.perf_counter()
        loss, grads, stats = jax.block_until_ready(
            ref(params, tokens, labels))
        means = lambda name: ", ".join(f"{float(v):.4f}"
                                       for v in stats[name])
        print(f"reference: float32 at precision highest, {cfg.loops} "
              f"passes over {cfg.n_layers} layers on {tokens.size} tokens: "
              f"{time.perf_counter() - start:.1f} s (compile included where "
              f"the cache did not hold it); mean over tokens a pass of the "
              f"exit probability p_t {means('p_mean')} and of the "
              f"cross-entropy l_t {means('l_mean')}", flush=True)
        return loss, grads

    paths = reference.leaf_paths(cfg.n_layers)

    def checked(state):
        return {name: reference.leaf(state[0], paths[name])
                for name in reference.CHECKED}

    # The step's only Mosaic kernels are the flash forward, dQ and dK+dV,
    # once a layer and pass and the forward once more recomputed: XLA
    # numbers the instances of all three names from one counter, four a
    # layer and pass (0-223 at 14 layers) from a small offset
    # (perfbench/tests/test_chip_compile_looped_lm.py holds every kernel
    # instruction of the step to this list).
    kernels = {}
    if mix["attention"] in ("flash", "ring_flash"):
        kernels["flash"] = dict(
            kernel_cost_loop.looped_causal_attention_train(
                per_chip, cfg.n_heads, seq_len, cfg.head_dim, cfg.n_layers,
                cfg.loops),
            match=_defined(scopes.FLASH_FWD, scopes.FLASH_BWD_DQ,
                           scopes.FLASH_BWD_DKV,
                           instances=4 * cfg.n_layers * cfg.loops + 64))
    return Cell(
        step=step, state_shapes=state_shapes, batch_shapes=batch_shapes,
        make=make,
        flops_per_step=train_flops(config, seq_len, global_batch),
        item="tokens", items_per_step=global_batch * seq_len,
        grad_per_delta=-1.0 / mix["optimizer"]["learning_rate"],
        checked=checked, reference=run_reference, kernels=kernels)
