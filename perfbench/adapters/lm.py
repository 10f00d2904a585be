"""Adapter of kind ``lm``: a GPT-2-style decoder trained through
``horovod_tpu.models.transformer.make_train_step``.

The configuration file holds the published sizes under their published
(Hugging Face GPT-2) keys; the traffic mix holds everything about the job:
sequence length, batch per chip, mesh axes, optimizer, ``attention``,
``remat``, ``shard_optimizer``, ``packed``.  All of it reaches the step
builder as data.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models import transformer as tfm
from perfbench import kernel_cost
from perfbench.cell import Cell, on_first_device, seeded
from perfbench.reference import lm as reference

MIX_KEYS = {"seq_len", "batch_per_chip", "mesh_axes", "optimizer",
            "attention", "remat", "shard_optimizer", "packed"}


def train_flops(config: dict, seq_len: int, global_batch: int) -> float:
    """Model FLOPs of one training step (copy of
    ``horovod_tpu.benchmark.lm_train_flops``, PaLM appendix B):
    ``6 * N * tokens`` over every matmul parameter (2 forward + 4 backward
    per parameter and token; the embedding look-up is not a matmul, the
    tied head is) plus causal attention ``6 * B * T^2 * d * L`` (QK^T and
    PV are ``4 * B * T^2 * d`` per layer forward, three times that
    trained, halved by causality).  Recomputation is never counted."""
    d, f, v = config["n_embd"], config["n_inner"], config["vocab_size"]
    layers = config["n_layer"]
    n_matmul = layers * (4 * d * d + 2 * d * f) + d * v
    tokens = global_batch * seq_len
    return (6.0 * n_matmul * tokens
            + 6.0 * global_batch * seq_len * seq_len * d * layers)


def _optimizer(spec: dict):
    if spec["name"] != "sgd":
        raise ValueError(f"optimizer {spec['name']!r}: the lm adapter "
                         f"knows 'sgd'")
    acc = spec.get("accumulator_dtype", "float32")
    return optax.sgd(spec["learning_rate"], momentum=spec["momentum"],
                     accumulator_dtype=None if acc == "float32"
                     else jnp.dtype(acc).type)


def build(config: dict, mix: dict, mesh) -> Cell:
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"lm adapter: unknown mix keys {sorted(unknown)}")
    if mix["packed"]:
        raise NotImplementedError(
            "packed=true needs a document-length generator in this "
            "adapter (PERF.md, open row gpt67_t8192_packed)")
    data_axis = mix["mesh_axes"][0]
    seq_len, per_chip = mix["seq_len"], mix["batch_per_chip"]
    global_batch = per_chip * int(mesh.shape[data_axis])
    cfg = tfm.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"],
        max_seq=max(seq_len, config["n_positions"]), dtype=jnp.bfloat16)
    optimizer = _optimizer(mix["optimizer"])
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
        toks = jax.random.randint(
            k_data, (pool, global_batch, seq_len + 1), 0, cfg.vocab_size,
            jnp.int32)
        batches = [(toks[i, :, :-1], toks[i, :, 1:]) for i in range(pool)]
        return (params, init_opt(params)), batches

    make, state_shapes, batch_shapes = seeded(
        make_arrays, (named(specs), named(opt_specs)),
        (data_sharding, data_sharding))

    ref = jax.jit(functools.partial(reference.loss_and_tail_grads,
                                    n_heads=cfg.n_heads))

    def run_reference(state, batch):
        # On one device, reading the replicated weights in place.
        params, (tokens, labels) = on_first_device((state[0], batch), mesh)
        return ref(params, tokens, labels)

    def checked(state):
        params = state[0]
        return {"ln_f_scale": params["ln_f_scale"],
                "w2_last": params["layers"][-1]["w2"]}

    flash = kernel_cost.causal_attention_train(
        per_chip, cfg.n_heads, seq_len, cfg.head_dim)
    flash = {k: v * cfg.n_layers for k, v in flash.items()}
    uses_kernel = mix["attention"] in ("flash", "ring_flash")
    return Cell(
        step=step, state_shapes=state_shapes, batch_shapes=batch_shapes,
        make=make,
        flops_per_step=train_flops(config, seq_len, global_batch),
        item="tokens", items_per_step=global_batch * seq_len,
        grad_per_delta=-1.0 / mix["optimizer"]["learning_rate"],
        checked=checked, reference=run_reference,
        # The step's only Mosaic kernels are the flash forward, dQ and
        # dK/dV; their instructions carry no kernel name (PERF.md, list
        # for the tracing issue), so the custom-call target is the handle.
        kernels={"flash": dict(flash, match=[
            'custom_call_target="tpu_custom_call"'])} if uses_kernel else {})
