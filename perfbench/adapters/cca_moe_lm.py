"""Adapter of kind ``cca_moe_lm``: a ZAYA1-style decoder (every layer
compressed convolutional attention, then one SwiGLU expert a token out of
sixteen and a skip under an MLP router whose state is handed from layer to
layer; both sub-layers joined to the stream by a scaled residual merge; the
head tied to the embedding) trained through
``horovod_tpu.models.transformer.make_train_step``, the step builder every
LM kind uses.

The configuration file holds the published sizes under their published
(Hugging Face ``zaya``) keys.  ``num_experts`` is what this chip holds,
from ``experts_held_from`` on; ``published.num_experts`` is the published
count, which the router scores beside the skip.  The traffic mix holds
everything about the job.  All of it reaches the step builder as data.

What this kind asks of "Adding things" (``perfbench/README.md``), as new
files only: this adapter with ``build`` and :func:`train_flops`;
``reference/cca_moe_lm.py``; ``kernel_cost_cca.py``; ``cca_reduce.py`` and
the ten readers ``layer_metrics/{cca,zaya}_*.py``;
``controls_cca_moe_lm.py``;
``tests/test_{reference,flops,harness,chip_compile}_cca_moe_lm.py``.

At set-up, outside the window, :func:`build`'s ``make`` chooses which
experts of each layer this chip holds, a uniform router's rows of the first
batch's first sequence over the whole stack (the configuration's
``assumed``, ``expert_placement``): one program of one layer, run layer
after layer, after the weights' program.  The tied head is flat from the
start (``logit_scale``; ``assumed``, ``tied_head_scale``): the step's time
follows the rows the held experts receive, and a head that asks nothing of
the stack leaves them where the placement put them (PERF.md, PR 53).
:func:`build`'s reference hook prints how long
the reference took and, a layer, the rows each held expert receives and the
share of the tokens that skip.
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
from perfbench import kernel_cost_cca
from perfbench.adapters.lm import _optimizer as lm_optimizer
from perfbench.adapters.moe_lm import _defined, zipf_tokens
from perfbench.cell import Cell, on_first_device, seeded
from perfbench.reference import cca_moe_lm as reference

# Leaves of ``reference.CHECKED`` whose reading a run prints and check (b)
# does not hold: the key temperature's gradient is two numbers, each the sum
# over every query-key pair of ``dS x S``, which cancels to a thousandth of
# its terms, so its relative error is the rounding's and follows the seed
# (0.002-0.28 on twenty-one seeds: configs/zaya1-8b.json, ``check.why``).
READ_NOT_HELD = ("k_temp_last",)

MIX_KEYS = {"seq_len", "batch_per_chip", "mesh_axes", "optimizer",
            "attention", "remat", "shard_optimizer", "packed",
            "token_distribution"}


def matmul_parameters(config: dict) -> dict:
    """Parameters that are matmul operands **for one token**, by where
    they sit: attention (``W_q`` of ``heads x head_dim``, ``W_k`` of ``kv
    heads x head_dim``, the two value projections of half the key-value
    heads each, ``W_o``), the head-grouped convolution (``cca_time1`` taps
    of a ``head_dim x head_dim`` matrix a query and key-value head: a
    convolution whose every tap is a matmul), the router (``d x w``, two
    ``w x w`` and ``w x (E + 1)`` over the published count and the skip),
    the one routed expert a token passes through **on this chip** (with
    probability ``num_experts / (published.num_experts + 1)`` under a
    uniform router; the skip multiplies nothing) and the tied head.  The
    depthwise taps, the mean, the norms, the temperature, the rotation and
    the merges multiply no matrix."""
    d, hd = config["hidden_size"], config["head_dim"]
    heads, groups = (config["num_attention_heads"],
                     config["num_key_value_heads"])
    w = config["router_hidden_size"]
    choices = config["published"]["num_experts"] + 1
    return {
        "attention": 2 * d * heads * hd + 2 * d * groups * hd,
        "grouped_conv": config["cca_time1"] * (heads + groups) * hd * hd,
        "router": d * w + 2 * w * w + w * choices,
        "experts": (config["num_experts_per_tok"] * config["num_experts"]
                    / choices * 3 * d * config["moe_intermediate_size"]),
        "head": d * config["vocab_size"]}


def train_flops(config: dict, seq_len: int, global_batch: int) -> float:
    """Model FLOPs of one training step on ``global_batch`` sequences of
    ``seq_len`` tokens, PaLM appendix B: ``6 x tokens x`` the matmul
    parameters a token passes through (:func:`matmul_parameters`, the
    layers' ``num_hidden_layers`` times, the tied head once), plus causal
    attention over the ``seq (seq + 1) / 2`` pairs a query head needs, ``4
    x head_dim`` a pair forward (QK^T, PV) and twice that backward: ``12 x
    pairs x heads x head_dim`` a layer.  Recomputation is never
    counted."""
    n = matmul_parameters(config)
    layers = config["num_hidden_layers"]
    tokens = global_batch * seq_len
    pairs = global_batch * seq_len * (seq_len + 1) // 2
    a_layer = (n["attention"] + n["grouped_conv"] + n["router"]
               + n["experts"])
    return (6.0 * tokens * (layers * a_layer + n["head"])
            + 12.0 * pairs * config["num_attention_heads"]
            * config["head_dim"] * layers)


def model_config(config: dict, seq_len: int) -> tfm.TransformerConfig:
    """The published keys as the program's config."""
    layers = config["num_hidden_layers"]
    rope = config["rope_parameters"]["hybrid"]
    if (config["hidden_act"] != "silu" or config["attention_bias"]
            or config["lm_head_bias"] or not config["tie_word_embeddings"]
            or config["sliding_window"] is not None
            or set(config["layer_types"][:layers]) != {"hybrid"}
            or rope["rope_type"] != "default"
            or rope["partial_rotary_factor"]
            != config["partial_rotary_factor"]):
        raise NotImplementedError(
            "cca_moe_lm adapter: silu, no bias, a tied head, no sliding "
            "window, every layer 'hybrid' (attention, then experts) and "
            "the default rotary embedding over a part of a head are what "
            "the program runs for this kind")
    return tfm.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_width=config["head_dim"], n_layers=layers, d_ff=0,
        max_seq=max(seq_len, config["max_position_embeddings"]),
        dtype=jnp.bfloat16, positions="rope",
        rope_theta=float(rope["rope_theta"]),
        norm_eps=config["rms_norm_eps"], tie_embeddings=True, mlp="swiglu",
        n_experts=config["published"]["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        experts_held=config["num_experts"],
        experts_held_from=config["experts_held_from"],
        cca_taps=(config["cca_time0"], config["cca_time1"]),
        rotary_dims=int(config["head_dim"]
                        * config["partial_rotary_factor"]),
        router_width=config["router_hidden_size"], residual_scaling=True,
        logit_scale=config["logit_scale"])


def reference_dims(cfg: tfm.TransformerConfig) -> dict:
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "rotary_dims": cfg.rotary_dims,
            "eps": cfg.norm_eps, "theta": cfg.rope_theta,
            "n_experts": cfg.n_experts, "held_from": cfg.experts_held_from}


# The out projections that the adapter shrinks by the published depth.
OUT_PROJECTIONS = ("wo", "w_down")


def make_arrays(key, pool, *, cfg, config, draw, global_batch, seq_len,
                init_opt):
    """``(state, [batch] * pool)`` from ``key``: the weights as the
    configuration's ``assumed`` says (the program's own initialisation, the
    embedding's scale, the out projections shrunk by the published depth;
    the final norm's scale stays at 1, the tied head's scale is the
    config's constant ``logit_scale``; the held experts are placed after
    it, :func:`placed`), and Zipf batches ``(tokens, labels)``."""
    k_params, k_data = jax.random.split(key)
    params = tfm.init_params(k_params, cfg)
    params["embed"] = config["embedding_init_std"] * jax.random.normal(
        jax.random.fold_in(k_params, 1), params["embed"].shape, jnp.float32)
    shrink = (2 * config["published"]["num_hidden_layers"]) ** -0.5
    for layer in params["layers"]:
        for name in OUT_PROJECTIONS:
            layer[name] = layer[name] * shrink
    toks = zipf_tokens(k_data, (pool, global_batch, seq_len + 1),
                       cfg.vocab_size, draw["exponent"])
    batches = [(toks[i, :, :-1], toks[i, :, 1:]) for i in range(pool)]
    return (params, init_opt(params)), batches


def for_reference(params, cfg: tfm.TransformerConfig):
    """``params`` as the plain reference reads them: it writes ``logits =
    RMSNorm_f(x) E^T`` and knows no constant, so the final norm's scale it
    is handed is the program's times ``logit_scale``: the same function of
    every other leaf."""
    return dict(params, ln_f_scale=params["ln_f_scale"] * cfg.logit_scale)


def placed(make, mesh, dims):
    """``make`` followed by the placement of every layer's held experts
    (``reference.place_layer``: the float32 forward of the first batch's
    first sequence, a layer at a time through one compiled program, each
    layer making up what the layers below fell short of their share by;
    the two router leaves it permutes keep their replicated sharding)."""
    whole = NamedSharding(mesh, P())
    first = jax.jit(lambda embed, tokens: embed[tokens[0]].astype(
        jnp.float32), out_shardings=whole)

    @functools.partial(jax.jit, out_shardings=whole)
    def one_layer(layer, x, state, owed):
        perm, x, state, _, owed = reference.place_layer(
            layer, x, state, owed, dims=dims)
        moved = reference.place(layer, perm)
        return moved["router_w3"], moved["router_bias"], x, state, owed

    def make_placed(seed: int, pool: int):
        (params, opt_state), batches = make(seed, pool)
        x = first(params["embed"], batches[0][0])
        state = jnp.zeros((x.shape[0], params["layers"][0][
            "router_down"].shape[1]), jnp.float32, device=whole)
        layers, owed = [], jnp.zeros((), jnp.float32, device=whole)
        for layer in params["layers"]:
            w3, bias, x, state, owed = one_layer(layer, x, state, owed)
            layers.append(dict(layer, router_w3=w3, router_bias=bias))
        return (dict(params, layers=layers), opt_state), batches

    return make_placed


def build(config: dict, mix: dict, mesh) -> Cell:
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"cca_moe_lm adapter: unknown mix keys "
                         f"{sorted(unknown)}")
    draw = mix["token_distribution"]
    if draw["name"] != "zipf":
        raise ValueError(f"token_distribution {draw['name']!r}: the "
                         f"cca_moe_lm adapter knows 'zipf'")
    if mix["shard_optimizer"]:
        raise NotImplementedError(
            "shard_optimizer=true: this adapter's check reads a gradient "
            "from whole leaves (checked)")
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
            make_arrays, cfg=cfg, config=config, draw=draw,
            global_batch=global_batch, seq_len=seq_len,
            init_opt=optimizer.init),
        (named(specs), named(opt_specs)), (data_sharding, data_sharding))
    make = placed(make, mesh, dims)

    ref = jax.jit(functools.partial(reference.loss_and_grads, dims=dims))
    tokens_per_chip = per_chip * seq_len
    choices = moe.router_choices(cfg)
    expected = global_batch * seq_len / choices

    def run_reference(state, batch):
        # On one device, reading the replicated weights in place.
        params, (tokens, labels) = on_first_device((state[0], batch), mesh)
        params = for_reference(params, cfg)
        start = time.perf_counter()
        loss, grads, stats = jax.block_until_ready(
            ref(params, tokens, labels))
        print(f"reference: float32 at precision highest, {cfg.n_layers} "
              f"layers on {tokens.size} tokens, attention a block of query "
              f"rows at a time, the {cfg.held_experts} held experts one "
              f"after another: {time.perf_counter() - start:.1f} s "
              f"(compile included where the cache did not hold it)",
              flush=True)
        read_only.update({name: np.asarray(grads[name], np.float32)
                          for name in READ_NOT_HELD})
        for i, (rows, skips) in enumerate(zip(np.asarray(stats["rows"]),
                                              np.asarray(stats["skips"]))):
            # The reference's own routing, not the program's.
            print(f"held experts, first batch, layer {i} (float32 "
                  f"reference routing): rows per held expert min "
                  f"{rows.min()} / mean {rows.mean():.1f} / max "
                  f"{rows.max()} against the expected {expected:.0f} "
                  f"(tokens / {choices}); {rows.sum()} rows in a buffer of "
                  f"{moe.rows_bound(tokens_per_chip, 1, cfg.held_experts)}"
                  f" = tokens, which no routing can exceed: dropped 0 by "
                  f"the bound; {skips} tokens skip, "
                  f"{100.0 * skips / tokens.size:.2f}% against "
                  f"{100.0 / choices:.2f}%", flush=True)
        return loss, grads

    paths = reference.leaf_paths(cfg.n_layers)
    grad_per_delta = -1.0 / mix["optimizer"]["learning_rate"]
    read_only = {}

    def checked(state):
        """The leaves check (b) recovers a gradient from, every one read
        from the MOMENTUM slot: after one step from zero it holds the
        gradient itself, rounded to bf16 once, and is handed over divided
        by ``grad_per_delta`` so that the harness's product gives it back
        (``bd_moe_lm``'s device).  A parameter's own change is ``-lr x``
        that gradient, lost in the float32 rounding of ``new - old`` where
        the leaf is a number near 1 (the depthwise taps read 0.31-0.45 off
        that way: PERF.md, PR 53).  A leaf of ``READ_NOT_HELD`` is printed
        beside the reference's and not handed over."""
        momentum = next(s.trace for s in state[1] if hasattr(s, "trace"))
        leaves = {name: reference.leaf(momentum, paths[name]).astype(
                      jnp.float32) for name in reference.CHECKED}
        for name in READ_NOT_HELD:
            got, want = np.asarray(leaves.pop(name)), read_only.get(name)
            if want is not None and got.any():
                print(f"read, not held: {name}: relative L2 error of the "
                      f"gradient in the momentum slot "
                      f"{np.linalg.norm(got - want) / np.linalg.norm(want):.4f}"
                      f" (gradient {got.tolist()}, reference "
                      f"{want.tolist()})", flush=True)
        return {name: leaf / grad_per_delta for name, leaf in leaves.items()}

    # Flash: forward, its recomputation, dQ and dK+dV a layer; the grouped
    # matmuls: three forward, three recomputed, six backward a layer.  XLA
    # numbers the instances of a name from one counter.
    instances = 16 * cfg.n_layers + 64
    kernels = {}
    if mix["attention"] in ("flash", "ring_flash"):
        kernels["flash"] = dict(
            kernel_cost_cca.grouped_causal_attention_train(
                per_chip, cfg.n_heads, cfg.kv_heads, seq_len, cfg.head_dim,
                cfg.n_layers),
            match=_defined(scopes.FLASH_FWD, scopes.FLASH_BWD_DQ,
                           scopes.FLASH_BWD_DKV, instances=instances))
    kernels["moe_gmm"] = dict(
        kernel_cost_cca.one_of_seventeen_experts_train(
            tokens_per_chip, cfg.held_experts, choices, cfg.d_model,
            cfg.d_expert, cfg.n_layers),
        match=_defined(scopes.MOE_GMM, scopes.MOE_GMM_NT, scopes.MOE_TGMM,
                       instances=instances))
    return Cell(
        step=step, state_shapes=state_shapes, batch_shapes=batch_shapes,
        make=make,
        flops_per_step=train_flops(config, seq_len, global_batch),
        item="tokens", items_per_step=global_batch * seq_len,
        grad_per_delta=grad_per_delta, checked=checked,
        reference=run_reference, kernels=kernels)
