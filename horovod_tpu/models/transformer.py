"""Decoder-only Transformer LM with composable data / tensor / sequence
parallelism — the long-context flagship.

No reference equivalent (Horovod ships no models; SURVEY §2.5/§5.7 shows no
TP/SP anywhere) — this model exists to exercise the framework's mesh axes
the way its CNN benchmark exercises DP.  Written functionally (explicit
param pytree, manual-SPMD forward) so it drops straight into ``shard_map``:

* data axis   — batch sharded, gradients averaged (fused pmean)
* model axis  — Megatron-style TP: qkv/up-proj column-parallel, out/down
  row-parallel, boundaries via :mod:`horovod_tpu.parallel.tensor`
* seq axis    — ring attention over contiguous sequence chunks
  (:mod:`horovod_tpu.parallel.sequence`)

bf16 matmuls / fp32 params+softmax, MXU-friendly dims.

The block is config-driven (:class:`TransformerConfig`, whose comments say
what each field means): the defaults are the GPT-2-style block (learned
positions, GELU MLP, tied head), and every other block goes through the
same ``forward`` and ``make_train_step``.  A layer holds up to two
**parts** (:mod:`horovod_tpu.models.parts`), a sequence mixer and a
feed-forward form; :data:`PARTS` is the table of them, and
:func:`layer_parts` the one function that says which of them layer ``i``
holds, from ``layer_types`` (one of :data:`LAYER_KINDS` a layer) and the
fields that select a form.  This file runs the table: parameters, specs,
the stack, the losses the parts hand back, the step, and the refusals,
which are asked of the parts.  OLMoE, Olmo-Hybrid, Nemotron-3,
GLM-4.7-Flash, Keye-VL-2.0's language model, Jamba2 and ZAYA1 are configs,
not code here; so is a stack run several times on the same weights with a readout
after every pass (``loops``).  ``decode_step`` and the pipelined builder
implement the GPT-2 block alone and say so by name.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import (attention as attention_mod, linear_attention,
                                mamba1, mamba2, mlp as mlp_mod, moe, parts)
from horovod_tpu.ops.flash_attention import BlockDiffusion
from horovod_tpu.parallel import sequence as seq_mod
from horovod_tpu import telemetry
from horovod_tpu.telemetry import scopes

# What perfbench/ reads here, under the names they always had.
_rmsnorm = parts.rmsnorm
_indexer_proj = attention_mod.indexer_proj

FULL_ATTENTION = "full_attention"
LINEAR_ATTENTION = "linear_attention"
MAMBA2 = "mamba2"
MAMBA = "mamba"
ATTENTION_ONLY = "attention"
MLP_ONLY = "mlp"
# What a layer of each type holds: its sequence mixer (a row of PARTS, or
# "attention" for the form of softmax attention the config selects) and
# whether the config's feed-forward form follows it.
LAYER_KINDS = {FULL_ATTENTION: ("attention", True),
               LINEAR_ATTENTION: ("linear_attention", True),
               MAMBA2: ("mamba2", False),
               MAMBA: ("mamba1", True),
               ATTENTION_ONLY: ("attention", False),
               MLP_ONLY: (None, True)}
# Every part a layer can hold (models/parts.py), in the order their rules
# are checked.
PARTS = {part.name: part for part in (
    linear_attention.PART, mamba2.PART, attention_mod.ATTENTION, mlp_mod.MLP,
    mlp_mod.MLP_BESIDE_EXPERTS, attention_mod.LATENT_ATTENTION,
    attention_mod.SPARSE_ATTENTION,
    moe.LATENT_EXPERTS, moe.SIGMOID_EXPERTS, moe.SOFTMAX_EXPERTS,
    mamba1.PART, attention_mod.CCA_ATTENTION, moe.ZAYA_EXPERTS)}
# The fields no part owns: the model's sizes, its positions and head, the
# layers' kinds and the prediction module.
BLOCK_FIELDS = ("vocab_size", "d_model", "n_heads", "n_layers", "max_seq",
                "dtype", "positions", "rope_theta", "norm_eps",
                "tie_embeddings", "layer_types", "mtp_layer_types",
                "mtp_loss_coef", "diffusion_block", "mask_token_id",
                "post_norm", "loops", "exit_entropy_coef",
                "residual_scaling", "logit_scale")


def layer_parts(cfg, i: int, mtp: bool = False):
    """``(mixer, feed-forward)``: the parts layer ``i`` of ``cfg`` holds
    (of the prediction module's layers with ``mtp``), ``None`` where it
    holds none.  **The one place that chooses**: everything else asks."""
    kind = cfg.mtp_layer_types[i] if mtp else cfg.layer_type(i)
    mixer, has_ffn = LAYER_KINDS[kind]
    if mixer == "attention":
        mixer = ("latent_attention" if cfg.latent_attention
                 else "sparse_attention" if cfg.sparse_attention
                 else "cca_attention" if cfg.cca_taps
                 else "attention")
    if not has_ffn:
        ffn = None
    elif not cfg.n_experts:
        ffn = "mlp"
    elif not mtp and i < cfg.dense_layers:
        ffn = "mlp_beside_experts"
    elif cfg.router_width:
        ffn = "zaya_experts"
    elif cfg.mlp == "relu2":
        ffn = "latent_experts"
    else:
        ffn = "sigmoid_experts" if cfg.sigmoid_router else "softmax_experts"
    return PARTS.get(mixer), PARTS.get(ffn)


def stack_parts(cfg, mtp: bool = False):
    """:func:`layer_parts` of every layer of the stack (of the prediction
    module with ``mtp``)."""
    count = len(cfg.mtp_layer_types) if mtp else cfg.n_layers
    return [layer_parts(cfg, i, mtp) for i in range(count)]


def parts_in_use(cfg):
    """The parts that some layer of ``cfg`` holds, the prediction
    module's among them, each once."""
    chosen = stack_parts(cfg) + stack_parts(cfg, mtp=True)
    return list(dict.fromkeys(
        part for pair in chosen for part in pair if part))


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq: int = 2048
    dtype: object = jnp.bfloat16
    # --- the block; the defaults are the GPT-2-style one -----------------
    # "learned": a [max_seq, d_model] table added to the embedding;
    # "rope": rotary embedding of q and k (rotate-half convention,
    # positions from 0), no table; "none": neither (a hybrid's recurrent
    # layers carry position, its full layers see the causal mask alone).
    positions: str = "learned"
    rope_theta: float = 10000.0
    # RMSNorm, each with its own scale, over the whole q and the whole k
    # projection before the head split (OLMoE).
    qk_norm: bool = False
    norm_eps: float = 1e-6
    # False: an untied ``head`` leaf [d_model, vocab] instead of embed.T.
    tie_embeddings: bool = True
    # Key-value heads that ``n_heads`` query heads share, ``n_heads /
    # n_kv_heads`` each (grouped-query attention); 0: one each.
    n_kv_heads: int = 0
    # Latent attention (MLA), all four together: heads of ``head_width``
    # (0: d_model / n_heads, the plain block's) whose last ``rope_dim``
    # query dims are rotary; the queries come up from a latent of
    # ``q_latent_rank`` and each head's rotary-free key dims and its
    # values (``head_width`` wide too) from one of ``kv_latent_rank``,
    # each latent RMS-normed; the rotary key of ``rope_dim`` is one head,
    # shared by all.  The out projection takes n_heads * head_width.
    head_width: int = 0
    q_latent_rank: int = 0
    kv_latent_rank: int = 0
    rope_dim: int = 0
    # "gelu": w2 gelu(w1 h); "swiglu": w_down (silu(w_gate h) * (w_up h));
    # "relu2" (with experts only): the latent mixture below.
    mlp: str = "gelu"
    # n_experts > 0: the MLP is ``n_experts`` SwiGLU experts of width
    # ``d_expert`` with softmax-then-top-``experts_per_token`` routing
    # that drops nothing (models/moe.py); d_ff is then the width of the
    # dense MLP that the first ``dense_layers`` layers keep instead (the
    # config's ``mlp`` form: SwiGLU; 0: every layer's is the experts').
    # ``experts_held`` (0: all) of them, from ``experts_held_from`` on,
    # are held by this chip and computed here; the rest are another
    # chip's, and left out (the router still scores and ranks them all).
    n_experts: int = 0
    experts_per_token: int = 0
    d_expert: int = 0
    norm_topk_prob: bool = False
    experts_held: int = 0
    experts_held_from: int = 0
    dense_layers: int = 0
    # mlp="relu2": the router scores all ``n_experts`` by sigmoid (+ a
    # selection bias), its top-k weights are renormalised and scaled by
    # ``routed_scale``; an expert is w_down relu(w_up l)^2 on ``l``, the
    # token in a latent width ``d_latent`` between two dense projections;
    # a shared expert of width ``d_shared`` on the hidden state is added
    # for every token.  mlp="swiglu" with ``d_shared``: the same router
    # and ``routed_scale`` over SwiGLU experts on the hidden state itself
    # (no ``d_latent``), the shared expert SwiGLU too.
    d_latent: int = 0
    d_shared: int = 0
    routed_scale: float = 1.0
    # Added to the cross-entropy: coefficient of the load-balancing loss
    # and of the router z-loss (moe.router_losses).
    router_aux_coef: float = 0.0
    router_z_coef: float = 0.0
    # One of LAYER_KINDS per layer; empty: "full_attention" everywhere.
    # A "linear_attention" layer's mixer is the gated delta rule
    # (models/linear_attention.py) over ``linear_key_heads`` heads of
    # ``linear_key_head_dim`` (q, k) and ``linear_value_head_dim`` (v, the
    # state's other side), after a causal depthwise convolution of
    # ``linear_conv_kernel`` taps; beta in (0, 2) with
    # ``linear_allow_neg_eigval``, else (0, 1).
    layer_types: Tuple[str, ...] = ()
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 0
    linear_allow_neg_eigval: bool = False
    # A "mamba2" layer (models/mamba2.py): ``ssm_heads`` heads of
    # ``ssm_head_dim`` channels with a state of ``ssm_state`` each, B and
    # C shared by the heads of each of ``ssm_groups`` groups, a causal
    # depthwise convolution of ``ssm_conv_kernel`` taps, the recurrence in
    # chunks of ``ssm_chunk`` tokens.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 0
    ssm_conv_kernel: int = 0
    ssm_chunk: int = 0
    # A "mamba" layer's mixer (models/mamba1.py), before the config's
    # feed-forward form: ``mamba_inner`` channels with a state of
    # ``mamba_state`` each and a decay of its own for every pair of them,
    # the step from a projection of rank ``mamba_dt_rank``, a causal
    # depthwise convolution of ``mamba_conv_kernel`` taps.
    mamba_inner: int = 0
    mamba_state: int = 0
    mamba_dt_rank: int = 0
    mamba_conv_kernel: int = 0
    # Multi-token prediction: layers of these types on [norm(embed(x_{t+1}));
    # norm(h_t)] W_eh predict x_{t+2} through the model's own embedding
    # and head; ``mtp_loss_coef`` x their cross-entropy is added.
    mtp_layer_types: Tuple[str, ...] = ()
    mtp_loss_coef: float = 0.0
    # RMSNorm of q and of k over each head's ``head_dim``, one learned
    # scale [head_dim] each, after the head split and before the rotation
    # (Qwen3); ``qk_norm`` above norms the whole projection instead.
    qk_norm_per_head: bool = False
    # Learned sparse attention, all four together: beside every softmax
    # attention layer an indexer of ``index_heads`` heads of
    # ``index_head_dim`` over ONE key head scores every earlier key of a
    # query from the layer's normed input (gradient cut), the
    # ``index_topk`` best are the keys all of the query's heads read
    # (every earlier key while there are no more), and
    # ``indexer_loss_coef`` x the mean over tokens of KL(head-mean
    # attention probabilities || softmax of the indexer's scores on those
    # keys), summed over layers, is added to the loss and reaches the
    # indexer alone (ops/sparse_attention.py).
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    indexer_loss_coef: float = 0.0
    # Block-diffusion training (Arriola et al., arXiv:2503.09573), with
    # ``diffusion_block`` > 0 the length of a block: the step takes clean
    # tokens [B, L], which of them are noised and each block's rate
    # (``make_train_step``, :func:`diffusion_noise`), runs the stack once
    # over the clean sequence and its noised copy (``mask_token_id`` where
    # noised; both halves at positions 0..L-1) under the mask of
    # ``ops.flash_attention.BlockDiffusion``, and its loss is the mean
    # over the L positions of the noised copy's cross-entropy **at** the
    # noised positions (no shift), each over its block's rate
    # (:func:`diffusion_loss_fn`).  Every mixer is plain softmax attention.
    diffusion_block: int = 0
    mask_token_id: int = -1
    # Sandwich norm: a second RMSNorm, with a scale of its own
    # (``ln1_post_scale``, ``ln2_post_scale``), on the output of the plain
    # attention part and of the dense MLP before the residual add, ``x +
    # RMSNorm(branch(RMSNorm(x)))``.
    post_norm: bool = False
    # ``loops`` > 1: the stack runs that many times over the SAME layers
    # (arXiv:2510.25741), the final norm after every pass, its output both
    # that pass's readout and the next pass's input.  Every readout goes
    # through the one head and an exit gate ``lambda_t = sigmoid(h w_g +
    # b_g)`` (``exit_gate_w``, ``exit_gate_b``; float32); a token leaves
    # after pass t with ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, the
    # last pass taking what is left, and the loss is the mean over tokens
    # of ``sum_t p_t xent_t - exit_entropy_coef H(p)`` (:func:`loss_fn`).
    # The passes are written out one after another in the program (``loops
    # x n_layers`` layer bodies, each readout after its pass): one
    # ``lax.scan`` over them is a quarter of the code and of the compile
    # and the same step time, and its body's heap packs worse, 1.3 GiB
    # more at ten layers of the cell's sizes (PERF.md, PR 51).
    loops: int = 1
    exit_entropy_coef: float = 0.0
    # Compressed convolutional attention (arXiv:2510.04476;
    # models/attention.py, ``cca_qkv``) in place of plain attention, with
    # ``cca_taps`` the taps of its two causal convolutions along the
    # sequence, ``(2, 2)``: q and k are projected to ``n_heads`` and
    # ``n_kv_heads`` heads of ``head_width`` (narrower together than
    # ``d_model``), mixed by a depthwise and a head-grouped convolution,
    # the mean of the unmixed q and k added back, half of the value heads
    # read from the previous token, q and k L2-normed a head with a learned
    # temperature a key-value head, and rotary over the first
    # ``rotary_dims`` of every head (0: the whole head).
    cca_taps: Tuple[int, ...] = ()
    rotary_dims: int = 0
    # ``router_width`` > 0: the experts' router is an MLP of that width
    # (models/moe.py, ``route_mlp``) fed by the token and by the previous
    # expert layer's router state, which every expert layer hands to the
    # next beside ``x``; it makes ONE choice a token among ``n_experts``
    # SwiGLU experts and a skip (``p u``, no matrix), under a selection
    # bias that chooses and does not weigh.
    router_width: int = 0
    # ``x <- a_r * (x + b_r) + a_o * (branch(RMSNorm(x)) + b_o)`` in place
    # of ``x + branch``, four vectors of ``d_model`` a part
    # (``parts.merged``), for the parts that write it (``Part.scaled_merge``).
    residual_scaling: bool = False
    # A constant the logits are multiplied by (T5's ``d_model ** -0.5`` on a
    # tied head; the ``logit_scale`` / ``output_multiplier`` of later tied
    # models): a tied head reads the embedding's own scale, and with the
    # constant here and not in ``ln_f_scale`` that leaf stays near 1, where
    # an optimizer's step is small beside it.  1.0: no multiply.
    logit_scale: float = 1.0

    def __post_init__(self):
        if self.positions not in ("learned", "rope", "none"):
            raise ValueError(f"positions={self.positions!r}: expected "
                             f"'learned', 'rope' or 'none'")
        kinds = tuple(LAYER_KINDS)
        if self.layer_types and (set(self.layer_types) - set(kinds)
                                 or len(self.layer_types) != self.n_layers):
            raise ValueError(
                f"layer_types={self.layer_types!r}: expected n_layers="
                f"{self.n_layers} entries of {kinds}")
        if set(self.mtp_layer_types) - set(kinds):
            raise ValueError(f"mtp_layer_types={self.mtp_layer_types!r}: "
                             f"expected entries of {kinds}")
        if bool(self.mtp_layer_types) != bool(self.mtp_loss_coef):
            raise ValueError("mtp_layer_types and mtp_loss_coef come "
                             "together")
        # Each part's own rules: what its fields need, and that they mean
        # nothing without it.
        used = parts_in_use(self)
        for part in PARTS.values():
            part.validate(self, part in used)
        plain = [part.name for part in used if not part.scaled_merge]
        if self.residual_scaling and plain:
            raise NotImplementedError(
                f"residual_scaling=True: the scaled residual merge is "
                f"written for compressed convolutional attention and the "
                f"experts under the MLP router, not for "
                + ", ".join(plain))
        if self.positions == "rope" and self.head_dim % 2:
            raise ValueError(f"positions='rope' needs an even head_dim, "
                             f"got {self.head_dim}")
        if self.diffusion_block < 0 or (
                (self.mask_token_id >= 0) != (self.diffusion_block > 0)):
            raise ValueError(
                f"diffusion_block={self.diffusion_block} (a block's "
                f"length, 0: off) and mask_token_id={self.mask_token_id} "
                f"(-1: none) come together")
        if self.diffusion_block:
            if self.mask_token_id >= self.vocab_size:
                raise ValueError(
                    f"mask_token_id={self.mask_token_id} is no row of a "
                    f"vocabulary of {self.vocab_size}")
            mixers = {mixer.name if mixer else "no mixer"
                      for mixer, _ in stack_parts(self)} - {"attention"}
            refused = (sorted(mixers)
                       + _off_default(self, ("mtp_layer_types",))
                       + ["positions='learned'"]
                       * (self.positions == "learned"))
            if refused:
                raise NotImplementedError(
                    f"diffusion_block={self.diffusion_block} runs layers "
                    f"of plain softmax attention under rotary or no "
                    f"positions, without a prediction module: not "
                    + ", ".join(refused))
        if self.loops < 1:
            raise ValueError(
                f"loops={self.loops}: passes over the stack, 1 or more")
        if self.loops == 1 and self.exit_entropy_coef:
            raise ValueError(
                f"exit_entropy_coef={self.exit_entropy_coef} means nothing "
                f"without loops > 1")
        if self.loops > 1:
            # The module reads the last layer's un-normed output once; the
            # block-diffusion loss reads one head on half the positions.
            refused = _off_default(self, ("mtp_layer_types",
                                          "diffusion_block"))
            if refused:
                raise NotImplementedError(
                    f"loops={self.loops} reads the head and the exit gate "
                    f"after every pass of one causal sequence: not "
                    f"implemented with " + ", ".join(refused))

    @property
    def head_dim(self) -> int:
        return self.head_width or self.d_model // self.n_heads

    @property
    def latent_attention(self) -> bool:
        return self.kv_latent_rank > 0

    @property
    def sparse_attention(self) -> bool:
        return self.index_topk > 0

    @property
    def attn_width(self) -> int:
        """What the query projection gives and the out projection takes."""
        return (self.n_heads * self.head_width if self.head_width
                else self.d_model)

    @property
    def sigmoid_router(self) -> bool:
        """SwiGLU experts under the sigmoid router, with a shared expert
        (the latent mixture, ``mlp="relu2"``, has the same router)."""
        return self.mlp == "swiglu" and self.d_shared > 0

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.n_experts

    def layer_type(self, i: int) -> str:
        return self.layer_types[i] if self.layer_types else FULL_ATTENTION


def _off_default(cfg, fields):
    """``name=value`` for each of ``fields`` that ``cfg`` sets."""
    defaults = TransformerConfig.__dataclass_fields__
    return [f"TransformerConfig.{name}={getattr(cfg, name)!r}"
            for name in fields if getattr(cfg, name) != defaults[name].default]


def _refuse_beyond_the_data_axis(cfg: TransformerConfig, **arguments) -> None:
    """Raise, by the argument's name and the config field's, for what a
    part of ``cfg`` does not implement: a tensor axis, a sequence axis,
    ``segment_ids`` / ``packed`` — never a silent fall back.  Each part in
    use is asked (``Part.unsupported``); the multi-token-prediction
    module implements none of the three (its second target is the next
    shard's or the next document's at a boundary)."""
    asked = [(part.name, part.unsupported) for part in parts_in_use(cfg)]
    asked.append(("the multi-token-prediction module",
                  parts.everywhere("mtp_layer_types")))
    # The mask's blocks are counted over one whole, unpacked sequence on
    # one chip, and the out projection's sum over a model axis is untried.
    asked.append(("block diffusion", parts.everywhere("diffusion_block")))
    for name, value in arguments.items():
        if value is None or value is False:
            continue
        what = "segment_ids" if name == "packed" else name
        refused = [f"{part} with {', '.join(fields)}"
                   for part, unsupported in asked
                   if (fields := _off_default(cfg, unsupported.get(what, ())))]
        if refused:
            shown = f"={value!r}" if isinstance(value, (str, bool)) else ""
            raise NotImplementedError(
                f"{name}{shown} is not implemented by "
                + "; ".join(refused))


# The fields of the GPT-2 block that decode_step implements and, but for
# the last three, the pipelined builder: any other field of the config
# that is set is refused by name, whichever configuration added it.
_PIPELINED_FIELDS = ("vocab_size", "d_model", "n_heads", "n_layers", "d_ff",
                     "max_seq", "dtype", "norm_eps", "rope_theta",
                     "post_norm")
_DECODE_FIELDS = _PIPELINED_FIELDS + ("qk_norm", "tie_embeddings", "mlp")
# (A looped stack: decode_step would keep a cache a pass and layer and
# leave by the exit rule, the pipelined builder run a circular schedule;
# neither is written, and ``loops`` is refused by name: ROADMAP R17.)


def _refuse_all_but(cfg: TransformerConfig, where: str, implemented) -> None:
    others = _off_default(cfg, [f.name for f in dataclasses.fields(cfg)
                                if f.name not in implemented])
    if others:
        raise NotImplementedError(
            f"{where} implements the GPT-2 block and not "
            + ", ".join(others))


def init_params(rng, cfg: TransformerConfig):
    """GLOBAL-shape parameters; shard with :func:`param_specs` +
    ``jax.device_put`` before use."""
    keys = jax.random.split(rng, 2 + cfg.n_layers)
    d, v = cfg.d_model, cfg.vocab_size
    dense = parts.dense

    def one_layer(key, chosen):
        """A layer's leaves: those of each part it holds."""
        k = jax.random.split(key, 6)
        layer = {}
        for part in filter(None, chosen):
            layer.update(part.init(k, cfg))
        return layer

    params = {
        "embed": dense(keys[0], (v, d), scale=0.02),
        "ln_f_scale": jnp.ones((d,), jnp.float32),
        "layers": [one_layer(key, chosen)
                   for key, chosen in zip(keys[2:], stack_parts(cfg))],
    }
    if cfg.mtp_layer_types:
        k_mtp = jax.random.split(jax.random.fold_in(keys[1], 2),
                                 1 + len(cfg.mtp_layer_types))
        params["mtp"] = {
            "embed_norm_scale": jnp.ones((d,), jnp.float32),
            "hidden_norm_scale": jnp.ones((d,), jnp.float32),
            "w_eh": dense(k_mtp[0], (2 * d, d)),
            "layers": [one_layer(key, chosen) for key, chosen in
                       zip(k_mtp[1:], stack_parts(cfg, mtp=True))],
            "ln_f_scale": jnp.ones((d,), jnp.float32),
        }
    if cfg.positions == "learned":
        params["pos"] = dense(keys[1], (cfg.max_seq, d), scale=0.02)
    if not cfg.tie_embeddings:
        params["head"] = dense(jax.random.fold_in(keys[1], 1), (d, v))
    if cfg.loops > 1:
        params["exit_gate_w"] = dense(jax.random.fold_in(keys[1], 3), (d, 1))
        params["exit_gate_b"] = jnp.zeros((1,), jnp.float32)
    return params


def param_specs(cfg: TransformerConfig, model_axis: Optional[str]):
    """PartitionSpec tree matching :func:`init_params` output: Megatron TP
    sharding over ``model_axis`` (column-parallel outputs, row-parallel
    inputs) where a part splits its leaves, everything else replicated."""
    def one_layer(chosen):
        layer = {}
        for part in filter(None, chosen):
            layer.update(part.specs(cfg, model_axis))
        return layer

    specs = {
        "embed": P(),
        "ln_f_scale": P(),
        "layers": [one_layer(chosen) for chosen in stack_parts(cfg)],
    }
    if cfg.mtp_layer_types:
        specs["mtp"] = {
            "embed_norm_scale": P(), "hidden_norm_scale": P(), "w_eh": P(),
            "layers": [one_layer(chosen)
                       for chosen in stack_parts(cfg, mtp=True)],
            "ln_f_scale": P()}
    if cfg.positions == "learned":
        specs["pos"] = P()
    if not cfg.tie_embeddings:
        specs["head"] = P()
    if cfg.loops > 1:
        specs.update(exit_gate_w=P(), exit_gate_b=P())
    return specs


@jax.named_scope(scopes.HEAD)
def _logits_head(x, params, cfg, normed: bool = False):
    """Final rmsnorm + projection onto the vocabulary, by the transposed
    embedding or the untied ``head`` (shared fwd/decode).  ``normed``: ``x``
    has been through the final norm already (a looped stack's state)."""
    dt = cfg.dtype
    if not normed:
        x = _rmsnorm(x, params["ln_f_scale"], cfg.norm_eps)
    if cfg.logit_scale != 1.0:
        # On the [.., d] side of the product, in float32.
        x = (x.astype(jnp.float32) * cfg.logit_scale).astype(x.dtype)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return (x @ w.astype(dt)).astype(jnp.float32)


def _remat_wrap(body, remat: str):
    """Wrap a per-layer block in ``jax.checkpoint`` per the ``remat``
    policy — the HBM-for-FLOPs trade that makes compute-bound LM configs
    fit (docs/benchmarks.md):

    * ``"none"``  — save every intermediate (XLA default).
    * ``"dots"``  — save matmul outputs only, recompute elementwise
      (``checkpoint_dots``): the usual sweet spot, cheap recompute.
    * ``"full"``  — save only the block's inputs, recompute it in the
      backward: O(L) fewer activation bytes, ~1.3x fwd FLOPs.

    A layer is up to two blocks, its sequence mixer and its MLP, each
    wrapped by itself: the backward holds one half's recomputed intermediates at
    a time (a whole layer's do not fit a v5e beside an Olmo-Hybrid
    period at 16384 tokens, PERF.md PR 31), for one more saved [B, T, d]
    a layer.
    """
    if remat == "none":
        return body
    if remat == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots)
    if remat == "full":
        return jax.checkpoint(body)
    raise ValueError(f"remat={remat!r}: expected 'none', 'dots' or 'full'")


def forward(params, tokens, cfg: TransformerConfig,
            model_axis: Optional[str] = None,
            seq_axis: Optional[str] = None,
            attention: str = "ring",
            segment_ids=None, remat: str = "none"):
    """tokens: [B, T_local] int32 -> logits [B, T_local, vocab] fp32
    (:func:`forward_with_router_stats` without the router's sums)."""
    return forward_with_router_stats(params, tokens, cfg, model_axis,
                                     seq_axis, attention, segment_ids,
                                     remat)[0]


def forward_with_router_stats(params, tokens, cfg: TransformerConfig,
                              model_axis: Optional[str] = None,
                              seq_axis: Optional[str] = None,
                              attention: str = "ring",
                              segment_ids=None, remat: str = "none"):
    """tokens: [B, T_local] int32 -> (logits [B, T_local, vocab] fp32,
    one :class:`moe.RouterStats` per layer — empty for a dense MLP).

    Inside shard_map, weight leaves arrive as LOCAL shards (per
    :func:`param_specs`); outside (single device) they are global and the
    axis args must be None.

    ``segment_ids`` ([B, T] int32, sequence packing) is supported on
    every attention route; under a ``seq_axis`` pass this shard's slice
    (sharded exactly like ``tokens``) — ring attention rotates the
    K-side ids with the K/V blocks, Ulysses all-gathers them (int32 per
    token) after its head scatter.
    """
    x, extras, _, _ = _hidden_states(params, tokens, cfg, model_axis,
                                     seq_axis, attention, segment_ids, remat)
    return (_logits_head(x, params, cfg, normed=cfg.loops > 1),
            extras["router_stats"])


def _hidden_states(params, tokens, cfg: TransformerConfig, model_axis,
                   seq_axis, attention, segment_ids, remat, layout=None,
                   readout=None):
    """``(x, extras, run_layers, readouts)``: the last layer's output
    before the final norm; what the loss collects from the layers run so
    far, a list a name (``router_stats``: one :class:`moe.RouterStats` per
    softmax-routed MoE layer; ``index_kl``: every sparse attention
    layer's summed KL of its indexer); and the function that ran the
    stack (``run_layers(x, layers, chosen, label)``), for the
    multi-token-prediction module to run its own layers by.  ``layout``:
    ``(positions [T], mask)`` where the tokens are not one causal sequence
    (:func:`diffusion_loss_fn`).  With ``cfg.loops`` > 1 the stack runs
    that many times and ``x`` is the last pass's state **after** the final
    norm; ``readouts[t]`` is ``readout(h)`` of pass ``t``'s normed state
    ``h`` [B, T, d], taken right after its pass (None with one pass or
    without ``readout``); ``extras`` lists every pass's layers in turn."""
    _refuse_beyond_the_data_axis(cfg, model_axis=model_axis,
                                 seq_axis=seq_axis, segment_ids=segment_ids)
    if cfg.diffusion_block and layout is None:
        raise NotImplementedError(
            f"TransformerConfig.diffusion_block={cfg.diffusion_block}: the "
            f"model reads a clean sequence beside its noised copy, which "
            f"make_train_step and diffusion_loss_fn build; forward() runs "
            f"one causal sequence")
    dt = cfg.dtype
    t_local = tokens.shape[1]
    mask = None
    with jax.named_scope(scopes.EMBED):
        pos_offset = (lax.axis_index(seq_axis) * t_local) if seq_axis else 0
        if cfg.positions == "learned":
            positions = None
            x = (params["embed"][tokens] +
                 lax.dynamic_slice_in_dim(params["pos"], pos_offset,
                                          t_local, axis=0)[None]).astype(dt)
        else:
            positions = pos_offset + jnp.arange(t_local)
            if layout is not None:
                positions, mask = layout
            x = params["embed"][tokens].astype(dt)
    ctx = parts.Ctx(model_axis, seq_axis, attention, positions, tokens.size,
                    mask=mask)
    extras = collections.defaultdict(list)

    @functools.cache
    def block(part):
        """A part's block, norm to residual, recomputed by itself.  What
        the part carries from layer to layer (``Part.carries``) is an
        argument and a result of the block beside ``x``: saved as the
        block's input, never recomputed across layers."""
        return _remat_wrap(
            lambda x, layer, segment_ids, *carried: part.apply(
                x, layer, cfg, ctx._replace(segment_ids=segment_ids),
                *carried), remat)

    def run_layers(x, layers, chosen, label="%d"):
        """``x`` through ``layers``, each holding the parts ``chosen``
        names for it; ``label % i`` names layer ``i`` in the trace-time
        series.  ``carried``: what the parts hand from layer to layer
        beside ``x``, by name; nothing before the first that hands it."""
        carried = {}
        for i, (layer, (mixer, ffn)) in enumerate(zip(layers, chosen)):
            with jax.named_scope(scopes.LAYER % i):
                # Packing is the mixer's business alone.
                for part, ids in ((mixer, segment_ids), (ffn, None)):
                    if not part:
                        continue
                    taken = [tuple(map(carried.get, part.carries))] * bool(
                        part.carries)
                    x, extra, *handed = block(part)(x, layer, ids, *taken)
                    carried.update(zip(part.carries, *handed))
                    for name, value in extra.items():
                        extras[name].append(value)
            for part in filter(None, (mixer, ffn)):
                part.record(label % i, x, layer, cfg, ctx)
        return x

    chosen = stack_parts(cfg)
    if cfg.loops == 1:
        return (run_layers(x, params["layers"], chosen), extras, run_layers,
                None)

    telemetry.gauge("hvd_lm_loops", "Passes of the most recently traced "
                    "looped stack over its layers").set(cfg.loops)
    readouts = []
    for t in range(cfg.loops):
        with jax.named_scope(scopes.LOOP % t):
            x = run_layers(x, params["layers"], chosen)
            with jax.named_scope(scopes.LOOP_NORM):
                x = _rmsnorm(x, params["ln_f_scale"], cfg.norm_eps)
        readouts.append(readout(x) if readout else None)
    return x, extras, run_layers, readouts


def _log_likelihood(logits, labels):
    """``log softmax(logits)[labels]`` a token: the one cross-entropy
    formula, under whatever scope the caller holds."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


@jax.named_scope(scopes.LOSS)
def token_xent(logits, labels):
    """Next-token cross-entropy a token, ``[..., T]`` float32: what
    :func:`xent` is the mean of."""
    return -_log_likelihood(logits, labels)


@jax.named_scope(scopes.LOSS)
def xent(logits, labels, counted=None):
    """Mean next-token cross-entropy (the one loss formula — shared by
    the plain and pipelined training steps and the oracle tests), over
    the positions where ``counted`` (bool, broadcast against ``labels``)
    holds; over all of them without it."""
    ll = _log_likelihood(logits, labels)
    if counted is None:
        return -jnp.mean(ll)
    counted = jnp.broadcast_to(counted, ll.shape)
    return -jnp.sum(jnp.where(counted, ll, 0.0)) / jnp.sum(counted)


def _mtp_loss(params, x, labels, cfg: TransformerConfig, run_layers):
    """Cross-entropy of the multi-token-prediction module: with ``x`` [B,
    T, d] the stack's output before the final norm (``h_t``) and
    ``labels`` the next tokens (``x_{t+1}``), ``[RMSNorm(embed(x_{t+1}));
    RMSNorm(h_t)] W_eh`` through the module's layers and its own final
    norm, then **the model's embedding and head**, predicts ``x_{t+2}``:
    the mean over the ``T - 1`` positions that have one.  Every position
    runs (the layers are causal, and ``T`` keeps the length the kernels
    tile); the last is left out of the mean."""
    mtp, dt = params["mtp"], cfg.dtype
    with jax.named_scope(scopes.MTP):
        with jax.named_scope(scopes.EMBED):
            ahead = _rmsnorm(params["embed"][labels].astype(dt),
                             mtp["embed_norm_scale"], cfg.norm_eps)
            here = _rmsnorm(x, mtp["hidden_norm_scale"], cfg.norm_eps)
            h = (jnp.concatenate([ahead, here], axis=-1)
                 @ mtp["w_eh"].astype(dt))
        h = run_layers(h, mtp["layers"], stack_parts(cfg, mtp=True),
                       "mtp_%d")
        logits = _logits_head(h, dict(params, ln_f_scale=mtp["ln_f_scale"]),
                              cfg)
        with jax.named_scope(scopes.LOSS):
            second = jnp.roll(labels, -1, axis=1)
            has_second = jnp.arange(labels.shape[1]) < labels.shape[1] - 1
        return xent(logits, second, has_second)


def loss_fn(params, tokens, labels, cfg: TransformerConfig,
            model_axis=None, seq_axis=None, attention="ring",
            segment_ids=None, remat="none", batch_axes=()):
    """Mean next-token cross-entropy over the LOCAL shard (callers pmean
    over data/seq axes), plus, with experts, ``router_aux_coef`` x the
    load-balancing loss and ``router_z_coef`` x the router z-loss over
    all layers' tokens together.  ``batch_axes``: the mesh axes the batch
    is split over, so that the mean of the shards' losses is the global
    batch's loss (:func:`moe.router_losses`).  With ``cfg.loops`` > 1 the
    cross-entropy's place is taken by :func:`exit_mixture` of every pass's
    (``TransformerConfig.loops``)."""
    readout = None
    if cfg.loops > 1:
        # A recomputed block of its own a pass: [B, T, vocab] float32
        # logits do not outlive their readout.
        read = _remat_wrap(functools.partial(_exit_readout, cfg=cfg), remat)
        heads = {name: leaf for name, leaf in params.items()
                 if name in ("embed", "head", "exit_gate_w", "exit_gate_b")}
        readout = lambda h: read(h, heads, labels)
    x, extras, run_layers, readouts = _hidden_states(
        params, tokens, cfg, model_axis, seq_axis, attention, segment_ids,
        remat, readout=readout)
    if cfg.loops > 1:
        loss = exit_mixture(*zip(*readouts), cfg.exit_entropy_coef)
    else:
        loss = xent(_logits_head(x, params, cfg), labels)
    if cfg.mtp_layer_types:
        ahead = _mtp_loss(params, x, labels, cfg, run_layers)
        with jax.named_scope(scopes.LOSS):
            loss = loss + cfg.mtp_loss_coef * ahead
    # Read after the module has run: its layers' are among them.
    index_kl, router_stats = extras["index_kl"], extras["router_stats"]
    if index_kl:
        # Every sparse layer run, the prediction module's included: the
        # mean over this shard's tokens of each, summed over layers.
        with jax.named_scope(scopes.LOSS):
            loss = loss + cfg.indexer_loss_coef * (
                sum(index_kl) / tokens.size)
    return _with_router_losses(loss, router_stats, tokens.size, cfg,
                               batch_axes)


def _exit_readout(h, heads, labels, cfg):
    """What one pass of a looped stack leaves of its normed state ``h``
    [B, T, d]: the next-token cross-entropy a token through the model's
    one head, and the exit gate's pre-activation (float32, exact: a sum
    on the vector unit, no matmul pass), both ``[B, T]`` float32."""
    losses = token_xent(_logits_head(h, heads, cfg, normed=True), labels)
    with jax.named_scope(scopes.HEAD), jax.named_scope(scopes.EXIT_GATE):
        gates = (jnp.sum(h.astype(jnp.float32) * heads["exit_gate_w"][:, 0],
                         axis=-1) + heads["exit_gate_b"][0])
    return losses, gates


def exit_log_probs(gates):
    """``log p`` [loops, ...] of the exit distribution from the gates'
    pre-activations ``g`` [loops, ...], ``lambda_t = sigmoid(g_t)``: ``p_t
    = lambda_t S_{t-1}`` with ``S_t = prod_{j<=t} (1 - lambda_j)``, the
    last pass taking what is left, ``p_L = S_{L-1}`` (its own gate is
    unused), so that the passes' sum to one.  In logarithms: ``log S_t =
    -sum_{j<=t} softplus(g_j)``, ``log lambda_t = -softplus(-g_t)``."""
    stay = -jnp.cumsum(jax.nn.softplus(gates), axis=0)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
    return jnp.concatenate(
        [(-jax.nn.softplus(-gates) + before)[:-1], before[-1:]])


@jax.named_scope(scopes.LOSS)
@jax.named_scope(scopes.EXIT_MIX)
def exit_mixture(losses, gates, entropy_coef: float):
    """The expected-exit loss of a looped stack (arXiv:2510.25741, first
    stage): with ``losses`` and ``gates`` (a pass each, [B, T]) every pass's
    cross-entropy and gate pre-activation a token and ``p`` the exit
    distribution (:func:`exit_log_probs`), the mean over tokens of
    ``sum_t p_t losses_t - entropy_coef H(p)``, ``H(p) = -sum_t p_t log
    p_t``.  The gate learns through ``p`` and ``H``, the language model
    through every pass's cross-entropy under its weight."""
    losses, gates = jnp.stack(losses), jnp.stack(gates)
    log_p = exit_log_probs(gates)
    p = jnp.exp(log_p)
    return jnp.mean(jnp.sum(p * losses, axis=0)
                    + entropy_coef * jnp.sum(p * log_p, axis=0))


def _with_router_losses(loss, router_stats, tokens: int, cfg, batch_axes):
    """``loss`` plus the softmax routers' load-balancing and z losses over
    all layers' ``tokens`` together, under their coefficients."""
    if router_stats:
        with jax.named_scope(scopes.LOSS):
            balance, z = moe.router_losses(
                router_stats, tokens * len(router_stats), batch_axes)
            loss = (loss + cfg.router_aux_coef * balance
                    + cfg.router_z_coef * z)
    return loss


def diffusion_noise(key, batch: int, length: int, block: int,
                    t_min: float = 1e-3):
    """``(masked [batch, length] bool, rates [batch, length / block]
    float32)`` for a step of a ``diffusion_block`` config, for a caller
    with no input layer of its own: each block's rate ~ U[``t_min``, 1]
    (the linear schedule, clipped below as the block-diffusion paper
    clips it), each token noised with its block's rate, independently."""
    k_rates, k_masked = jax.random.split(key)
    rates = jax.random.uniform(k_rates, (batch, length // block),
                               jnp.float32, t_min, 1.0)
    masked = (jax.random.uniform(k_masked, (batch, length), jnp.float32)
              < jnp.repeat(rates, block, axis=1))
    return masked, rates


def diffusion_loss_fn(params, tokens, masked, rates, cfg: TransformerConfig,
                      attention="flash", remat="none", batch_axes=()):
    """The block-diffusion loss over the LOCAL shard: with ``tokens`` [B,
    L] the clean ids, ``masked`` [B, L] which of them the noised copy
    hides and ``rates`` [B, L / diffusion_block] each block's rate ``t``,
    ``(1 / (B L)) sum over masked i of (1 / t_block(i)) x (-log softmax(
    head(h_i))[tokens_i])``: ``h_i`` the noised copy's last hidden state
    at ``i`` itself, the weight the linear schedule's.  One pass over ``2
    L`` positions, the clean sequence first and its noised copy after it
    (how they are laid out is this function's own business: the mask and
    the positions it hands the stack say it, and nothing outside reads
    it), both halves at positions ``0..L-1``; the head runs on the noised
    half alone, and the clean half's last hidden states feed no loss.
    Plus the routers' losses over all ``2 L`` positions, as
    :func:`loss_fn` adds them."""
    length, block = tokens.shape[1], cfg.diffusion_block
    if length % block or rates.shape != (tokens.shape[0], length // block):
        raise ValueError(
            f"rates {rates.shape} must hold one rate for each block of "
            f"{block} of tokens {tokens.shape}")
    with jax.named_scope(scopes.EMBED), jax.named_scope(
            scopes.DIFFUSION_ASSEMBLE):
        noised = jnp.where(masked, cfg.mask_token_id, tokens)
        stream = jnp.concatenate([tokens, noised], axis=1)
        positions = jnp.tile(jnp.arange(length), 2)
        weights = jnp.where(masked, 1.0 / jnp.repeat(rates, block, axis=1),
                            0.0)
    x, extras, _, _ = _hidden_states(
        params, stream, cfg, None, None, attention, None, remat,
        layout=(positions, BlockDiffusion(length, block)))
    with jax.named_scope(scopes.HEAD):
        # (Its gradient's padding is the head's too.)
        noised_half = x[:, length:]
    logits = _logits_head(noised_half, params, cfg)
    with jax.named_scope(scopes.LOSS):
        ll = _log_likelihood(logits, tokens)
        loss = -jnp.sum(weights * ll) / tokens.size
    return _with_router_losses(loss, extras["router_stats"], stream.size,
                               cfg, batch_axes)


@telemetry.span("make_train_step", step=scopes.LM_TRAIN_STEP)
def make_train_step(cfg: TransformerConfig, optimizer, mesh,
                    data_axis: str = "data",
                    model_axis: Optional[str] = None,
                    seq_axis: Optional[str] = None,
                    attention: str = "ring",
                    donate: bool = True,
                    packed: bool = False,
                    remat: str = "none",
                    steps_per_call: int = 1,
                    shard_optimizer: bool = False,
                    compression=None):
    """Jitted SPMD training step over dp x tp x sp.

    Returns ``step(params, opt_state, tokens, labels) ->
    (params, opt_state, loss)`` plus the param spec tree (for placing
    params with ``jax.device_put``).  ``packed=True`` adds a trailing
    ``segment_ids`` argument ([B, T] int32, sharded like tokens) so
    sequence packing reaches the jitted step on every attention route,
    including the sequence-parallel ones (see :func:`forward`).  With
    ``cfg.diffusion_block`` the step is ``step(params, opt_state, tokens,
    masked, rates)`` and its loss :func:`diffusion_loss_fn`'s
    (:func:`diffusion_noise` draws the last two).

    ``remat`` selects the per-layer rematerialization policy (see
    :func:`_remat_wrap`); ``steps_per_call > 1`` runs that many steps
    inside one compiled program via ``lax.scan`` on the SAME batch —
    the benchmark's dispatch-amortization shape (the ResNet harness's
    rationale at ``benchmark.make_train_step``; not for real training,
    which wants a fresh batch per step).

    ``shard_optimizer=True`` runs the ZeRO-1 sharded update
    (:mod:`horovod_tpu.parallel.zero`): reduce-scatter gradients over the
    data axis, optimizer step on this rank's 1/N flat shard, all-gather
    the updates.  Pure data parallelism only (params must be replicated,
    so ``model_axis``/``seq_axis`` must be ``None``).  The returned step
    additionally carries ``step.init`` (build the sharded-layout state
    from params) and ``step.optimizer`` (the ``ShardedOptimizer``).

    ``compression`` selects the gradient wire codec (name string, codec
    instance, or ``None`` → ``HOROVOD_COMPRESSION``; see
    :func:`horovod_tpu.ops.compression.resolve_codec`).  It rides the
    ZeRO reduce-scatter/all-gather wire, so a non-``none`` codec
    requires ``shard_optimizer=True``.
    """
    from horovod_tpu.ops.fusion import fused_pytree_mean

    _refuse_beyond_the_data_axis(cfg, model_axis=model_axis,
                                 seq_axis=seq_axis, packed=packed)
    specs = param_specs(cfg, model_axis)
    grad_axes = tuple(a for a in (data_axis, seq_axis) if a)

    from horovod_tpu.ops import compression as compression_mod
    codec = compression_mod.resolve_codec(compression)

    zopt = None
    if shard_optimizer:
        if model_axis or seq_axis:
            raise NotImplementedError(
                "shard_optimizer=True composes with pure data parallelism "
                "only (ZeRO-1 slices replicated params); got "
                f"model_axis={model_axis!r}, seq_axis={seq_axis!r}")
        from horovod_tpu.parallel import zero
        zopt = zero.sharded_optimizer(
            optimizer, data_axis, axis_size=int(mesh.shape[data_axis]),
            compression=codec)
    elif not isinstance(codec, compression_mod.NoneCodec):
        raise NotImplementedError(
            f"compression={codec.name!r} rides the ZeRO reduce-scatter "
            f"wire; pass shard_optimizer=True (the plain path's fused "
            f"pmean has no per-bucket wire to compress)")

    def _update(params, opt_state, loss_of):
        """One step on the batch whose loss is ``loss_of(params)``."""
        from horovod_tpu import resilience
        from horovod_tpu.parallel._vma import pin_to
        # Differentiate with respect to a copy of the params typed as
        # varying over the gradient axes.  Under check_vma=True, autodiff
        # with respect to a replicated (unvarying) param already sums the
        # per-device gradients (the transpose of the unvarying->varying
        # promotion is a psum), and the explicit mean below would then
        # average N identical sums: a step N times too large.  Pinned,
        # the gradients stay local and the fused pmean is the one
        # reduction.  The model axis is left alone: there the automatic
        # sum IS the Megatron "f" operator (parallel/tensor.py).
        local_params = jax.tree_util.tree_map(pin_to(set(grad_axes)),
                                              params)
        loss, grads = jax.value_and_grad(loss_of)(local_params)

        def do_update():
            if zopt is not None:
                # ZeRO-1: the mean happens on the reduce-scattered 1/N
                # shard inside the sharded update — no separate fused
                # pmean pass.
                updates, new_opt = zopt.update(grads, opt_state, params)
            else:
                # DP gradient averaging (fused psum) over data (+seq)
                # axes; TP/f-op already settled the model axis.
                g = fused_pytree_mean(grads, grad_axes)
                with jax.named_scope(scopes.OPTIMIZER):
                    updates, new_opt = optimizer.update(g, opt_state,
                                                        params)
            with jax.named_scope(scopes.OPTIMIZER):
                new_params = jax.tree_util.tree_map(lambda p, u: p + u,
                                                    params, updates)
            return new_params, new_opt

        (new_params, new_opt), mean_loss = resilience.apply_step_guard(
            do_update, loss=loss, grads=grads,
            old_state=(params, opt_state), axes=grad_axes,
            # agreement must also settle the TP axis: model-sharded
            # leaves would otherwise disagree on the select.
            agree_axes=tuple(a for a in (data_axis, seq_axis, model_axis)
                             if a))
        return new_params, new_opt, mean_loss

    # The step's arguments keep their names: a trace's buffer assignment
    # is read by them (perfbench/memory_reduce.py).
    if cfg.diffusion_block:
        def _one_step(params, opt_state, tokens, masked, rates):
            return _update(params, opt_state, lambda p: diffusion_loss_fn(
                p, tokens, masked, rates, cfg, attention, remat, grad_axes))
    else:
        def _one_step(params, opt_state, tokens, labels, segment_ids=None):
            return _update(params, opt_state, lambda p: loss_fn(
                p, tokens, labels, cfg, model_axis, seq_axis, attention,
                segment_ids, remat, grad_axes))

    if steps_per_call > 1:
        @functools.wraps(_one_step)
        def _step(params, opt_state, *batch):
            def body(carry, _):
                p, o = carry
                p, o, loss = _one_step(p, o, *batch)
                return (p, o), loss
            (params, opt_state), losses = lax.scan(
                body, (params, opt_state), None, length=steps_per_call)
            return params, opt_state, losses[-1]
    else:
        _step = _one_step

    # Param-like opt-state leaves (momenta etc.) inherit the matching
    # param's spec; everything else (step counters, empty states) is
    # replicated.  tree_map_params aligns by optimizer structure, so
    # distinct params that happen to share a shape cannot be confused.
    # In sharded mode the param-like leaves are flat bucket vectors
    # partitioned 1/N over the data axis instead.
    import optax
    if zopt is not None:
        opt_state_shapes = jax.eval_shape(zopt.init, init_abstract(cfg))
        opt_specs = zopt.state_specs(opt_state_shapes)
    else:
        opt_state_shapes = jax.eval_shape(optimizer.init, init_abstract(cfg))
        opt_specs = optax.tree_map_params(
            optimizer, lambda _leaf, spec: spec, opt_state_shapes, specs,
            transform_non_params=lambda _leaf: P())

    data_spec = P(data_axis, seq_axis) if seq_axis else P(data_axis)
    in_specs = (specs, opt_specs) + (data_spec,) * (
        3 if packed or cfg.diffusion_block else 2)
    step = jax.shard_map(
        _step, mesh=mesh,
        in_specs=in_specs,
        out_specs=(specs, opt_specs, P()),
        # The ZeRO path's axis_index-dependent slicing + psum_scatter do
        # not type under the vma checker, nor do the expert layer's
        # grouped-matmul kernels in the Pallas interpreter (their index
        # maps read arrays that vary over the batch axes,
        # ops/grouped_matmul.py); the plain dense path keeps it on.
        check_vma=zopt is None and all(part.check_vma
                                       for part in parts_in_use(cfg)))
    jitted = jax.jit(scopes.named(step, scopes.LM_TRAIN_STEP),
                     donate_argnums=(0, 1) if donate else ())
    if zopt is not None:
        @functools.wraps(jitted)
        def wrapped(*a, **kw):
            return jitted(*a, **kw)
        wrapped.lower = jitted.lower
        wrapped.jitted = jitted
        wrapped.init = zopt.init
        wrapped.optimizer = zopt
        wrapped.state_shardings = functools.partial(zopt.state_shardings,
                                                    mesh)
        return wrapped, specs, opt_specs
    return jitted, specs, opt_specs


def init_abstract(cfg: TransformerConfig):
    """ShapeDtypeStructs of the params (for spec derivation without
    materializing weights)."""
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# Inference: KV-cache decode + greedy generation (reference docs/inference
# topic; Horovod itself ships no inference machinery — this is the
# TPU-idiomatic decode loop: static shapes, lax.scan, cache updates via
# dynamic_update_slice so the whole generation compiles to one program).
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int,
                  model_axis_size: int = 1):
    """Per-layer K/V caches of shape [B, max_len, H_local, head_dim]
    (H_local = n_heads / model_axis_size under tensor parallelism)."""
    h_local = cfg.n_heads // model_axis_size
    z = lambda: jnp.zeros((batch, max_len, h_local, cfg.head_dim),
                          cfg.dtype)
    return [{"k": z(), "v": z()} for _ in range(cfg.n_layers)]


def decode_step(params, token, cache, pos, cfg: TransformerConfig,
                model_axis: Optional[str] = None):
    """One-token decode.  token: [B] int32, pos: scalar int32 position.

    Returns (logits [B, vocab] fp32, updated cache).  Attention runs over
    the full static cache length with a position mask (TPU-friendly: no
    dynamic shapes), so cost is O(max_len) per step.
    """
    # A rotated key cache, an expert layer per token and a recurrent
    # layer's state and convolution window beside the key cache are not
    # written (serving: ROADMAP R8/R13).
    _refuse_all_but(cfg, "decode_step", _DECODE_FIELDS)
    dt = cfg.dtype
    hd = cfg.head_dim
    x = (params["embed"][token] +
         lax.dynamic_slice_in_dim(params["pos"], pos, 1, axis=0)[0]
         ).astype(dt)                                    # [B, D]
    new_cache = []
    for layer, c in zip(params["layers"], cache):
        q, k, v, dh = attention_mod.qkv_proj(x, layer, cfg, model_axis)
        b = q.shape[0]
        # Defensive cast: the cache is cfg.dtype forever; any future
        # dtype drift upstream (the r4 rmsnorm f32-scale promotion was
        # exactly such a leak) must not change the cache layout.
        ck = lax.dynamic_update_slice_in_dim(
            c["k"], k[:, None].astype(c["k"].dtype), pos, axis=1)
        cv = lax.dynamic_update_slice_in_dim(
            c["v"], v[:, None].astype(c["v"].dtype), pos, axis=1)
        new_cache.append({"k": ck, "v": cv})
        # Scores in fp32: a one-token decode is latency-bound, not
        # MXU-bound, so the extra precision over local_attention's
        # input-dtype scores is free (identical under fp32 configs,
        # which is what the decode==forward oracle test runs).
        s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                       ck.astype(jnp.float32)) * (hd ** -0.5)
        mask = jnp.arange(ck.shape[1]) <= pos              # [T]
        s = jnp.where(mask[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bht,bthd->bhd", p,
                       cv.astype(jnp.float32)).astype(dt)
        x = attention_mod.attn_out(o.reshape(b, dh), x, layer, cfg,
                                   model_axis)
        x = mlp_mod.mlp_block(x, layer, cfg, model_axis)
    return _logits_head(x, params, cfg), new_cache


def generate(params, prompt, total_len: int, cfg: TransformerConfig,
             model_axis: Optional[str] = None):
    """Greedy decode to ``total_len`` tokens, teacher-forcing ``prompt``.

    prompt: [B, P] int32 (P >= 1).  Returns [B, total_len] int32 whose
    first P entries are the prompt.  One ``lax.scan`` — a single compiled
    program regardless of length.
    """
    b, p_len = prompt.shape
    if total_len > cfg.max_seq:
        raise ValueError(
            f"total_len={total_len} exceeds the positional table "
            f"(max_seq={cfg.max_seq})")
    if p_len > total_len:
        raise ValueError(
            f"prompt length {p_len} exceeds total_len={total_len}; the "
            f"output must contain the whole prompt")
    cache = init_kv_cache(
        cfg, b, total_len,
        lax.axis_size(model_axis) if model_axis else 1)

    def body(carry, pos):
        token, cache = carry
        logits, cache = decode_step(params, token, cache, pos, cfg,
                                    model_axis)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # Teacher-force while still inside the prompt.
        nxt = jnp.where(pos + 1 < p_len, prompt[:, jnp.minimum(
            pos + 1, p_len - 1)], nxt)
        return (nxt, cache), nxt

    (last, _), toks = lax.scan(body, (prompt[:, 0], cache),
                               jnp.arange(total_len - 1))
    return jnp.concatenate([prompt[:, :1], toks.T], axis=1)


# ---------------------------------------------------------------------------
# Pipeline-parallel forward: the transformer over a 'pipe' mesh axis
# (parallel/pipeline.py GPipe schedule; no reference equivalent).
# ---------------------------------------------------------------------------

def stack_layer_params(params, n_stages: int):
    """Re-layout the per-layer param list for pipelining.

    Returns a dict of leaves shaped [n_stages, layers_per_stage, ...] —
    shard the leading dim over the pipe axis (device p holds stage p).
    """
    layers = params["layers"]
    if len(layers) % n_stages:
        raise ValueError(f"{len(layers)} layers not divisible into "
                         f"{n_stages} stages")
    from horovod_tpu.parallel.pipeline import stack_stage_params
    lps = len(layers) // n_stages
    return stack_stage_params(
        [stack_stage_params(layers[s * lps:(s + 1) * lps])
         for s in range(n_stages)])


def stack_layer_params_interleaved(params, n_devices: int, virtual: int):
    """Round-robin (Megatron-interleave) re-layout: leaves
    [n_devices·virtual, layers_per_chunk, ...] ordered so that sharding
    the leading dim over the pipe axis hands device p local slot k =
    global chunk ``k·n_devices + p`` (global row ``j = p·v + k`` holds
    chunk ``(j % v)·P + j // v``)."""
    layers = params["layers"]
    n_chunks = n_devices * virtual
    if len(layers) % n_chunks:
        raise ValueError(f"{len(layers)} layers not divisible into "
                         f"{n_chunks} virtual chunks")
    from horovod_tpu.parallel.pipeline import stack_stage_params
    lpc = len(layers) // n_chunks
    chunk = lambda c: stack_stage_params(layers[c * lpc:(c + 1) * lpc])
    order = [(j % virtual) * n_devices + j // virtual
             for j in range(n_chunks)]
    return stack_stage_params([chunk(c) for c in order])


def stacked_layer_specs(pipe_axis: str):
    """PartitionSpec for every stacked-layer leaf: stage dim over pipe."""
    return P(pipe_axis)


def forward_pipelined(params, stacked_layers, tokens,
                      cfg: TransformerConfig, pipe_axis: str = "pipe",
                      n_microbatches: int = 2, virtual: int = 1):
    """Forward pass with the layer stack pipelined over ``pipe_axis``.

    ``params`` supplies embed/pos/ln_f (replicated); ``stacked_layers``
    comes from :func:`stack_layer_params` with its stage dim sharded over
    the pipe axis (inside shard_map each device sees a [1, lps, ...]
    slice).  The batch is split into ``n_microbatches`` and flows through
    :func:`horovod_tpu.parallel.pipeline.pipeline_apply`; embedding and
    logits head are computed replicated (they are cheap relative to the
    layer stack, which is where PP's memory win lives).  Attention is
    local causal (compose PP with DP via a 2-D mesh; TP/SP composition
    belongs on the model/seq axes of the non-pipelined forward).
    """
    from horovod_tpu.parallel.pipeline import (pipeline_apply,
                                               pipeline_apply_interleaved)

    b, t = tokens.shape
    mb = _embed_microbatches(params, tokens, cfg, n_microbatches)
    if virtual > 1:
        # Round-robin virtual chunks (stack_layer_params_interleaved):
        # the fill shrinks to (P-1)/v chunk-ticks — see
        # pipeline_apply_interleaved for the schedule derivation.
        y = pipeline_apply_interleaved(_pipe_stage_fn(cfg), stacked_layers,
                                       mb, axis_name=pipe_axis,
                                       virtual=virtual)
    else:
        y = pipeline_apply(_pipe_stage_fn(cfg), stacked_layers, mb,
                           axis_name=pipe_axis)
    x = y.reshape(b, t, cfg.d_model)
    return _logits_head(x, params, cfg)


def _embed_microbatches(base, tokens, cfg: TransformerConfig,
                        n_microbatches: int):
    """Embedding prologue shared by both pipeline schedules:
    tokens [B, T] -> microbatched activations [M, B/M, T, D]."""
    b, t = tokens.shape
    if b % n_microbatches:
        raise ValueError(f"batch {b} not divisible by "
                         f"{n_microbatches} microbatches")
    with jax.named_scope(scopes.EMBED):
        x = (base["embed"][tokens] +
             base["pos"][None, :t]).astype(cfg.dtype)      # [B, T, D]
    return x.reshape(n_microbatches, b // n_microbatches, t, cfg.d_model)


def _pipe_stage_fn(cfg: TransformerConfig):
    """stage_fn for the pipeline schedules: scan this device's layer
    slice (leaves [1, lps, ...]) over the activation."""
    dt = cfg.dtype

    def one_layer(x, lp):
        with jax.named_scope(scopes.ATTN_QKV):
            q, k, v, dh = attention_mod.qkv_proj(x, lp, cfg, None)
        bb, tt = q.shape[:2]
        o = seq_mod.local_attention(q, k, v, causal=True)
        with jax.named_scope(scopes.ATTN_OUT):
            x = attention_mod.attn_out(o.reshape(bb, tt, dh), x, lp, cfg,
                                       None)
        with jax.named_scope(scopes.MLP):
            x = mlp_mod.mlp_block(x, lp, cfg, None)
        # attention computes in f32; pin the carried activation to the
        # model dtype so the layer scan (and the pipeline's microbatch
        # buffers) keep a stable, bf16-safe type
        return x.astype(dt), None

    def stage_fn(stage_params, act):
        # stage_params leaves: [1, lps, ...] — this device's stage.  A
        # local stage dim > 1 means n_stages exceeded the pipe axis size;
        # silently running only slice 0 would drop layers, so refuse.
        lead = {l.shape[0] for l in
                jax.tree_util.tree_leaves(stage_params)}
        if lead != {1}:
            raise ValueError(
                f"each device must hold exactly one stage; got local "
                f"stage dims {sorted(lead)} — n_stages passed to "
                f"stack_layer_params must equal the pipe axis size")
        local = jax.tree_util.tree_map(lambda l: l[0], stage_params)
        out, _ = lax.scan(one_layer, act, local)
        return out

    return stage_fn


def split_pipeline_params(params, n_stages: int, virtual: int = 1):
    """Re-layout :func:`init_params` output for the pipelined step: the
    one canonical base/stacked split (used by the example and tests).
    ``virtual > 1`` uses the round-robin interleaved chunk layout
    (``n_stages`` is then the PIPE AXIS size, not the chunk count)."""
    base = {k: v for k, v in params.items() if k != "layers"}
    if virtual > 1:
        return {"base": base,
                "stacked": stack_layer_params_interleaved(
                    params, n_stages, virtual)}
    return {"base": base, "stacked": stack_layer_params(params, n_stages)}


@telemetry.span("make_train_step", step=scopes.LM_PIPELINED_TRAIN_STEP)
def make_train_step_pipelined(cfg: TransformerConfig, optimizer, mesh,
                              data_axis: Optional[str] = "data",
                              pipe_axis: str = "pipe",
                              n_microbatches: int = 2,
                              donate: bool = True,
                              schedule: str = "gpipe",
                              virtual: int = 2):
    """Jitted DP x PP training step.

    ``schedule="gpipe"``: differentiation happens OUTSIDE the shard_map
    (jit-of-shard_map): JAX transposes the GPipe schedule (scan +
    ppermute) into the exact backward pipeline, and GSPMD handles the
    data-axis gradient averaging because the loss is a global-batch
    mean — verified exact against the plain forward's gradients
    (tests/test_parallel.py).

    ``schedule="1f1b"``: the hand-scheduled one-forward-one-backward
    pipeline (:func:`horovod_tpu.parallel.pipeline.pipeline_1f1b`) —
    same exact gradients (same oracle), but peak activation state is
    O(pipe) instead of O(n_microbatches) saved microbatches per stage:
    choose it when many microbatches of residuals don't fit HBM.  On a
    lockstep SPMD mesh its bubble is NOT smaller than GPipe's — see
    docs/parallelism.md for the measured comparison.

    ``schedule="interleaved"``: Megatron-style virtual stages
    (:func:`horovod_tpu.parallel.pipeline.pipeline_apply_interleaved`)
    with ``virtual`` round-robin chunks per device — the fill/drain
    bubble divides by ``virtual`` (GPipe-class activation memory;
    params from ``split_pipeline_params(params, P, virtual)``).
    Requires ``n_microbatches % pipe == 0``.

    ``schedule="interleaved_1f1b"``: the FULL Megatron schedule
    (:func:`horovod_tpu.parallel.pipeline.pipeline_1f1b_interleaved`):
    virtual-stage round-robin + hand-scheduled 1F1B with a fwd-packed
    warmup and bwd drain — bubble ÷ v at O(pipe) activation memory
    (2v·P saved chunk inputs).  Same exact gradients; same params
    layout as "interleaved"; requires ``n_microbatches % pipe == 0``
    and ``n_microbatches >= pipe``.

    Params layout: :func:`split_pipeline_params` output
    (``{"base": embed/pos/ln_f (replicated), "stacked":
    stack_layer_params(...) (stage dim over pipe)}``).
    Returns ``(step, shardings)`` where ``step(params, opt_state, tokens,
    labels) -> (params, opt_state, loss)`` and ``shardings(params) ->
    (param_shardings, opt_state_shardings)`` (place both trees).
    """
    from jax.sharding import NamedSharding

    # The pipelined forward embeds with the position table, scans stacked
    # dense layers of one type and returns no router sums.
    _refuse_all_but(cfg, "make_train_step_pipelined", _PIPELINED_FIELDS)
    n_stages = mesh.shape[pipe_axis]
    v_eff = (virtual if schedule in ("interleaved", "interleaved_1f1b")
             else 1)
    if cfg.n_layers % (n_stages * v_eff):
        raise ValueError(f"{cfg.n_layers} layers not divisible over "
                         f"{n_stages * v_eff} pipe chunks")
    sspec_one = stacked_layer_specs(pipe_axis)
    data_spec = P(data_axis) if data_axis else P()

    def smapped(base, stacked, tokens):
        bspec = {k: P() for k in base}
        sspec = {k: sspec_one for k in stacked}
        return jax.shard_map(
            lambda b_, s_, t_: forward_pipelined(
                dict(b_, layers=[]), s_, t_, cfg, pipe_axis,
                n_microbatches, virtual=v_eff),
            mesh=mesh, in_specs=(bspec, sspec, data_spec),
            out_specs=data_spec, check_vma=False)(base, stacked, tokens)

    if schedule in ("1f1b", "interleaved_1f1b"):
        from horovod_tpu.parallel.pipeline import make_pipeline_1f1b_loss

        def head_loss(y, tgt, base):
            return xent(_logits_head(y, base, cfg), tgt)

        # microbatches/targets: [M, mb, T, ...] with the microbatch dim
        # sharded over data (GSPMD reshards the embedded activations once
        # per step; semantics are unchanged — the loss is a global mean).
        mb_spec = P(None, data_axis) if data_axis else P()

        def _loss(params, tokens, labels):
            f = make_pipeline_1f1b_loss(
                _pipe_stage_fn(cfg), head_loss, mesh,
                stage_spec={k: sspec_one for k in params["stacked"]},
                mb_spec=mb_spec,
                aux_spec={k: P() for k in params["base"]},
                axis_name=pipe_axis,
                data_axes=(data_axis,) if data_axis else (),
                virtual=v_eff)
            base = params["base"]
            b, t = tokens.shape
            mb = _embed_microbatches(base, tokens, cfg, n_microbatches)
            tgt = labels.reshape(n_microbatches, b // n_microbatches, t)
            return f(params["stacked"], base, mb, tgt)
    elif schedule in ("gpipe", "interleaved"):
        # Both differentiate through the scanned schedule (jit of
        # shard_map); interleaved just runs the virtual-chunk scan.
        def _loss(params, tokens, labels):
            return xent(smapped(params["base"], params["stacked"], tokens),
                        labels)
    else:
        raise ValueError(f"schedule={schedule!r}: expected 'gpipe', "
                         f"'1f1b', 'interleaved' or 'interleaved_1f1b'")

    def _step(params, opt_state, tokens, labels):
        loss, grads = jax.value_and_grad(_loss)(params, tokens, labels)
        with jax.named_scope(scopes.OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(lambda p, u: p + u, params,
                                            updates)
        return params, opt_state, loss

    def shardings(params):
        """(param_shardings, opt_state_shardings) for ``params``.

        Opt-state momenta inherit the matching param's sharding; scalar
        leaves (schedule counts) are replicated — place BOTH trees before
        training or a checkpoint restore brings scalars back committed
        to one device and jit rejects the mixed placement.
        """
        import optax
        p_sh = {
            "base": {k: NamedSharding(mesh, P()) for k in params["base"]},
            "stacked": {k: NamedSharding(mesh, sspec_one)
                        for k in params["stacked"]},
        }
        o_sh = optax.tree_map_params(
            optimizer, lambda _l, s_: s_,
            jax.eval_shape(optimizer.init, params), p_sh,
            transform_non_params=lambda _l: NamedSharding(mesh, P()))
        return p_sh, o_sh

    step = jax.jit(scopes.named(_step, scopes.LM_PIPELINED_TRAIN_STEP),
                   donate_argnums=(0, 1) if donate else ())
    return step, shardings
