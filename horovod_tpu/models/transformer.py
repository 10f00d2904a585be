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

The block is config-driven (:class:`TransformerConfig`): the defaults are
the GPT-2-style block (learned positions, GELU MLP, tied head); rotary
positions, QK-norm, SwiGLU, an untied head and a dropless
mixture-of-experts MLP (:mod:`horovod_tpu.models.moe`) are fields of the
same config through the same ``forward`` and ``make_train_step`` — OLMoE's
block is ``positions="rope", qk_norm=True, mlp="swiglu",
tie_embeddings=False, n_experts=64, experts_per_token=8``.  ``layer_types``
names each layer's sequence mixer: ``"full_attention"`` (the block above)
or ``"linear_attention"`` (the gated delta rule of
:mod:`horovod_tpu.models.linear_attention`, sized by the ``linear_*``
fields); Olmo-Hybrid is three linear layers to one full one, with
``positions="none"``.  Three more kinds are **one part alone**, with one
norm and one residual add: ``"mamba2"`` (the state-space mixer of
:mod:`horovod_tpu.models.mamba2`, sized by the ``ssm_*`` fields),
``"attention"`` (softmax attention, no MLP) and ``"mlp"`` (the config's
feed-forward part, no mixer).  ``n_kv_heads`` fewer than ``n_heads`` is
grouped-query attention; ``mlp="relu2"`` with experts is the latent
mixture of experts with a shared expert (:func:`moe.latent_moe_ffn`), of
which this chip may hold a share (``experts_held``); ``mtp_layer_types``
adds a multi-token-prediction module and its loss.  Nemotron-3 is
``MEMEMEM*EME`` of those three, repeated.  ``kv_latent_rank`` makes the
softmax attention **latent** (DeepSeek-V2's MLA, in the up-projected form
training runs: :func:`_latent_qkv`), with heads of ``head_width`` that
need not be ``d_model / n_heads`` and a rotary part of ``rope_dim``;
``dense_layers`` leading layers keep a dense MLP of ``d_ff`` before the
expert layers; ``mlp="swiglu"`` with ``d_shared`` is SwiGLU experts under
the sigmoid router beside a shared SwiGLU expert
(:func:`moe.sigmoid_moe_ffn`).  GLM-4.7-Flash is those three together,
with a prediction module of one such layer.  ``head_width`` alone is
plain attention whose heads are not ``d_model / n_heads`` wide,
``qk_norm_per_head`` norms q and k a head at a time, and the ``index_*``
fields put an indexer beside every attention layer that chooses the
``index_topk`` keys each query reads and learns from its own loss
(:mod:`horovod_tpu.ops.sparse_attention`); Keye-VL-2.0's language model is
those three over grouped heads and softmax-routed experts.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import linear_attention, mamba2, moe
from horovod_tpu.ops import sparse_attention
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel import sequence as seq_mod
from horovod_tpu.parallel import tensor as tp
from horovod_tpu.telemetry import scopes


FULL_ATTENTION = "full_attention"
LINEAR_ATTENTION = "linear_attention"
MAMBA2 = "mamba2"
ATTENTION_ONLY = "attention"
MLP_ONLY = "mlp"
LAYER_TYPES = (FULL_ATTENTION, LINEAR_ATTENTION, MAMBA2, ATTENTION_ONLY,
               MLP_ONLY)
# What a layer of each type holds: its sequence mixer and whether the
# config's feed-forward part follows it.
_MIXER = {FULL_ATTENTION: FULL_ATTENTION, LINEAR_ATTENTION: LINEAR_ATTENTION,
          MAMBA2: MAMBA2, ATTENTION_ONLY: FULL_ATTENTION, MLP_ONLY: None}
_HAS_MLP = {FULL_ATTENTION: True, LINEAR_ATTENTION: True, MAMBA2: False,
            ATTENTION_ONLY: False, MLP_ONLY: True}


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
    # One of LAYER_TYPES per layer; empty: "full_attention" everywhere.
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

    def __post_init__(self):
        if self.positions not in ("learned", "rope", "none"):
            raise ValueError(f"positions={self.positions!r}: expected "
                             f"'learned', 'rope' or 'none'")
        linear = (self.linear_key_heads, self.linear_value_heads,
                  self.linear_key_head_dim, self.linear_value_head_dim,
                  self.linear_conv_kernel)
        if self.layer_types:
            unknown = set(self.layer_types) - set(LAYER_TYPES)
            if unknown or len(self.layer_types) != self.n_layers:
                raise ValueError(
                    f"layer_types={self.layer_types!r}: expected n_layers="
                    f"{self.n_layers} entries of {LAYER_TYPES}")
        if self.has_linear_layers:
            if min(linear) <= 0:
                raise ValueError(
                    "a 'linear_attention' layer needs linear_key_heads, "
                    "linear_value_heads, linear_key_head_dim, "
                    "linear_value_head_dim and linear_conv_kernel")
            if self.linear_key_heads != self.linear_value_heads:
                raise NotImplementedError(
                    f"linear_value_heads={self.linear_value_heads} != "
                    f"linear_key_heads={self.linear_key_heads}: value heads "
                    f"that share a key head are not implemented")
        elif any(linear) or self.linear_allow_neg_eigval:
            raise ValueError("the linear_* fields mean nothing without a "
                             "'linear_attention' entry in layer_types")
        if set(self.mtp_layer_types) - set(LAYER_TYPES):
            raise ValueError(f"mtp_layer_types={self.mtp_layer_types!r}: "
                             f"expected entries of {LAYER_TYPES}")
        if bool(self.mtp_layer_types) != bool(self.mtp_loss_coef):
            raise ValueError("mtp_layer_types and mtp_loss_coef come "
                             "together")
        ssm = (self.ssm_heads, self.ssm_head_dim, self.ssm_state,
               self.ssm_groups, self.ssm_conv_kernel, self.ssm_chunk)
        if MAMBA2 in self.layer_types + self.mtp_layer_types:
            if min(ssm) <= 0 or self.ssm_heads % self.ssm_groups:
                raise ValueError(
                    "a 'mamba2' layer needs ssm_heads, ssm_head_dim, "
                    "ssm_state, ssm_groups (a divisor of ssm_heads), "
                    "ssm_conv_kernel and ssm_chunk")
        elif any(ssm):
            raise ValueError("the ssm_* fields mean nothing without a "
                             "'mamba2' entry in layer_types")
        if self.n_kv_heads and self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_kv_heads={self.n_kv_heads} does not "
                             f"divide n_heads={self.n_heads}")
        if self.mlp not in ("gelu", "swiglu", "relu2"):
            raise ValueError(f"mlp={self.mlp!r}: expected 'gelu', "
                             f"'swiglu' or 'relu2'")
        # A head width alone is plain attention with heads of that width.
        latent = (self.q_latent_rank, self.kv_latent_rank, self.rope_dim)
        if any(latent):
            latent += (self.head_width,)
            if (self.q_latent_rank or self.kv_latent_rank) \
                    and not self.head_width:
                raise ValueError(
                    f"q_latent_rank={self.q_latent_rank}, kv_latent_rank="
                    f"{self.kv_latent_rank}: latent attention needs "
                    f"head_width, the width its up-projections give a "
                    f"head")
            if min(latent) <= 0:
                raise ValueError(
                    "q_latent_rank, kv_latent_rank and rope_dim come "
                    "together, with head_width: they are latent attention")
            if self.rope_dim > self.head_width:
                raise ValueError(
                    f"rope_dim={self.rope_dim} is wider than head_width="
                    f"{self.head_width}: the rotary part is the tail of a "
                    f"head")
            if self.positions != "rope" or self.rope_dim % 2:
                raise ValueError(
                    f"latent attention carries position in its rotary "
                    f"part: it needs positions='rope' and an even "
                    f"rope_dim, got {self.positions!r} and "
                    f"{self.rope_dim}")
            if self.qk_norm or self.n_kv_heads or self.qk_norm_per_head:
                raise NotImplementedError(
                    "latent attention norms its latents and gives every "
                    "head its own key: qk_norm, qk_norm_per_head and "
                    "n_kv_heads are not implemented with it")
        if self.qk_norm and self.qk_norm_per_head:
            raise ValueError("qk_norm norms the whole projection, "
                             "qk_norm_per_head each head: one of them")
        sparse = (self.index_heads, self.index_head_dim, self.index_topk,
                  self.indexer_loss_coef)
        if any(sparse):
            if min(sparse) <= 0:
                raise ValueError(
                    "index_heads, index_head_dim, index_topk and "
                    "indexer_loss_coef come together: they are learned "
                    "sparse attention")
            if self.positions != "rope" or self.index_head_dim % 2:
                raise ValueError(
                    f"the indexer's queries and key are rotary: it needs "
                    f"positions='rope' and an even index_head_dim, got "
                    f"{self.positions!r} and {self.index_head_dim}")
            if self.latent_attention:
                raise NotImplementedError(
                    "an indexer beside latent attention (index_heads with "
                    "kv_latent_rank) is not implemented")
        if self.positions == "rope" and self.head_dim % 2:
            raise ValueError(f"positions='rope' needs an even head_dim, "
                             f"got {self.head_dim}")
        if self.mlp == "relu2":
            if (not self.n_experts or self.d_latent <= 0
                    or self.d_shared <= 0):
                raise ValueError("mlp='relu2' is the latent mixture of "
                                 "experts: it needs n_experts, d_latent "
                                 "and d_shared")
            if self.router_aux_coef or self.router_z_coef:
                raise NotImplementedError(
                    "mlp='relu2': the sigmoid router has no auxiliary "
                    "loss (its balance is the selection bias's)")
        elif self.d_latent:
            raise ValueError("d_latent means nothing without mlp='relu2'")
        elif self.d_shared:
            if self.mlp != "swiglu" or not self.n_experts:
                raise ValueError(
                    "d_shared is the shared expert beside routed experts: "
                    "it needs n_experts and mlp='swiglu' (or 'relu2', the "
                    "latent mixture)")
            if (self.router_aux_coef or self.router_z_coef
                    or self.norm_topk_prob):
                raise NotImplementedError(
                    "d_shared with mlp='swiglu' is the sigmoid router: it "
                    "has no auxiliary loss (its balance is the selection "
                    "bias's) and always renormalises its top-k "
                    "(norm_topk_prob is the softmax router's)")
        elif self.routed_scale != 1.0:
            raise ValueError("routed_scale scales the sigmoid router's "
                             "weights: it means nothing without d_shared")
        if self.n_experts:
            if self.mlp == "gelu":
                raise ValueError("n_experts > 0: the experts are SwiGLU "
                                 "(mlp='swiglu') or latent relu^2 "
                                 "(mlp='relu2')")
            if not 0 < self.experts_per_token <= self.n_experts:
                raise ValueError(
                    f"experts_per_token={self.experts_per_token} must lie "
                    f"in 1..n_experts={self.n_experts}")
            if self.d_expert <= 0:
                raise ValueError("n_experts > 0 needs d_expert, one "
                                 "expert's width")
            if not (0 <= self.experts_held_from and
                    self.experts_held_from + self.held_experts
                    <= self.n_experts):
                raise ValueError(
                    f"experts_held={self.experts_held} from "
                    f"{self.experts_held_from} is not a range of the "
                    f"n_experts={self.n_experts}")
            if not 0 <= self.dense_layers <= self.n_layers:
                raise ValueError(
                    f"dense_layers={self.dense_layers} must lie in "
                    f"0..n_layers={self.n_layers}")
            if self.dense_layers and self.mlp != "swiglu":
                raise NotImplementedError(
                    f"dense_layers={self.dense_layers} with mlp="
                    f"{self.mlp!r}: the leading dense MLP is SwiGLU")
        elif (self.experts_per_token or self.d_expert or self.norm_topk_prob
              or self.router_aux_coef or self.router_z_coef
              or self.experts_held or self.experts_held_from
              or self.dense_layers):
            raise ValueError("experts_per_token, d_expert, norm_topk_prob, "
                             "experts_held*, dense_layers and the router "
                             "loss coefficients mean nothing without "
                             "n_experts")

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

    @property
    def has_linear_layers(self) -> bool:
        return LINEAR_ATTENTION in self.layer_types

    @property
    def recurrent_layer_types(self) -> Tuple[str, ...]:
        """The types in use whose state crosses the sequence in order."""
        used = self.layer_types + self.mtp_layer_types
        return tuple(t for t in (LINEAR_ATTENTION, MAMBA2) if t in used)

    def layer_type(self, i: int) -> str:
        return self.layer_types[i] if self.layer_types else FULL_ATTENTION


def _refuse(cfg: TransformerConfig, where: str, fields) -> None:
    """Raise for config fields ``where`` does not implement — never a
    silent fall back to the default block.  ``fields``: names to check
    against the dataclass defaults."""
    defaults = TransformerConfig()
    for name in fields:
        if getattr(cfg, name) != getattr(defaults, name):
            raise NotImplementedError(
                f"{where} does not implement TransformerConfig.{name}="
                f"{getattr(cfg, name)!r}")


def _refuse_with_recurrent_layers(cfg: TransformerConfig,
                                  **arguments) -> None:
    """Raise, by the argument's name, for what the recurrent layers
    (linear attention, Mamba-2) do not implement: a sequence axis (the
    state crosses chunk boundaries in order) and ``segment_ids`` /
    ``packed`` (the state's reset and the convolution's mask at a
    document boundary: ROADMAP R11).  Nor does the multi-token-prediction
    module, whose second target is the next shard's or the next
    document's at such a boundary."""
    kinds = [f"the {kind!r} layers of TransformerConfig.layer_types"
             for kind in cfg.recurrent_layer_types]
    if cfg.mtp_layer_types:
        kinds.append("the multi-token-prediction module "
                     "(TransformerConfig.mtp_layer_types)")
    for name, value in arguments.items():
        if kinds and value is not None and value is not False:
            raise NotImplementedError(
                f"{name}={value!r}: {kinds[0]} do not implement it")


def init_params(rng, cfg: TransformerConfig):
    """GLOBAL-shape parameters; shard with :func:`param_specs` +
    ``jax.device_put`` before use."""
    keys = jax.random.split(rng, 2 + cfg.n_layers)
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    d_kv = cfg.kv_heads * cfg.head_dim

    def dense(key, shape, scale=None):
        scale = scale if scale is not None else (shape[0] ** -0.5)
        return (jax.random.normal(key, shape, jnp.float32) * scale)

    def experts(key, shape):
        # [E, in, out]: each expert a dense matrix of its own fan-in.
        return dense(key, (cfg.held_experts,) + shape,
                     scale=shape[0] ** -0.5)

    def one_layer(key, kind, dense_mlp=False):
        """A layer's leaves: a norm's scale and the weights of each part
        it holds (``dense_mlp``: a dense MLP where the config has
        experts)."""
        k = jax.random.split(key, 6)
        k_up, k_router = jax.random.split(jax.random.fold_in(k[4], 1))
        layer = {}
        if _MIXER[kind]:
            layer["ln1_scale"] = jnp.ones((d,), jnp.float32)
        if _HAS_MLP[kind]:
            layer["ln2_scale"] = jnp.ones((d,), jnp.float32)
        if _MIXER[kind] == LINEAR_ATTENTION:
            layer.update(linear_attention.init_layer(k[0], cfg, dense))
        elif _MIXER[kind] == MAMBA2:
            layer.update(mamba2.init_layer(k[0], cfg, dense))
        elif _MIXER[kind] == FULL_ATTENTION and cfg.latent_attention:
            wide = cfg.n_heads * cfg.head_dim
            r_q, r_kv = cfg.q_latent_rank, cfg.kv_latent_rank
            layer.update(
                w_qa=dense(k[0], (d, r_q)),
                q_latent_norm_scale=jnp.ones((r_q,), jnp.float32),
                w_qb=dense(k[1], (r_q, wide)),
                # To [c_kv | k_r], the latent and the one rotary key.
                w_kva=dense(k[2], (d, r_kv + cfg.rope_dim)),
                kv_latent_norm_scale=jnp.ones((r_kv,), jnp.float32),
                # To [k_n | v] of each head in turn.
                w_kvb=dense(jax.random.fold_in(k[2], 1),
                            (r_kv, 2 * wide - cfg.n_heads * cfg.rope_dim)),
                wo=dense(k[3], (wide, d)))
        elif _MIXER[kind] == FULL_ATTENTION:
            wide = cfg.attn_width
            layer.update(wq=dense(k[0], (d, wide)),
                         wk=dense(k[1], (d, d_kv)),
                         wv=dense(k[2], (d, d_kv)),
                         wo=dense(k[3], (wide, d)))
            if cfg.qk_norm:
                layer["q_norm_scale"] = jnp.ones((d,), jnp.float32)
                layer["k_norm_scale"] = jnp.ones((d_kv,), jnp.float32)
            if cfg.qk_norm_per_head:
                layer["q_norm_scale"] = jnp.ones((cfg.head_dim,),
                                                 jnp.float32)
                layer["k_norm_scale"] = jnp.ones((cfg.head_dim,),
                                                 jnp.float32)
            if cfg.sparse_attention:
                k_index = jax.random.split(jax.random.fold_in(k[0], 1), 3)
                layer.update(
                    index_wq=dense(k_index[0], (
                        d, cfg.index_heads * cfg.index_head_dim)),
                    index_wk=dense(k_index[1], (d, cfg.index_head_dim)),
                    index_ww=dense(k_index[2], (d, cfg.index_heads)))
        if not _HAS_MLP[kind]:
            return layer
        if dense_mlp:
            layer.update(w_gate=dense(k[4], (d, f)),
                         w_up=dense(k_up, (d, f)),
                         w_down=dense(k[5], (f, d)))
        elif cfg.mlp == "relu2":
            e, lat = cfg.d_expert, cfg.d_latent
            k_lat, k_shared = jax.random.split(jax.random.fold_in(k[5], 1))
            layer.update(
                router=dense(k_router, (d, cfg.n_experts)),
                # Chooses and is not trained: its gradient is zero.
                router_bias=jnp.zeros((cfg.n_experts,), jnp.float32),
                w_latent_in=dense(k_lat, (d, lat)),
                w_latent_out=dense(jax.random.fold_in(k_lat, 1), (lat, d)),
                w_up=experts(k_up, (lat, e)),
                w_down=experts(k[5], (e, lat)),
                w_shared_up=dense(k_shared, (d, cfg.d_shared)),
                w_shared_down=dense(jax.random.fold_in(k_shared, 1),
                                    (cfg.d_shared, d)))
        elif cfg.n_experts:
            e = cfg.d_expert
            layer.update(router=dense(k_router, (d, cfg.n_experts)),
                         w_gate=experts(k[4], (d, e)),
                         w_up=experts(k_up, (d, e)),
                         w_down=experts(k[5], (e, d)))
            if cfg.sigmoid_router:
                k_shared = jax.random.split(jax.random.fold_in(k[5], 1), 3)
                layer.update(
                    # Chooses and is not trained: its gradient is zero.
                    router_bias=jnp.zeros((cfg.n_experts,), jnp.float32),
                    w_shared_gate=dense(k_shared[0], (d, cfg.d_shared)),
                    w_shared_up=dense(k_shared[1], (d, cfg.d_shared)),
                    w_shared_down=dense(k_shared[2], (cfg.d_shared, d)))
        elif cfg.mlp == "swiglu":
            layer.update(w_gate=dense(k[4], (d, f)),
                         w_up=dense(k_up, (d, f)),
                         w_down=dense(k[5], (f, d)))
        else:
            layer.update(w1=dense(k[4], (d, f)), w2=dense(k[5], (f, d)))
        return layer

    params = {
        "embed": dense(keys[0], (v, d), scale=0.02),
        "ln_f_scale": jnp.ones((d,), jnp.float32),
        "layers": [one_layer(keys[2 + i], cfg.layer_type(i),
                             i < cfg.dense_layers)
                   for i in range(cfg.n_layers)],
    }
    if cfg.mtp_layer_types:
        k_mtp = jax.random.split(jax.random.fold_in(keys[1], 2),
                                 1 + len(cfg.mtp_layer_types))
        params["mtp"] = {
            "embed_norm_scale": jnp.ones((d,), jnp.float32),
            "hidden_norm_scale": jnp.ones((d,), jnp.float32),
            "w_eh": dense(k_mtp[0], (2 * d, d)),
            "layers": [one_layer(key, kind) for key, kind in
                       zip(k_mtp[1:], cfg.mtp_layer_types)],
            "ln_f_scale": jnp.ones((d,), jnp.float32),
        }
    if cfg.positions == "learned":
        params["pos"] = dense(keys[1], (cfg.max_seq, d), scale=0.02)
    if not cfg.tie_embeddings:
        params["head"] = dense(jax.random.fold_in(keys[1], 1), (d, v))
    return params


def param_specs(cfg: TransformerConfig, model_axis: Optional[str]):
    """PartitionSpec tree matching :func:`init_params` output: Megatron TP
    sharding over ``model_axis`` (column-parallel outputs, row-parallel
    inputs), everything else replicated."""
    m = model_axis
    col = P(None, m)     # split output dim
    row = P(m, None)     # split input dim
    attention = {"wq": col, "wk": col, "wv": col, "wo": row}
    if cfg.qk_norm or cfg.qk_norm_per_head:
        attention.update(q_norm_scale=P(), k_norm_scale=P())
    if cfg.sparse_attention:
        attention.update(index_wq=P(), index_wk=P(), index_ww=P())
    if cfg.latent_attention:
        # Whole on every chip (heads over the model axis: ROADMAP R16).
        attention = {name: P() for name in (
            "w_qa", "q_latent_norm_scale", "w_qb", "w_kva",
            "kv_latent_norm_scale", "w_kvb", "wo")}
    mixers = {FULL_ATTENTION: dict(attention, ln1_scale=P()),
              LINEAR_ATTENTION: dict(linear_attention.layer_specs(),
                                     ln1_scale=P()),
              MAMBA2: dict(mamba2.layer_specs(), ln1_scale=P()),
              None: {}}
    mlp = {"ln2_scale": P()}
    if cfg.mlp == "relu2":
        # The experts this chip holds, whole (the exchange with the chips
        # that hold the others: ROADMAP R2).
        mlp.update({name: P() for name in (
            "router", "router_bias", "w_latent_in", "w_latent_out", "w_up",
            "w_down", "w_shared_up", "w_shared_down")})
    elif cfg.n_experts:
        # Every held expert on every chip of the mesh (experts over an
        # axis: ROADMAP R2).
        mlp.update(router=P(), w_gate=P(), w_up=P(), w_down=P())
        if cfg.sigmoid_router:
            mlp.update({name: P() for name in (
                "router_bias", "w_shared_gate", "w_shared_up",
                "w_shared_down")})
    elif cfg.mlp == "swiglu":
        mlp.update(w_gate=col, w_up=col, w_down=row)
    else:
        mlp.update(w1=col, w2=row)

    # A leading dense MLP in a model with experts: whole, like them.
    dense_mlp = {"ln2_scale": P(), "w_gate": P(), "w_up": P(), "w_down": P()}

    def one_layer(kind, dense=False):
        return dict((dense_mlp if dense else mlp) if _HAS_MLP[kind] else {},
                    **mixers[_MIXER[kind]])

    specs = {
        "embed": P(),
        "ln_f_scale": P(),
        "layers": [one_layer(cfg.layer_type(i), i < cfg.dense_layers)
                   for i in range(cfg.n_layers)],
    }
    if cfg.mtp_layer_types:
        specs["mtp"] = {
            "embed_norm_scale": P(), "hidden_norm_scale": P(), "w_eh": P(),
            "layers": [one_layer(kind) for kind in cfg.mtp_layer_types],
            "ln_f_scale": P()}
    if cfg.positions == "learned":
        specs["pos"] = P()
    if not cfg.tie_embeddings:
        specs["head"] = P()
    return specs


def _rmsnorm(x, scale, eps):
    # Stats in f32; output in the INPUT dtype.  The scale param is f32,
    # and without the cast it silently promoted every rmsnorm output —
    # and therefore every qkv/mlp matmul INPUT — to f32: measured 63.5%
    # -> 72.2% MFU on the d3584/L6 LM config from this one cast (r4).
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)).astype(x.dtype) *
            scale.astype(x.dtype))


def _mlp_block(x, layer, cfg, model_axis):
    """rmsnorm -> dense MLP (gelu, or SwiGLU) -> row-parallel psum ->
    residual (shared by the training forward and the KV-cache decode so
    the two cannot drift)."""
    dt = cfg.dtype
    h = _rmsnorm(x, layer["ln2_scale"], cfg.norm_eps)
    hi = tp.region_input(h, model_axis) if model_axis else h
    if cfg.mlp == "swiglu":
        u = (jax.nn.silu(hi @ layer["w_gate"].astype(dt))
             * (hi @ layer["w_up"].astype(dt)))
        dn = u @ layer["w_down"].astype(dt)
    else:
        u = jax.nn.gelu(hi @ layer["w1"].astype(dt))
        dn = u @ layer["w2"].astype(dt)
    if model_axis:
        dn = lax.psum(dn, model_axis)
    return x + dn


def _moe_block(x, layer, cfg):
    """rmsnorm -> dropless mixture of experts -> residual; also the
    router's sums for the auxiliary losses (None from the latent layer,
    which has none)."""
    h = _rmsnorm(x, layer["ln2_scale"], cfg.norm_eps)
    if cfg.mlp == "relu2":
        return x + moe.latent_moe_ffn(h, layer, cfg)[0], None
    if cfg.sigmoid_router:
        return x + moe.sigmoid_moe_ffn(h, layer, cfg)[0], None
    y, stats = moe.moe_ffn(h, layer, cfg)
    return x + y, stats


def _holds_experts(layer) -> bool:
    """Whether ``layer``'s feed-forward part is the config's experts: in
    a model with ``dense_layers`` the leading layers hold a dense MLP
    instead, and the tree says which (:func:`init_params`)."""
    return "router" in layer


def _rotary(x, positions, theta: float):
    """Rotary embedding of ``x`` [..., T, H, head_dim] at ``positions``
    [T], rotate-half convention (HF ``apply_rotary_pos_emb``): the pair
    (x_i, x_{i + head_dim/2}) turns by ``position * theta^(-2i/head_dim)``.
    Angles and the rotation in float32."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _qkv_proj(x, layer, cfg, model_axis, positions=None, normed=None):
    """rmsnorm -> q/k/v projections -> (QK-norm) -> head split ->
    (rotary at ``positions`` [T]) (shared by forward, decode_step and
    forward_pipelined so the projection math cannot drift).  Returns q,
    k, v with a trailing [heads, head_dim] split.  ``normed``: the normed
    ``x`` where the caller has it already (it hands it to an indexer too)."""
    dt = cfg.dtype
    h = (_rmsnorm(x, layer["ln1_scale"], cfg.norm_eps) if normed is None
         else normed)
    if cfg.latent_attention:
        return _latent_qkv(h, layer, cfg, positions)
    hi = tp.region_input(h, model_axis) if model_axis else h
    q = hi @ layer["wq"].astype(dt)
    k = hi @ layer["wk"].astype(dt)
    v = hi @ layer["wv"].astype(dt)
    if cfg.qk_norm:
        q = _rmsnorm(q, layer["q_norm_scale"], cfg.norm_eps)
        k = _rmsnorm(k, layer["k_norm_scale"], cfg.norm_eps)
    dh = q.shape[-1]

    def heads(a):
        return a.reshape(a.shape[:-1] + (a.shape[-1] // cfg.head_dim,
                                         cfg.head_dim))

    q, k, v = heads(q), heads(k), heads(v)
    # The per-head norm and the rotation after it are a part of their own
    # in a trace; without the norm the rotation is booked as it always was.
    with (jax.named_scope(scopes.QK_HEAD_NORM_ROPE) if cfg.qk_norm_per_head
          else contextlib.nullcontext()):
        if cfg.qk_norm_per_head:
            q = _rmsnorm(q, layer["q_norm_scale"], cfg.norm_eps)
            k = _rmsnorm(k, layer["k_norm_scale"], cfg.norm_eps)
        if cfg.positions == "rope":
            q = _rotary(q, positions, cfg.rope_theta)
            k = _rotary(k, positions, cfg.rope_theta)
    return q, k, v, dh


@jax.named_scope(scopes.DSA_INDEX_PROJ)
def _indexer_proj(u, layer, cfg, positions):
    """The indexer's operands from the layer's normed input ``u`` [B, T, d],
    whose gradient stops here (the indexer learns from its own loss and
    moves nothing else): queries ``[B, T, index_heads, index_head_dim]`` and
    ONE key head ``[B, T, index_head_dim]``, both rotary at ``positions``
    over all their dims, and a weight a head ``[B, T, index_heads]``."""
    dt = cfg.dtype
    u = lax.stop_gradient(u)
    qi = (u @ layer["index_wq"].astype(dt)).reshape(
        u.shape[:-1] + (cfg.index_heads, cfg.index_head_dim))
    ki = (u @ layer["index_wk"].astype(dt))[..., None, :]
    w = u @ layer["index_ww"].astype(dt)
    qi = _rotary(qi, positions, cfg.rope_theta)
    ki = _rotary(ki, positions, cfg.rope_theta)[..., 0, :]
    return qi, ki, w


def _latent_qkv(h, layer, cfg, positions):
    """Latent attention's q, k, v from the normed input ``h`` [..., T, d],
    in the up-projected form (K and V materialised per head; the absorbed
    form and a cache of latents are decode's: ROADMAP R13): ``c_q =
    RMSNorm(h W_qa)``, ``q = c_q W_qb`` as heads of ``[q_n | q_r]``;
    ``[c_kv | k_r] = h W_kva``, ``c_kv <- RMSNorm(c_kv)``, ``[k_n | v] =
    c_kv W_kvb`` per head; ``q_r`` and ``k_r`` rotary at ``positions``,
    ``k_r`` **one head that every head's key ends in** (so its gradient
    sums over the heads); ``k = [k_n | k_r]``.  Returns q, k, v [..., T,
    heads, head_width] and ``heads * head_width``."""
    dt, heads, hd, rope = cfg.dtype, cfg.n_heads, cfg.head_dim, cfg.rope_dim
    nope, rank = hd - rope, cfg.kv_latent_rank
    with jax.named_scope(scopes.MLA_Q):
        c_q = _rmsnorm(h @ layer["w_qa"].astype(dt),
                       layer["q_latent_norm_scale"], cfg.norm_eps)
        q = (c_q @ layer["w_qb"].astype(dt)).reshape(
            h.shape[:-1] + (heads, hd))
    with jax.named_scope(scopes.MLA_KV):
        down = h @ layer["w_kva"].astype(dt)
        c_kv = _rmsnorm(down[..., :rank], layer["kv_latent_norm_scale"],
                        cfg.norm_eps)
        up = (c_kv @ layer["w_kvb"].astype(dt)).reshape(
            h.shape[:-1] + (heads, nope + hd))
        k_n, v = up[..., :nope], up[..., nope:]
    with jax.named_scope(scopes.MLA_ROPE):
        q = jnp.concatenate(
            [q[..., :nope], _rotary(q[..., nope:], positions,
                                    cfg.rope_theta)], axis=-1)
        k_r = _rotary(down[..., None, rank:], positions, cfg.rope_theta)
        k = jnp.concatenate(
            [k_n, jnp.broadcast_to(k_r, k_n.shape[:-1] + (rope,))], axis=-1)
    return q, k, v, heads * hd


def _share_kv_heads(k, v, n_heads: int):
    """Grouped-query attention's K and V as the attention routes take
    them, one head a query head: each key-value head repeated for the
    ``n_heads / kv_heads`` query heads that read it (so dK and dV sum over
    the group); as they are where the counts are equal.  A copy in HBM:
    a kernel that reads head ``h // group`` instead is ROADMAP R3."""
    group = n_heads // k.shape[-2]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=-2), jnp.repeat(v, group, axis=-2)


def _attn_out(o_flat, x, layer, dt, model_axis):
    """Output projection (row-parallel psum under TP) + residual."""
    o = o_flat @ layer["wo"].astype(dt)
    if model_axis:
        o = lax.psum(o, model_axis)
    return x + o


_flash_declined_shapes: set = set()


def _flash_profitable(t: int) -> bool:
    """``attention="auto"``'s flash-vs-lax decision, made at TRACE time
    from the (static) sequence length.  With the kernel's auto block
    sizes (r3 sweep, docs/kernels.md table): measured fwd-only PARITY at
    T=1024 and measured wins from T=2048 up (fwd-only and fwd+bwd), so
    1024 is the safe default threshold — at worst a tie; override with
    HOROVOD_FLASH_AUTO_MIN_T.  Auto also refuses lengths the compiled
    kernel cannot tile (indivisible by the 128-lane block) and falls
    back to the lax path — ``auto`` NEVER raises on shape; only an
    explicit ``attention="flash"`` may (the user asked for the kernel).
    """
    import os
    min_t = int(os.environ.get("HOROVOD_FLASH_AUTO_MIN_T", "1024"))
    if t >= min_t and t % 128 != 0:
        if t not in _flash_declined_shapes:   # one-time per length
            _flash_declined_shapes.add(t)
            import logging
            logging.getLogger("horovod_tpu").debug(
                "attention='auto': T=%d is not divisible by 128; using "
                "the lax attention path (pad the sequence to enable the "
                "flash kernel)", t)
        return False
    return t >= min_t


@jax.named_scope(scopes.HEAD)
def _logits_head(x, params, cfg):
    """Final rmsnorm + projection onto the vocabulary, by the transposed
    embedding or the untied ``head`` (shared fwd/decode)."""
    dt = cfg.dtype
    x = _rmsnorm(x, params["ln_f_scale"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return (x @ w.astype(dt)).astype(jnp.float32)


# Latent attention's fields, for the paths that refuse it by name: the
# tensor axis (heads of a latent's up-projection over chips), the
# sequence axis (the shared rotary key under the ring and Ulysses
# routes), decode_step (a cache of latents, the absorbed form) and the
# pipelined builder.
_LATENT_FIELDS = ("head_width", "q_latent_rank", "kv_latent_rank",
                  "rope_dim")
# Learned sparse attention's, for the same four paths: a query's selected
# keys lie on other chips under a sequence axis, the indexer's key cache
# and a selection per decoded token are not written (ROADMAP R3), and the
# per-head norm's scale is not split with the heads.
_SPARSE_FIELDS = ("index_heads", "index_head_dim", "index_topk",
                  "indexer_loss_coef", "qk_norm_per_head")


def _refuse_under_model_axis(cfg, model_axis) -> None:
    # QK-norm's statistics span the whole projection, which the model
    # axis splits; the experts live whole on every chip (ROADMAP R2), and
    # so do the linear-attention layers' heads.
    if model_axis:
        _refuse(cfg, f"model_axis={model_axis!r}",
                ("qk_norm", "n_experts", "layer_types", "n_kv_heads",
                 "mtp_layer_types") + _LATENT_FIELDS + _SPARSE_FIELDS)


def _refuse_under_seq_axis(cfg, seq_axis) -> None:
    if seq_axis:
        _refuse(cfg, f"seq_axis={seq_axis!r}",
                _LATENT_FIELDS + _SPARSE_FIELDS)


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
    x, router_stats = _hidden_states(params, tokens, cfg, model_axis,
                                     seq_axis, attention, segment_ids,
                                     remat)[:2]
    return _logits_head(x, params, cfg), router_stats


def _hidden_states(params, tokens, cfg: TransformerConfig, model_axis,
                   seq_axis, attention, segment_ids, remat):
    """``(x, router stats, run_layers, index_kl)``: the last layer's
    output before the final norm, one :class:`moe.RouterStats` per
    softmax-routed MoE layer, the function that ran the stack
    (``run_layers(x, layers, types, label)``), for the
    multi-token-prediction module to run its own layers by, and the list
    that every sparse attention layer run so far has put its indexer's
    summed KL in."""
    _refuse_under_model_axis(cfg, model_axis)
    _refuse_under_seq_axis(cfg, seq_axis)
    _refuse_with_recurrent_layers(cfg, seq_axis=seq_axis,
                                  segment_ids=segment_ids)
    if cfg.sparse_attention and segment_ids is not None:
        raise NotImplementedError(
            "segment_ids: learned sparse attention "
            "(TransformerConfig.index_topk) does not implement it: the "
            "selection would have to stay inside a document")
    dt = cfg.dtype
    t_local = tokens.shape[1]
    with jax.named_scope(scopes.EMBED):
        pos_offset = (lax.axis_index(seq_axis) * t_local) if seq_axis else 0
        if cfg.positions == "learned":
            positions = None
            x = (params["embed"][tokens] +
                 lax.dynamic_slice_in_dim(params["pos"], pos_offset,
                                          t_local, axis=0)[None]).astype(dt)
        else:
            positions = pos_offset + jnp.arange(t_local)
            x = params["embed"][tokens].astype(dt)

    def attention_part(x, layer, segment_ids):
        # --- attention block (each route opens its own attn/<route>) ---
        if cfg.sparse_attention:
            # The route of its own: the indexer chooses each query's keys
            # (ops/sparse_attention.py), whatever ``attention`` says.
            with jax.named_scope(scopes.ATTN_QKV):
                u = _rmsnorm(x, layer["ln1_scale"], cfg.norm_eps)
                q, k, v, dh = _qkv_proj(x, layer, cfg, model_axis, positions,
                                        normed=u)
                qi, ki, w = _indexer_proj(u, layer, cfg, positions)
            with jax.named_scope(scopes.ATTN_FLASH):
                o, kl = sparse_attention.dsa_attention(
                    q, k, v, qi, ki, w, topk=cfg.index_topk,
                    index_scale=(cfg.index_heads
                                 * cfg.index_head_dim) ** -0.5)
            with jax.named_scope(scopes.ATTN_OUT):
                return (_attn_out(o.reshape(o.shape[:2] + (dh,)), x, layer,
                                  dt, model_axis), jnp.sum(kl))
        with jax.named_scope(scopes.ATTN_QKV):
            q, k, v, dh = _qkv_proj(x, layer, cfg, model_axis, positions)
        b, t = q.shape[:2]
        flash = seq_axis is None and (
            attention in ("flash", "ring_flash")
            or (attention == "auto" and _flash_profitable(t)))
        with jax.named_scope(scopes.ATTN_FLASH if flash
                             else scopes.ATTN_QKV):
            k, v = _share_kv_heads(k, v, q.shape[-2])
        if seq_axis is not None:
            if attention == "ring_flash" or (attention == "auto" and
                                             _flash_profitable(t)):
                # Ring attention with the flash kernel as the per-step
                # block math: auto upgrades when the LOCAL chunk length
                # clears the kernel's measured crossover.
                o = seq_mod.ring_flash_attention(
                    q, k, v, seq_axis, True, None, None, segment_ids)
            elif attention in ("ring", "auto"):
                o = seq_mod.ring_attention(q, k, v, seq_axis, causal=True,
                                           segment_ids=segment_ids)
            elif attention == "ulysses":
                o = seq_mod.ulysses_attention(q, k, v, seq_axis, causal=True,
                                              segment_ids=segment_ids)
            else:
                # The single-device flash kernel route makes no sense
                # under a sequence axis; K/V blocks arrive over ICI and
                # the blockwise math lives in ring[_flash]_attention.
                # Never silently substitute a different algorithm.
                raise ValueError(
                    f"attention={attention!r} is not available with a "
                    f"sequence axis; choose 'ring', 'ring_flash' or "
                    f"'ulysses'")
        elif flash:
            # Pallas flash kernel (ops/flash_attention.py): same exact
            # math blockwise in VMEM; requires T divisible by its blocks.
            # 'ring_flash' without a seq axis degenerates to exactly
            # this kernel (a 1-ring's only step is the diagonal one) —
            # the user still measures the algorithm they selected.
            o = flash_attention(q, k, v, True, segment_ids=segment_ids)
        else:
            o = seq_mod.local_attention(q, k, v, causal=True,
                                        segment_ids=segment_ids)
        with jax.named_scope(scopes.ATTN_OUT):
            return _attn_out(o.reshape(b, t, dh), x, layer, dt, model_axis)

    def mlp_part(x, layer):
        with jax.named_scope(scopes.MLP):
            if _holds_experts(layer):
                return _moe_block(x, layer, cfg)
            if cfg.n_experts:
                # A leading dense layer of a model with experts.
                with jax.named_scope(scopes.MLP_DENSE):
                    return _mlp_block(x, layer, cfg, model_axis), None
            return _mlp_block(x, layer, cfg, model_axis), None

    def linear_attention_part(x, layer, segment_ids):
        # The mixer opens its own scopes (attn/qkv/gdn_*, attn/gdn_scan,
        # attn/out/gdn_*); the norm is booked with its projections and
        # the residual add with the out projection.
        with jax.named_scope(scopes.ATTN_QKV), \
                jax.named_scope(scopes.GDN_PROJ):
            h = _rmsnorm(x, layer["ln1_scale"], cfg.norm_eps)
        y = linear_attention.mixer(h, layer, cfg)
        with jax.named_scope(scopes.ATTN_OUT), \
                jax.named_scope(scopes.GDN_OUT):
            return x + y

    def mamba2_part(x, layer, segment_ids):
        # As the linear mixer: attn/qkv/ssm_*, attn/ssm_scan,
        # attn/out/ssm_*.
        with jax.named_scope(scopes.ATTN_QKV), \
                jax.named_scope(scopes.SSM_PROJ):
            h = _rmsnorm(x, layer["ln1_scale"], cfg.norm_eps)
        y = mamba2.mixer(h, layer, cfg)
        with jax.named_scope(scopes.ATTN_OUT), \
                jax.named_scope(scopes.SSM_OUT):
            return x + y

    mixers = {FULL_ATTENTION: _remat_wrap(attention_part, remat),
              LINEAR_ATTENTION: _remat_wrap(linear_attention_part, remat),
              MAMBA2: _remat_wrap(mamba2_part, remat)}
    mlp_part = _remat_wrap(mlp_part, remat)
    router_stats, index_kl = [], []

    def run_layers(x, layers, types, label="%d"):
        """``x`` through ``layers`` of ``types``; ``label % i`` names
        layer ``i`` in the trace-time series."""
        for i, (layer, kind) in enumerate(zip(layers, types)):
            mixer, name = _MIXER[kind], label % i
            with jax.named_scope(scopes.LAYER % i):
                if mixer:
                    x = mixers[mixer](x, layer, segment_ids)
                if mixer == FULL_ATTENTION and cfg.sparse_attention:
                    x, kl = x
                    index_kl.append(kl)
                if _HAS_MLP[kind]:
                    x, stats = mlp_part(x, layer)
            if mixer == LINEAR_ATTENTION:
                linear_attention.record_blocks(name, x, cfg)
            if mixer == MAMBA2:
                mamba2.record_chunks(name, x, cfg)
            if mixer == FULL_ATTENTION and cfg.sparse_attention:
                sparse_attention.record_path(sparse_attention.path(x))
            if _HAS_MLP[kind] and _holds_experts(layer):
                moe.record_held(name, tokens.size, cfg)
                moe.record_weight_copies(name, layer)
                if stats is not None:
                    router_stats.append(stats)
                if cfg.held_experts == cfg.n_experts:
                    # What lands on a share is data.
                    moe.record_assignments(
                        name, tokens.size * cfg.experts_per_token,
                        cfg.n_experts)
        return x

    x = run_layers(x, params["layers"],
                   [cfg.layer_type(i) for i in range(cfg.n_layers)])
    return x, router_stats, run_layers, index_kl


@jax.named_scope(scopes.LOSS)
def xent(logits, labels, counted=None):
    """Mean next-token cross-entropy (the one loss formula — shared by
    the plain and pipelined training steps and the oracle tests), over
    the positions where ``counted`` (bool, broadcast against ``labels``)
    holds; over all of them without it."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
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
        h = run_layers(h, mtp["layers"], cfg.mtp_layer_types, "mtp_%d")
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
    batch's loss (:func:`moe.router_losses`)."""
    x, router_stats, run_layers, index_kl = _hidden_states(
        params, tokens, cfg, model_axis, seq_axis, attention, segment_ids,
        remat)
    loss = xent(_logits_head(x, params, cfg), labels)
    if cfg.mtp_layer_types:
        ahead = _mtp_loss(params, x, labels, cfg, run_layers)
        with jax.named_scope(scopes.LOSS):
            loss = loss + cfg.mtp_loss_coef * ahead
    if index_kl:
        # Every sparse layer run, the prediction module's included: the
        # mean over this shard's tokens of each, summed over layers.
        with jax.named_scope(scopes.LOSS):
            loss = loss + cfg.indexer_loss_coef * (
                sum(index_kl) / tokens.size)
    if router_stats:
        with jax.named_scope(scopes.LOSS):
            balance, z = moe.router_losses(
                router_stats, tokens.size * len(router_stats), batch_axes)
            loss = (loss + cfg.router_aux_coef * balance
                    + cfg.router_z_coef * z)
    return loss


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
    including the sequence-parallel ones (see :func:`forward`).

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

    _refuse_under_model_axis(cfg, model_axis)
    _refuse_under_seq_axis(cfg, seq_axis)
    _refuse_with_recurrent_layers(cfg, seq_axis=seq_axis, packed=packed)
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

    def _one_step(params, opt_state, tokens, labels, segment_ids=None):
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
        loss, grads = jax.value_and_grad(loss_fn)(
            local_params, tokens, labels, cfg, model_axis, seq_axis,
            attention, segment_ids, remat, grad_axes)

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

    if steps_per_call > 1:
        def _step(params, opt_state, tokens, labels, segment_ids=None):
            def body(carry, _):
                p, o = carry
                p, o, loss = _one_step(p, o, tokens, labels, segment_ids)
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
    in_specs = (specs, opt_specs, data_spec, data_spec)
    if packed:
        in_specs = in_specs + (data_spec,)
    step = jax.shard_map(
        _step, mesh=mesh,
        in_specs=in_specs,
        out_specs=(specs, opt_specs, P()),
        # The ZeRO path's axis_index-dependent slicing + psum_scatter do
        # not type under the vma checker, nor do the expert layer's
        # grouped-matmul kernels in the Pallas interpreter (their index
        # maps read arrays that vary over the batch axes,
        # ops/grouped_matmul.py); the plain dense path keeps it on.
        check_vma=zopt is None and not cfg.n_experts)
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
    _refuse(cfg, "decode_step", _LATENT_FIELDS + _SPARSE_FIELDS + (
        "positions", "n_experts", "layer_types", "n_kv_heads",
        "mtp_layer_types"))
    dt = cfg.dtype
    hd = cfg.head_dim
    x = (params["embed"][token] +
         lax.dynamic_slice_in_dim(params["pos"], pos, 1, axis=0)[0]
         ).astype(dt)                                    # [B, D]
    new_cache = []
    for layer, c in zip(params["layers"], cache):
        q, k, v, dh = _qkv_proj(x, layer, cfg, model_axis)
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
        x = _attn_out(o.reshape(b, dh), x, layer, dt, model_axis)
        x = _mlp_block(x, layer, cfg, model_axis)
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
            q, k, v, dh = _qkv_proj(x, lp, cfg, None)
        bb, tt = q.shape[:2]
        o = seq_mod.local_attention(q, k, v, causal=True)
        with jax.named_scope(scopes.ATTN_OUT):
            x = _attn_out(o.reshape(bb, tt, dh), x, lp, dt, None)
        with jax.named_scope(scopes.MLP):
            x = _mlp_block(x, lp, cfg, None)
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
    _refuse(cfg, "make_train_step_pipelined",
            _LATENT_FIELDS + _SPARSE_FIELDS + (
        "positions", "qk_norm", "tie_embeddings", "mlp", "n_experts",
        "layer_types", "n_kv_heads", "mtp_layer_types"))
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
