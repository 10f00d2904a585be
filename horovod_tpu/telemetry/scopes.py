"""The names the compiled SPMD step carries: scopes, kernels, modules.

Every ``jax.named_scope(...)`` of the program takes its name from here,
so a trace or ``compiled.as_text()`` of any step reads in one vocabulary
(docs/timeline.md, "The compiled step in XProf/Perfetto").  A scope is
``op_name`` metadata of the HLO and costs nothing at run time; there is
no recorder and no switch.  Metadata is not part of JAX's
compilation-cache key: after renaming a scope, empty the cache or the old
names come back from it.
"""

from __future__ import annotations

# Model scopes.  A layer's parts nest under LAYER % i.
EMBED = "embed"
LAYER = "layer_%d"
ATTN_QKV = "attn/qkv"
ATTN_OUT = "attn/out"
ATTN_LOCAL = "attn/local_attention"
ATTN_FLASH = "attn/flash_attention"
ATTN_RING = "attn/ring_attention"
ATTN_RING_FLASH = "attn/ring_flash_attention"
ATTN_ULYSSES = "attn/ulysses_attention"
MLP = "mlp"
HEAD = "head"
LOSS = "loss"

# The parts of a mixture-of-experts layer (models/moe.py).  They open
# under MLP as bare path components (".../layer_1/mlp/moe_experts/..."),
# so a reader that knows only the model scopes still answers "mlp".
MOE_ROUTER = "moe_router"
MOE_DISPATCH = "moe_dispatch"
MOE_EXPERTS = "moe_experts"
MOE_COMBINE = "moe_combine"

# The two dense parts of a latent mixture of experts with a shared expert
# (models/moe.latent_moe_ffn), beside the four above and opened the same
# way: the projections into and out of the experts' latent width, and the
# shared expert every token passes through.
MOE_LATENT = "moe_latent"
MOE_SHARED = "moe_shared"

# The parts of a gated-delta-rule linear-attention layer
# (models/linear_attention.py).  The recurrence is the layer's route, as
# flash_attention is a full layer's; projections and convolution open
# under ATTN_QKV, the gated norm and the out projection under ATTN_OUT, as
# bare path components (".../layer_0/attn/qkv/gdn_conv/..."), so a reader
# that knows only the model scopes still answers "attn/qkv", "attn/out".
ATTN_GDN_SCAN = "attn/gdn_scan"
GDN_PROJ = "gdn_proj"
GDN_CONV = "gdn_conv"
GDN_GATE_NORM = "gdn_gate_norm"
GDN_OUT = "gdn_out"

# The parts of a Mamba-2 state-space mixer (models/mamba2.py), opened as
# the gated delta rule's are: the recurrence is the layer's route, the
# in-projection and the convolution sit under ATTN_QKV, the gated group
# norm and the out projection under ATTN_OUT.
ATTN_SSM_SCAN = "attn/ssm_scan"
SSM_PROJ = "ssm_proj"
SSM_CONV = "ssm_conv"
SSM_GATE_NORM = "ssm_gate_norm"
SSM_OUT = "ssm_out"

# The parts of a Mamba-1 state-space mixer (models/mamba1.py), opened as
# Mamba-2's are: the selective scan is the layer's route; the norm with
# the in-projection, the convolution and what makes dt, B and C of its
# output (x_proj, the three inner norms, dt_proj, softplus) sit under
# ATTN_QKV; the gate and the out projection under ATTN_OUT.
ATTN_MAMBA_SCAN = "attn/mamba_scan"
MAMBA_PROJ = "mamba_proj"
MAMBA_CONV = "mamba_conv"
MAMBA_DT_BC = "mamba_dt_bc"
MAMBA_GATE = "mamba_gate"
MAMBA_OUT = "mamba_out"

# The parts of latent attention's projections (models/attention.latent_qkv),
# bare components under ATTN_QKV: the query's way through its latent, the
# keys' and values' through theirs, and the rotary part with the
# concatenations that put a head together.
MLA_Q = "mla_q"
MLA_KV = "mla_kv"
MLA_ROPE = "mla_rope"

# The parts of learned sparse attention (ops/sparse_attention.py,
# models/attention.indexer_proj), bare components as latent attention's are.
# Under ATTN_QKV: the indexer's three projections and its rotation, and
# the per-head QK-norm with the rotation of q and k.  The route itself
# opens under ATTN_FLASH (it is the flash kernels' work under a mask, and
# a reader that knows only the model scopes books it there): the indexer's
# scores, the selection (mask and its transpose), the attention kernels
# under the mask (with the loss's gradient, which the dQ kernel writes),
# and the indexer's loss (the rows' KL against the head-mean
# probabilities, forward).
DSA_INDEX_PROJ = "dsa_index_proj"
QK_HEAD_NORM_ROPE = "qk_head_norm_rope"
DSA_INDEX_SCORES = "dsa_index_scores"
DSA_SELECT = "dsa_select"
DSA_FLASH = "dsa_flash"
DSA_INDEX_LOSS = "dsa_index_loss"

# A leading dense MLP in a model whose other layers hold experts: a bare
# component under MLP, so that a reader can tell it from an expert
# layer's norm and residual add, which sit under MLP too.
MLP_DENSE = "mlp_dense"

# The multi-token-prediction module (transformer._mtp_loss): a bare
# component that holds model scopes of its own (".../mtp/embed/...",
# ".../mtp/layer_0/attn/qkv/...", ".../mtp/head/..."), so a reader that
# knows only the model scopes books its parts with the main stack's.
MTP = "mtp"

# Block-diffusion training (transformer.diffusion_loss_fn): the noised
# copy laid beside the clean sequence, the repeated positions and the
# loss's weights; a bare component under EMBED.
DIFFUSION_ASSEMBLE = "diffusion_assemble"

# A stack run several times on the same weights (``TransformerConfig.loops``
# > 1, transformer._hidden_states): LOOP % t around pass ``t``.  A layer's
# scopes nest under it (".../loop_2/layer_0/attn/qkv/..."), so a reader
# that knows only the model scopes still answers "attn/qkv", "mlp".
# LOOP_NORM, right under the loop's scope and under no layer's, is the
# final norm after every pass, whose output is both the readout and the
# next pass's input.
LOOP = "loop_%d"
LOOP_NORM = "loop_norm"

# The second norm of a sandwich-normed branch (``post_norm``), on the
# branch's output before the residual add: a bare component under ATTN_OUT
# and under MLP.
POST_NORM = "post_norm"

# The readouts of a looped stack (transformer.loss_fn): the exit gate's
# pre-activation, a bare component under HEAD; and under LOSS the exit
# distribution, the weighted sum of the passes' cross-entropies and the
# entropy term.
EXIT_GATE = "exit_gate"
EXIT_MIX = "exit_mix"

# The parts of compressed convolutional attention (models/attention.py,
# ``cca_attention``), bare components under ATTN_QKV: CCA_MIX, both causal
# convolutions over the packed q and k, the mean of the pre-convolution q
# and k added back, and the value shift; CCA_NORM_ROPE, the L2 norm a
# head, the keys' temperature, the rotation of a head's first
# ``rotary_dims`` and the head layout.
CCA_MIX = "cca_mix"
CCA_NORM_ROPE = "cca_norm_rope"

# The parts of an MLP router with a state carried from expert layer to
# expert layer (models/moe.py, ``zaya_experts``), bare components under
# MOE_ROUTER: ROUTER_STATE, the down projection plus the previous layer's
# state a channel; ROUTER_MLP, the norm, the MLP, the softmax and the
# choice.  MOE_SKIP, a bare component under MLP beside the four parts
# above: the term of the tokens whose choice is the skip.
ROUTER_STATE = "router_state"
ROUTER_MLP = "router_mlp"
MOE_SKIP = "moe_skip"

# The scaled residual merge (``residual_scaling``, parts.merged): a bare
# component under ATTN_OUT and under MLP, as POST_NORM is.
RES_SCALE = "res_scale"

# Step scopes: what the step does with the gradients.
GRAD_MEAN = "grad_mean"
OPTIMIZER = "optimizer"
GRAD_REDUCE_SCATTER = "grad_reduce_scatter"
PARAM_ALL_GATHER = "param_all_gather"
LOSS_MEAN = "loss_mean"
STEP_GUARD = "step_guard"

# The three flash-attention Pallas kernels.
FLASH_FWD = "flash_fwd"
FLASH_BWD_DQ = "flash_bwd_dq"
FLASH_BWD_DKV = "flash_bwd_dkv"

# The three grouped-matmul Pallas kernels (ops/grouped_matmul.py).
MOE_GMM = "moe_gmm"
MOE_GMM_NT = "moe_gmm_nt"
MOE_TGMM = "moe_tgmm"

# The two Pallas kernels of a share's sum into the tokens
# (ops/moe_rows.py): the layout of the expert rows as tiles a row and the
# sum itself.  They run under MOE_COMBINE (the combine) and under
# MOE_DISPATCH in the backward pass (the gather's gradient is the same
# sum).
MOE_ROW_TILES = "moe_row_tiles"
MOE_ROWS_BACK = "moe_rows_back"

# The Pallas kernel of the sigmoid router's choice on a narrow share
# (ops/router_choice.py); it runs under MOE_ROUTER.
MOE_CHOOSE = "moe_choose"

# The two gated-delta-rule Pallas kernels (ops/gated_delta_rule.py); they
# run under ATTN_GDN_SCAN.
GDN_SCAN_FWD = "gdn_scan_fwd"
GDN_SCAN_BWD = "gdn_scan_bwd"

# The two Mamba-2 scan Pallas kernels (ops/mamba2_scan.py); they run under
# ATTN_SSM_SCAN.
SSM_SCAN_FWD = "ssm_scan_fwd"
SSM_SCAN_BWD = "ssm_scan_bwd"

# The two selective-scan Pallas kernels (ops/selective_scan.py); they run
# under ATTN_MAMBA_SCAN.
MAMBA_SCAN_FWD = "mamba_scan_fwd"
MAMBA_SCAN_BWD = "mamba_scan_bwd"

# The two Pallas kernels of the Mamba-1 gate (ops/mamba_gate.py); they run
# under MAMBA_GATE.
MAMBA_GATE_FWD = "mamba_gate_fwd"
MAMBA_GATE_BWD = "mamba_gate_bwd"

# The two Pallas kernels of latent attention's assembly
# (ops/mla_assemble.py); they run under MLA_ROPE.
MLA_ASSEMBLE_FWD = "mla_assemble_fwd"
MLA_ASSEMBLE_BWD = "mla_assemble_bwd"

# The two Pallas kernels of plain attention's assembly
# (ops/qk_assemble.py); they run under QK_HEAD_NORM_ROPE.
QK_ASSEMBLE_FWD = "qk_assemble_fwd"
QK_ASSEMBLE_BWD = "qk_assemble_bwd"

# The two short-convolution Pallas kernels (ops/short_conv.py); they run
# under GDN_CONV, SSM_CONV and MAMBA_CONV.
SHORT_CONV_FWD = "short_conv_fwd"
SHORT_CONV_BWD = "short_conv_bwd"

# The two gated-norm Pallas kernels (ops/gated_norm.py); they run under
# GDN_GATE_NORM and SSM_GATE_NORM.
GATED_NORM_FWD = "gated_norm_fwd"
GATED_NORM_BWD = "gated_norm_bwd"

# The seven sparse-attention Pallas kernels (ops/sparse_attention.py): the
# indexer's scores and their gradient, the selection, attention under the
# mask (forward, dQ with the scores' cotangent, dK+dV) and the rows' KL
# against the head-mean probabilities.
DSA_INDEX_FWD = "dsa_index_fwd"
DSA_INDEX_BWD = "dsa_index_bwd"
DSA_SELECT_KERNEL = "dsa_select_rows"
DSA_FWD = "dsa_fwd"
DSA_BWD_DQ = "dsa_bwd_dq"
DSA_BWD_DKV = "dsa_bwd_dkv"
DSA_PROBS = "dsa_probs"

# The functions handed to jax.jit: the XLA module is jit_<name>.
LM_TRAIN_STEP = "hvd_lm_train_step"
LM_PIPELINED_TRAIN_STEP = "hvd_lm_pipelined_train_step"
TRAIN_STEP = "hvd_train_step"
# The compile ledger (telemetry/spans.py) gives a program's rows under
# one of these ``role = step``.
STEP_NAMES = frozenset({LM_TRAIN_STEP, LM_PIPELINED_TRAIN_STEP, TRAIN_STEP})


def named(fn, name: str):
    """``fn`` under ``name``, for ``jax.jit`` to name the module by."""
    fn.__name__ = fn.__qualname__ = name
    return fn
