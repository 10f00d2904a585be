"""Gated-delta-rule linear attention, the sequence mixer of a
``"linear_attention"`` layer: what :mod:`horovod_tpu.models.transformer`
runs in place of softmax attention where ``TransformerConfig.layer_types``
says so (Olmo-Hybrid, Qwen3-Next and the other ``linear_*`` configs).

The layer, as Gated DeltaNet (arXiv:2412.06464) and the public
``linear_attention`` layer of HF transformers state it; per head,
``x`` the normed input::

    [q; k; v] = silu(causal_depthwise_conv1d([Wq x; Wk x; Wv x]))
    q = l2norm(q) * d_k ** -0.5,  k = l2norm(k)
    beta = sigmoid(Wb x) * (2 if allow_neg_eigval else 1)
    g = -exp(A_log) * softplus(Wa x + dt_bias)            # alpha = exp(g)
    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t
    y = Wo (RMSNorm(o_t) * silu(Wz x))

**The recurrence runs in chunked form**, never token by token: in a
block of :data:`BLOCK` tokens with ``b_i`` the running sum of ``g`` from
the block's start, ``u_i = beta_i (v_i - alpha_i S_{i-1}^T k_i)`` solves
the unit-lower-triangular system

    (I + N) U = diag(beta) (V - diag(exp b) K S_0),
    N_ij = beta_i exp(b_i - b_j) (k_i . k_j)  for j < i,

so with ``T = (I + N)^-1 diag(beta)``: ``U = T V - (T diag(exp b) K)
S_0`` (the WY/UT form), ``O = diag(exp b) Q S_0 + (Q K^T * exp(b_i -
b_j), j <= i) U`` and ``S_C = exp(b_C) S_0 + (diag(exp(b_C - b)) K)^T U``.
Every decay is ``exp`` of a difference that is ``<= 0``: nothing is
divided, so an ``alpha`` near 0 underflows to an exact zero and never to
``inf``.

**What runs it** is read from the operand (:func:`recurrence_path`), not
set by anyone: the Pallas kernels of
:mod:`horovod_tpu.ops.gated_delta_rule` wherever they can run (compiled
where the mesh that executes the step is TPU, in the Pallas interpreter
elsewhere), with a head's state in VMEM over all of its blocks and
nothing of a block but its inputs and its output in HBM; else
:func:`gated_delta_rule` below, the same algorithm as ``jax.numpy`` that
XLA compiles: everything that does not need ``S_0`` (``N``, the inverse,
``T V``, ``T diag(exp b) K``, the masked ``Q K^T``) for all blocks at
once through HBM, then a :func:`jax.lax.scan` over the blocks that
carries ``S`` and does three small matmuls a block.  It is the kernels'
oracle in the tests, and what runs where they cannot: a sequence length
that does not cut into their tiles, and, on the CPU, inside
``shard_map(check_vma=True)`` (the training step's), where the
interpreter's loops do not type.  ``hvd_gdn_blocks_total{path}`` says
which was traced.

**The short convolution**, ``silu`` and the normalisation of q and k are
one pass over each projection's output: the Pallas kernels of
:mod:`horovod_tpu.ops.short_conv` wherever they can run
(:func:`conv_path`), which read the model dtype once, keep float32 from
the taps to the norm and write q, k and v once, head-major, as the
recurrence's kernels read them; else :func:`causal_conv` and what follows
it as ``jax.numpy``, the kernels' oracle in the tests.
``hvd_short_conv_rows_total{path}`` says which was traced.

**The gated norm** between the recurrence and the out projection is one
pass likewise: the Pallas kernels of :mod:`horovod_tpu.ops.gated_norm`
wherever they can run (:func:`norm_path`), which read ``o`` as the
recurrence left it (head-major from its kernels behind the convolution's,
and then no transpose of ``o`` is made, forward or backward) and ``z``
once and write the out projection's operand token-major once; else
:func:`gated_norm` below, the same lines as ``jax.numpy``.
``hvd_gated_norm_rows_total{path}`` says which was traced.

Precision, of both: ``g``, its running sums, the decays, ``N``, the
inverse (its matmuls at precision ``highest``) and the carried state are
float32; every other matmul takes operands in the model dtype (``T``,
the state and ``U`` rounded to it where they are operands) and
accumulates in float32.  Between its three phases the ``jax.numpy`` form
stores ``T V``, ``U`` and the part of the output that comes from ``S_0``
in the model dtype; the kernels keep them in float32 on the chip.

Backward: the inverse has its own rule in both (``d(A^-1) = -A^-1 dA
A^-1``: two matmuls, where the chain of squarings would keep a dozen
``[BLOCK, BLOCK]`` matrices a block).  What the backward keeps of the
recurrence is the float32 state at each block's start
(:func:`saved_state_bytes`) in both: the kernels' forward writes them
when it runs under differentiation and their backward kernel recomputes
a block from its inputs and its state; the ``jax.numpy`` form is
differentiated through, with the scan's body recomputed.

Not here: ``segment_ids`` (the state's reset at a document boundary and
the convolution's mask: ROADMAP R11), a model or sequence axis, decode.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu import telemetry
from horovod_tpu.models import parts
from horovod_tpu.ops import gated_delta_rule as kernels
from horovod_tpu.ops import gated_norm as norm_kernels
from horovod_tpu.ops import short_conv
from horovod_tpu.ops.gated_delta_rule import BLOCK
from horovod_tpu.parallel._vma import pin_to, vma_of
from horovod_tpu.telemetry import scopes

# The published initialisation of the gates (HF ``linear_attention``
# layer, Mamba-2's): A ~ U(0, 16) and the step dt log-uniform in
# [0.001, 0.1], stored as log A and softplus^-1(dt).
A_INIT_RANGE = (1e-3, 16.0)
DT_INIT_RANGE = (1e-3, 0.1)
L2NORM_EPS = 1e-6


def qkv_widths(cfg):
    """Channels of q, k and v: what the convolution runs over."""
    qk = cfg.linear_key_heads * cfg.linear_key_head_dim
    return qk, qk, cfg.linear_value_heads * cfg.linear_value_head_dim


def init_layer(key, cfg, dense):
    """The mixer's leaves of one linear layer; ``dense(key, shape)`` is
    the caller's matrix initialiser."""
    d, h, dv = cfg.d_model, cfg.linear_value_heads, cfg.linear_value_head_dim
    wq, wk, wv = qkv_widths(cfg)
    k = jax.random.split(key, 10)
    a = jax.random.uniform(k[7], (h,), jnp.float32, *A_INIT_RANGE)
    dt = jnp.exp(jax.random.uniform(
        k[8], (h,), jnp.float32, *(math.log(x) for x in DT_INIT_RANGE)))
    bound = cfg.linear_conv_kernel ** -0.5      # torch's Conv1d, fan-in K
    return {
        "lin_wq": dense(k[0], (d, wq)), "lin_wk": dense(k[1], (d, wk)),
        "lin_wv": dense(k[2], (d, wv)), "lin_wz": dense(k[3], (d, wv)),
        "lin_wa": dense(k[4], (d, h)), "lin_wb": dense(k[5], (d, h)),
        "lin_conv": jax.random.uniform(
            k[6], (cfg.linear_conv_kernel, wq + wk + wv), jnp.float32,
            -bound, bound),
        "lin_a_log": jnp.log(a),
        # softplus^-1(dt) = dt + log(1 - exp(-dt))
        "lin_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "lin_norm_scale": jnp.ones((dv,), jnp.float32),
        "lin_wo": dense(k[9], (wv, d)),
    }


LEAVES = ("lin_wq", "lin_wk", "lin_wv", "lin_wz", "lin_wa", "lin_wb",
          "lin_conv", "lin_a_log", "lin_dt_bias", "lin_norm_scale", "lin_wo")


def causal_conv(x, w):
    """Depthwise causal convolution along T: ``x`` [B, T, C], ``w``
    [K, C]; ``y_t = sum_j w_j x_{t-K+1+j}`` with zeros before the
    sequence's start.  ``K`` shifted multiply-adds in float32, returned
    in float32: what follows (``silu``, the per-head normalisation) reads
    it unrounded, and the model dtype comes back once, at their end.

    The oracle of :mod:`horovod_tpu.ops.short_conv` (same operations, same
    order, same one rounding) and what both recurrent mixers run where its
    kernels cannot (``short_conv.takes``): as ``jax.numpy`` it pads
    ``x`` to ``T + K - 1`` rows and keeps float32 ``[T, C]`` arrays
    between its steps and for the backward."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[j] * lax.dynamic_slice_in_dim(padded, j, t, axis=1)
               .astype(jnp.float32) for j in range(taps))


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2NORM_EPS)


@jax.custom_vjp
def _unit_lower_inverse(n):
    """``(I + n)^-1`` for strictly lower-triangular ``n`` [..., C, C],
    float32.  ``n`` is nilpotent (``n^C = 0``), so the Neumann series
    ends and factors into ``log2 C`` products: ``(I - n)(I + n^2)(I +
    n^4)...``: a dozen [C, C] matmuls and no loop over rows."""
    size = n.shape[-1]
    eye = jnp.eye(size, dtype=n.dtype)

    def mm(a, b):
        return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)

    inverse, power, reach = eye - n, n, 2
    while reach < size:
        power = mm(power, power)
        inverse = inverse + mm(inverse, power)
        reach *= 2
    return inverse


def _unit_lower_inverse_fwd(n):
    inverse = _unit_lower_inverse(n)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, g):
    t = jnp.swapaxes(inverse, -1, -2)
    hi = lax.Precision.HIGHEST
    d = -jnp.matmul(jnp.matmul(t, g, precision=hi), t, precision=hi)
    return (jnp.tril(d, -1),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _mm(spec, a, b, dtype):
    """einsum of operands in ``dtype``, accumulated in float32."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def gated_delta_rule(q, k, v, g, beta, dtype):
    """The recurrence of the module's docstring from ``S_0 = 0`` as
    ``jax.numpy`` (the kernels' oracle, and what runs where they cannot:
    :func:`recurrence_path`): ``q``,
    ``k`` [B, T, H, d_k] (normalised, ``q`` scaled), ``v`` [B, T, H,
    d_v], ``g`` (``log alpha <= 0``) and ``beta`` [B, T, H] float32 ->
    ``o`` [B, T, H, d_v] in ``dtype``.  ``T`` a multiple of
    :data:`BLOCK`."""
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    if t % BLOCK:
        raise ValueError(f"linear attention: sequence length {t} is not a "
                         f"multiple of the recurrence's block of {BLOCK}")
    n = t // BLOCK

    def blocks(x):       # [B, T, H, ...] -> [B, H, n, BLOCK, ...]
        x = x.reshape((bsz, n, BLOCK, h) + x.shape[3:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v = blocks(q), blocks(k), blocks(v)
    beta = blocks(beta.astype(jnp.float32))
    b = jnp.cumsum(blocks(g.astype(jnp.float32)), axis=-1)
    rows = jnp.arange(BLOCK)
    lower = rows[:, None] >= rows[None, :]
    diff = b[..., :, None] - b[..., None, :]
    # exp(b_i - b_j) for j <= i, else 0 (masked before the exp: above the
    # diagonal the difference is positive and may overflow).
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    strict = jnp.where(rows[:, None] > rows[None, :], decay, 0.0)
    kk = _mm("bhnid,bhnjd->bhnij", k, k, dtype)
    solve = _unit_lower_inverse(beta[..., None] * strict * kk)
    solve = solve * beta[..., None, :]                     # T
    from_start = jnp.exp(b)[..., None]
    u0 = _mm("bhnij,bhnjv->bhniv", solve, v, dtype)
    w = _mm("bhnij,bhnjd->bhnid", solve, from_start * k, dtype)
    to_end = jnp.exp(b[..., -1:] - b)[..., None]
    xs = (w.astype(dtype), u0.astype(dtype), (to_end * k).astype(dtype),
          (from_start * q).astype(dtype), jnp.exp(b[..., -1]))

    @jax.checkpoint
    def block(state, x):
        w_c, u0_c, k_end, q_start, carry = x
        u = u0_c - _mm("bhid,bhdv->bhiv", w_c, state, dtype)
        across = _mm("bhid,bhdv->bhiv", q_start, state, dtype)
        state = (carry[..., None, None] * state
                 + _mm("bhid,bhiv->bhdv", k_end, u, dtype))
        return state, (u.astype(dtype), across.astype(dtype))

    # Blocks lead: the scan walks axis 0.
    xs = jax.tree_util.tree_map(lambda x: jnp.moveaxis(x, 2, 0), xs)
    # Inside shard_map the state varies over the axes its inputs do.
    state = pin_to(vma_of(u0) | vma_of(w))(
        jnp.zeros((bsz, h, dk, dv), jnp.float32))
    _, (u, across) = lax.scan(block, state, xs)
    u, across = jnp.moveaxis(u, 0, 2), jnp.moveaxis(across, 0, 2)
    within = _mm("bhnid,bhnjd->bhnij", q, k, dtype) * decay
    o = (across + _mm("bhnij,bhnjv->bhniv", within, u, dtype)).astype(dtype)
    return jnp.moveaxis(o, 1, 3).reshape(bsz, t, h, dv)


def recurrence_path(x) -> str:
    """What runs the recurrence over ``x`` [B, T, ...], read from ``x``
    alone: ``"kernel"``, the Pallas kernels of
    :mod:`horovod_tpu.ops.gated_delta_rule`, compiled where the mesh that
    executes ``x`` is TPU and interpreted elsewhere; ``"xla"``,
    :func:`gated_delta_rule`, where the kernels cannot run (a length that
    does not cut into their tiles; the interpreter inside
    ``shard_map(check_vma=True)``: ``gated_delta_rule.takes``)."""
    return "kernel" if kernels.takes(x) else "xla"


def conv_path(x, cfg) -> str:
    """What runs the three short convolutions of a layer of ``cfg`` over
    projections of ``x`` [B, T, d], read as :func:`recurrence_path` reads
    its: ``"kernel"``, the Pallas kernels of
    :mod:`horovod_tpu.ops.short_conv` (convolution, ``silu`` and a head's
    normalisation in one pass, written head-major); ``"xla"``,
    :func:`causal_conv` and what follows it as ``jax.numpy``, where they
    cannot run (``short_conv.takes``)."""
    h, dk, dv = (cfg.linear_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    return "kernel" if all(
        short_conv.takes(x, cfg.linear_conv_kernel, head_dim=d,
                         channels=h * d) for d in (dk, dv)) else "xla"


def _o_head_major(x, cfg) -> bool:
    """Whether the recurrence leaves ``o`` head-major [B * H, T, d_v]:
    from its kernels on the operands the convolution's kernels wrote."""
    return conv_path(x, cfg) == "kernel" and recurrence_path(x) == "kernel"


def gated_norm(o, z, scale, eps: float):
    """``RMSNorm(o) * silu(z)`` of the module's docstring as
    ``jax.numpy``: ``o`` [B, T, H, d_v] and ``z`` [B, T, H d_v] in the
    model dtype, ``scale`` [d_v] -> [B, T, H d_v] in that dtype, float32
    to the one rounding.  The oracle of
    :mod:`horovod_tpu.ops.gated_norm`'s norm-first form and what runs
    where its kernels cannot (:func:`norm_path`)."""
    o = o.astype(jnp.float32)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * scale
    return (o.reshape(z.shape)
            * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)


def norm_path(x, cfg) -> str:
    """What runs the gated norm of a layer of ``cfg`` over the
    recurrence's output for ``x`` [B, T, d], read as
    :func:`recurrence_path` reads its: ``"kernel"``, the Pallas kernels of
    :mod:`horovod_tpu.ops.gated_norm` (on ``o`` head-major or token-major,
    as the recurrence leaves it); ``"xla"``, :func:`gated_norm`, where
    they cannot run (``gated_norm.takes``)."""
    h, dv = cfg.linear_value_heads, cfg.linear_value_head_dim
    return "kernel" if norm_kernels.takes(
        x, dv, width=h * dv, head_major=_o_head_major(x, cfg)) else "xla"


def saved_state_bytes(batch: int, t: int, cfg) -> int:
    """Bytes of block states the backward of one layer's recurrence
    keeps: the float32 state at the start of each block (the block is
    recomputed from it), whichever path runs."""
    return (batch * (t // BLOCK) * cfg.linear_value_heads
            * cfg.linear_key_head_dim * cfg.linear_value_head_dim * 4)


def mixer(x, layer, cfg):
    """The whole mixer on the normed ``x`` [B, T, d] -> [B, T, d] (the
    caller adds the residual).  Opens its parts as bare components under
    ``attn/qkv`` and ``attn/out`` and the recurrence as a route of its
    own (``telemetry/scopes.py``)."""
    dt = cfg.dtype
    bsz, t, _ = x.shape
    h, dk, dv = (cfg.linear_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    wq, wk, _ = qkv_widths(cfg)
    with jax.named_scope(scopes.ATTN_QKV):
        with jax.named_scope(scopes.GDN_PROJ):
            q = x @ layer["lin_wq"].astype(dt)
            k = x @ layer["lin_wk"].astype(dt)
            v = x @ layer["lin_wv"].astype(dt)
            z = x @ layer["lin_wz"].astype(dt)
            # The gates leave their matmuls in float32: [B, T, H] each.
            a = jnp.matmul(x, layer["lin_wa"].astype(dt),
                           preferred_element_type=jnp.float32)
            beta = jax.nn.sigmoid(jnp.matmul(
                x, layer["lin_wb"].astype(dt),
                preferred_element_type=jnp.float32))
            if cfg.linear_allow_neg_eigval:
                beta = 2.0 * beta
            g = -jnp.exp(layer["lin_a_log"]) * jax.nn.softplus(
                a + layer["lin_dt_bias"])
        with jax.named_scope(scopes.GDN_CONV):
            conv = layer["lin_conv"]
            conv = conv[:, :wq], conv[:, wq:wq + wk], conv[:, wq + wk:]
            head_major = conv_path(x, cfg) == "kernel"
            if head_major:
                # [B * H, T, d]: what the recurrence's kernels read.
                q = short_conv.short_conv(q, conv[0], head_dim=dk,
                                          norm_scale=dk ** -0.5,
                                          eps=L2NORM_EPS)
                k = short_conv.short_conv(k, conv[1], head_dim=dk,
                                          norm_scale=1.0, eps=L2NORM_EPS)
                v = short_conv.short_conv(v, conv[2], head_dim=dv)
            else:
                q, k, v = (jax.nn.silu(causal_conv(a, w))
                           for a, w in zip((q, k, v), conv))
                q = (_l2norm(q.reshape(bsz, t, h, dk))
                     * dk ** -0.5).astype(dt)
                k = _l2norm(k.reshape(bsz, t, h, dk)).astype(dt)
                v = v.reshape(bsz, t, h, dv).astype(dt)
    # ``o`` stays as the recurrence's kernels leave it, [B * H, T, d_v],
    # where the norm's kernels read it.
    o_head_major = _o_head_major(x, cfg)
    norm_kernel = norm_path(x, cfg) == "kernel"
    with jax.named_scope(scopes.ATTN_GDN_SCAN):
        if recurrence_path(x) != "kernel":
            if head_major:
                q, k, v = (kernels.token_major(a, bsz) for a in (q, k, v))
            o = gated_delta_rule(q, k, v, g, beta, dt)
        elif head_major:
            o = kernels.gated_delta_rule_head_major(q, k, v, g, beta)
            if not norm_kernel:
                o = kernels.token_major(o, bsz)
        else:
            o = kernels.gated_delta_rule(q, k, v, g, beta)
    with jax.named_scope(scopes.ATTN_OUT):
        with jax.named_scope(scopes.GDN_GATE_NORM):
            if norm_kernel:
                o = norm_kernels.gated_norm(
                    o if o_head_major else o.reshape(bsz, t, h * dv), z,
                    layer["lin_norm_scale"], group=dv, gate_first=False,
                    head_major=o_head_major, eps=cfg.norm_eps)
            else:
                o = gated_norm(o, z, layer["lin_norm_scale"], cfg.norm_eps)
        with jax.named_scope(scopes.GDN_OUT):
            return o @ layer["lin_wo"].astype(dt)


def record_blocks(layer: int, x, cfg) -> None:
    """Trace-time series (what was compiled into the step, like
    ``hvd_moe_assignments_total``): the blocks of the recurrence layer
    ``layer`` walks per step on one device over the batch and heads of
    its input ``x`` [B, T, d], by what runs them
    (:func:`recurrence_path`), and the bytes of block states its backward
    keeps."""
    if not telemetry.enabled():
        return
    batch, t = x.shape[:2]
    telemetry.counter(
        "hvd_gdn_blocks_total",
        "Blocks of the chunked gated-delta-rule recurrence the traced "
        "linear-attention layer computes per step on one device (batch x "
        "heads x T / block), by what runs them (path: kernel | xla)",
        layer=str(layer), path=recurrence_path(x)).inc(
            batch * cfg.linear_value_heads * (t // BLOCK))
    telemetry.gauge(
        "hvd_gdn_saved_state_bytes",
        "Bytes of block states the backward pass of the traced "
        "linear-attention layer's recurrence keeps (0 = it recomputes "
        "them)",
        layer=str(layer)).set(saved_state_bytes(batch, t, cfg))
    short_conv.record_rows(layer, 3 * batch * t, conv_path(x, cfg))
    norm_kernels.record_rows(layer, batch * t, norm_path(x, cfg))


# --- the mixer as a part (models/parts.py) ----------------------------------

_FIELDS = ("linear_key_heads", "linear_value_heads", "linear_key_head_dim",
           "linear_value_head_dim", "linear_conv_kernel",
           "linear_allow_neg_eigval")


def _validate(cfg, used):
    sizes = tuple(getattr(cfg, name) for name in _FIELDS[:5])
    if not used:
        if any(sizes) or cfg.linear_allow_neg_eigval:
            raise ValueError("the linear_* fields mean nothing without a "
                             "'linear_attention' entry in layer_types")
        return
    if min(sizes) <= 0:
        raise ValueError(
            "a 'linear_attention' layer needs linear_key_heads, "
            "linear_value_heads, linear_key_head_dim, linear_value_head_dim "
            "and linear_conv_kernel")
    if cfg.linear_key_heads != cfg.linear_value_heads:
        raise NotImplementedError(
            f"linear_value_heads={cfg.linear_value_heads} != "
            f"linear_key_heads={cfg.linear_key_heads}: value heads that "
            f"share a key head are not implemented")


# The state crosses the sequence in order, so no sequence axis; its reset
# and the convolution's mask at a document boundary are ROADMAP R11; every
# leaf is whole on every chip.
PART = parts.Part(
    name="linear_attention", fields=_FIELDS,
    validate=parts.refuses_post_norm(_validate, "the gated delta rule"),
    init=lambda k, cfg: dict(init_layer(k[0], cfg, parts.dense),
                             ln1_scale=parts.ones(cfg.d_model)),
    specs=lambda cfg, model_axis: parts.whole("ln1_scale", *LEAVES),
    apply=parts.normed_mixer(mixer, scopes.GDN_PROJ, scopes.GDN_OUT),
    record=lambda name, x, layer, cfg, ctx: record_blocks(name, x, cfg),
    unsupported=parts.everywhere("layer_types"))
