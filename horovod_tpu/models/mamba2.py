"""Mamba-2 selective state-space mixer, the whole of a ``"mamba2"`` layer:
what :mod:`horovod_tpu.models.transformer` runs where
``TransformerConfig.layer_types`` says so (Nemotron-H and the other
``nemotron_h`` / ``mamba2`` configs).

The layer, as Mamba-2 (arXiv:2405.21060) and the public ``nemotron_h``
mixer state it; ``u`` the normed input, ``H`` heads of ``P`` channels, a
state of ``N`` a head, ``G`` groups of heads that share ``B`` and ``C``::

    [z | xBC | dt] = u W_in                     widths H P, H P + 2 G N, H
    xBC = silu(causal_depthwise_conv1d(xBC) + b)
    x [T, H, P], B [T, G, N], C [T, G, N] = split(xBC)
    delta = softplus(dt + dt_bias)
    a_t = exp(-delta_t exp(A_log))              a scalar a head and token
    S_t = a_t S_{t-1} + delta_t x_t B_t^T       S in R^{P x N}, from 0
    y_t = S_t C_t + D x_t
    out = GroupRMSNorm(y * silu(z)) W_out       G groups of H P / G channels

**The recurrence runs in chunked form** (the paper's SSD algorithm),
never token by token: in a chunk of ``ssm_chunk`` tokens with ``b_i`` the
running sum of ``log a`` from the chunk's start and ``S_0`` the state it
starts from,

    y_i = sum_{j <= i} exp(b_i - b_j) (C_i . B_j) delta_j x_j
          + exp(b_i) S_0 C_i,
    S_end = exp(b_L) S_0 + sum_j exp(b_L - b_j) delta_j x_j B_j^T.

Every decay is ``exp`` of a difference that is ``<= 0``: nothing is
divided, so an ``a`` near 0 underflows to an exact zero and never to
``inf``.

**What runs it** is read from the operand (:func:`recurrence_path`), not
from a switch: the Pallas kernels of :mod:`horovod_tpu.ops.mamba2_scan`
wherever they can run (compiled on a TPU mesh, interpreted elsewhere): a
group's head states stay in VMEM over all of its chunks, the decay masks
and the masked ``C B^T`` never go through HBM, and the chunk states only
as what the backward keeps.
:func:`ssd` is the same algorithm as ``jax.numpy`` that XLA compiles, for
the operands the kernels do not take (a chunk, a group's channels or a
state that is not whole lanes, a group wider than VMEM holds, as the
published Mamba-2 models' one group of 80 heads; on the CPU, inside
``shard_map(check_vma=True)``, so a training step without experts traces
it there) and as the tests' second oracle: the masked ``C B^T`` products
and every chunk's own contribution to its end state for all chunks at
once, a :func:`jax.lax.scan` over the chunks that carries ``S`` (one
multiply-add a chunk), then the carried states' part of the output for
all chunks at once.  ``hvd_ssm_chunks_total{path}`` says which was
traced.

**The short convolution** with its bias, ``silu`` and the cut into
``x``, ``B`` and ``C`` are one pass over ``xBC``: the Pallas kernels of
:mod:`horovod_tpu.ops.short_conv` wherever they can run
(:func:`conv_path`: the three widths whole lanes), float32 from the taps
to the one rounding, three token-major arrays written through three
output specs; else ``causal_conv`` and what follows it as ``jax.numpy``.
``hvd_short_conv_rows_total{path}`` says which was traced.

**The gated norm** between the recurrence and the out projection is one
pass likewise: the Pallas kernels of :mod:`horovod_tpu.ops.gated_norm`
wherever they can run (:func:`norm_path`), which read ``y`` and ``z``
once, keep float32 to the one rounding and write the normed operand of
the out projection once; else :func:`gated_norm` below, the same lines as
``jax.numpy``.  ``hvd_gated_norm_rows_total{path}`` says which was traced.

Precision, either way: ``delta``, ``log a``, its running sums, every
decay and the carried state are float32; every matmul takes operands in
the model dtype (the masked and decayed ``C B^T``, ``delta x`` and the
state rounded to it where they are operands) and accumulates in float32.

What the backward keeps of the recurrence is the float32 state at each
chunk's start (:func:`saved_state_bytes`), either way; the ``jax.numpy``
form differentiates through everything else, the backward kernel
recomputes a chunk from its inputs and that state.

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
from horovod_tpu.models.linear_attention import causal_conv
from horovod_tpu.ops import gated_norm as norm_kernels
from horovod_tpu.ops import mamba2_scan as kernels
from horovod_tpu.ops import short_conv
from horovod_tpu.parallel._vma import pin_to, vma_of
from horovod_tpu.telemetry import scopes

# Mamba-2's initialisation of the decay: A ~ U(1, 16) and the step delta
# log-uniform in [0.001, 0.1] floored at 1e-4, stored as log A and
# softplus^-1(delta).
A_INIT_RANGE = (1.0, 16.0)
DT_INIT_RANGE = (1e-3, 0.1)
DT_INIT_FLOOR = 1e-4

LEAVES = ("ssm_w_in", "ssm_conv", "ssm_conv_bias", "ssm_a_log",
          "ssm_dt_bias", "ssm_d", "ssm_norm_scale", "ssm_w_out")


def widths(cfg):
    """``(z, xBC, dt)``: the widths of the in-projection's three parts."""
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    return inner, inner + 2 * cfg.ssm_groups * cfg.ssm_state, cfg.ssm_heads


def init_layer(key, cfg, dense):
    """The mixer's leaves of one Mamba-2 layer; ``dense(key, shape)`` is
    the caller's matrix initialiser."""
    inner, conv, heads = widths(cfg)
    k = jax.random.split(key, 6)
    a = jax.random.uniform(k[2], (heads,), jnp.float32, *A_INIT_RANGE)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        k[3], (heads,), jnp.float32,
        *(math.log(x) for x in DT_INIT_RANGE))), DT_INIT_FLOOR)
    bound = cfg.ssm_conv_kernel ** -0.5         # torch's Conv1d, fan-in K
    return {
        "ssm_w_in": dense(k[0], (cfg.d_model, inner + conv + heads)),
        "ssm_conv": jax.random.uniform(
            k[1], (cfg.ssm_conv_kernel, conv), jnp.float32, -bound, bound),
        "ssm_conv_bias": jax.random.uniform(
            k[4], (conv,), jnp.float32, -bound, bound),
        "ssm_a_log": jnp.log(a),
        # softplus^-1(dt) = dt + log(1 - exp(-dt))
        "ssm_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "ssm_d": jnp.ones((heads,), jnp.float32),
        "ssm_norm_scale": jnp.ones((inner,), jnp.float32),
        "ssm_w_out": dense(k[5], (inner, cfg.d_model)),
    }


def _mm(spec, a, b, dtype):
    """einsum of operands in ``dtype``, accumulated in float32."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def ssd(x, b_in, c_in, delta, log_a, chunk: int, dtype):
    """The recurrence of the module's docstring from ``S_0 = 0``, without
    the ``D x`` skip: ``x`` [B, T, H, P], ``b_in``, ``c_in`` [B, T, G, N]
    (head ``h`` reads group ``h // (H / G)``), ``delta`` and ``log_a``
    (``<= 0``) [B, T, H] float32 -> ``y`` [B, T, H, P] float32.  ``T`` a
    multiple of ``chunk``."""
    bsz, t, h, p = x.shape
    g, n = b_in.shape[2:]
    if t % chunk:
        raise ValueError(f"mamba2: sequence length {t} is not a multiple "
                         f"of the recurrence's chunk of {chunk}")
    nc, r = t // chunk, h // g

    def chunks(v, *tail):      # [B, T, ...] -> [B, nc, chunk, *tail]
        return v.reshape((bsz, nc, chunk) + tail)

    # Heads as (group, head in group): a group's B and C meet its heads.
    xd = chunks(x.astype(jnp.float32) * delta[..., None], g, r, p)
    b_in, c_in = chunks(b_in, g, n), chunks(c_in, g, n)
    b = jnp.cumsum(chunks(log_a, g, r), axis=2)      # [B, nc, L, G, R]
    by_head = jnp.moveaxis(b, 2, -1)                 # [B, nc, G, R, L]
    rows = jnp.arange(chunk)
    lower = rows[:, None] >= rows[None, :]
    diff = by_head[..., :, None] - by_head[..., None, :]
    # exp(b_i - b_j) for j <= i, else 0 (masked before the exp: above the
    # diagonal the difference is positive and may overflow).
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    scores = _mm("bcign,bcjgn->bcgij", c_in, b_in, dtype)
    within = _mm("bcgrij,bcjgrp->bcigrp", scores[:, :, :, None] * decay,
                 xd, dtype)
    to_end = jnp.exp(b[:, :, -1:] - b)[..., None]    # [B, nc, L, G, R, 1]
    own = _mm("bcjgn,bcjgrp->bcgrpn", b_in, to_end * xd, dtype)
    carry = jnp.exp(b[:, :, -1])                     # [B, nc, G, R]

    def step(state, inputs):
        own_c, carry_c = inputs
        return carry_c[..., None, None] * state + own_c, state

    # Inside shard_map the state varies over the axes its inputs do.
    state = pin_to(vma_of(own))(jnp.zeros((bsz, g, r, p, n), jnp.float32))
    _, starts = lax.scan(step, state, (jnp.moveaxis(own, 1, 0),
                                       jnp.moveaxis(carry, 1, 0)))
    across = _mm("bcign,cbgrpn->bcigrp", c_in, starts, dtype)
    y = within + jnp.exp(b)[..., None] * across
    return y.reshape(bsz, t, h, p)


def ssd_scan(x, b_in, c_in, delta, log_a, d, chunk: int, groups: int):
    """:func:`ssd` with the ``D x`` skip on the operands of
    :func:`horovod_tpu.ops.mamba2_scan.mamba2_scan`, whose signature this
    is: ``x`` [B, T, H P], ``b_in``, ``c_in`` [B, T, G N] in the model
    dtype, ``delta``, ``log_a`` [B, T, H] float32, ``d`` [H] -> ``y``
    [B, T, H P] float32."""
    (bsz, t, width), h = x.shape, delta.shape[2]
    by_head = x.reshape(bsz, t, h, width // h)
    y = ssd(by_head, b_in.reshape(bsz, t, groups, -1),
            c_in.reshape(bsz, t, groups, -1), delta, log_a, chunk, x.dtype)
    return (y + d[:, None] * by_head.astype(jnp.float32)).reshape(
        bsz, t, width)


def recurrence_path(x, cfg) -> str:
    """What runs the recurrence of a layer of ``cfg`` over an operand
    ``x`` [B, T, ...]: ``"kernel"``, the Pallas kernels of
    :mod:`horovod_tpu.ops.mamba2_scan`, compiled where the mesh that
    executes ``x`` is TPU and interpreted elsewhere; or ``"xla"``,
    :func:`ssd_scan`, where the kernels cannot run (a chunk, a group's
    width or a state that is not whole lanes, a group wider than VMEM
    holds, and the interpreter inside ``shard_map(check_vma=True)``:
    ``mamba2_scan.takes``)."""
    return "kernel" if kernels.takes(
        x, cfg.ssm_chunk, cfg.ssm_heads // cfg.ssm_groups, cfg.ssm_head_dim,
        cfg.ssm_state) else "xla"


def _conv_widths(cfg):
    """The convolution's output cut into ``x``, ``B`` and ``C``."""
    inner, conv, _ = widths(cfg)
    return inner, (conv - inner) // 2, (conv - inner) // 2


def conv_path(u, cfg) -> str:
    """What runs the short convolution of a layer of ``cfg`` over the
    projection of ``u`` [B, T, d], read as :func:`recurrence_path` reads
    its: ``"kernel"``, the Pallas kernels of
    :mod:`horovod_tpu.ops.short_conv` (convolution, bias and ``silu`` in
    one pass, ``x``, ``B`` and ``C`` written as three arrays); ``"xla"``,
    ``causal_conv`` and what follows it as ``jax.numpy``, where they
    cannot run (``short_conv.takes``)."""
    return "kernel" if short_conv.takes(
        u, cfg.ssm_conv_kernel, widths=_conv_widths(cfg),
        channels=widths(cfg)[1]) else "xla"


def gated_norm(y, z, scale, groups: int, eps: float):
    """``GroupRMSNorm(y * silu(z))`` of the module's docstring as
    ``jax.numpy``: ``y`` [B, T, H P] float32, ``z`` [B, T, H P] in the
    model dtype, ``scale`` [H P] -> [B, T, H P] in ``z``'s dtype, float32
    to the one rounding.  The oracle of
    :mod:`horovod_tpu.ops.gated_norm`'s gate-first form and what runs
    where its kernels cannot (:func:`norm_path`)."""
    bsz, t, inner = y.shape
    y = (y.reshape(bsz, t, groups, inner // groups)
         * jax.nn.silu(z.astype(jnp.float32)).reshape(
             bsz, t, groups, inner // groups))
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return (y.reshape(bsz, t, inner) * scale).astype(z.dtype)


def norm_path(u, cfg) -> str:
    """What runs the gated norm of a layer of ``cfg`` over the
    recurrence's output for ``u`` [B, T, d], read as
    :func:`recurrence_path` reads its: ``"kernel"``, the Pallas kernels of
    :mod:`horovod_tpu.ops.gated_norm`; ``"xla"``, :func:`gated_norm`,
    where they cannot run (``gated_norm.takes``)."""
    inner = widths(cfg)[0]
    return "kernel" if norm_kernels.takes(
        u, inner // cfg.ssm_groups, width=inner,
        x_dtype=jnp.float32) else "xla"


def saved_state_bytes(batch: int, t: int, cfg) -> int:
    """Bytes of chunk states the backward of one layer's recurrence
    keeps: the float32 state at the start of each chunk."""
    return (batch * (t // cfg.ssm_chunk) * cfg.ssm_heads * cfg.ssm_head_dim
            * cfg.ssm_state * 4)


def mixer(u, layer, cfg):
    """The whole mixer on the normed ``u`` [B, T, d] -> [B, T, d] (the
    caller adds the residual).  Opens its parts as bare components under
    ``attn/qkv`` and ``attn/out`` and the recurrence as a route of its
    own (``telemetry/scopes.py``)."""
    dt = cfg.dtype
    g, n = cfg.ssm_groups, cfg.ssm_state
    inner, conv, _ = widths(cfg)
    with jax.named_scope(scopes.ATTN_QKV):
        with jax.named_scope(scopes.SSM_PROJ):
            w_in = layer["ssm_w_in"]
            z = u @ w_in[:, :inner].astype(dt)
            xbc = u @ w_in[:, inner:inner + conv].astype(dt)
            # The step leaves its matmul in float32: [B, T, H].
            delta = jax.nn.softplus(jnp.matmul(
                u, w_in[:, inner + conv:].astype(dt),
                preferred_element_type=jnp.float32) + layer["ssm_dt_bias"])
            log_a = -delta * jnp.exp(layer["ssm_a_log"])
        with jax.named_scope(scopes.SSM_CONV):
            if conv_path(u, cfg) == "kernel":
                x, b_in, c_in = short_conv.short_conv(
                    xbc, layer["ssm_conv"], layer["ssm_conv_bias"],
                    widths=_conv_widths(cfg))
            else:
                xbc = jax.nn.silu(causal_conv(xbc, layer["ssm_conv"])
                                  + layer["ssm_conv_bias"]).astype(dt)
                x = xbc[..., :inner]
                b_in = xbc[..., inner:inner + g * n]
                c_in = xbc[..., inner + g * n:]
    with jax.named_scope(scopes.ATTN_SSM_SCAN):
        # Token-major, three arrays, whichever way the convolution ran: a
        # group's heads are a column slab.
        scan = (kernels.mamba2_scan if recurrence_path(u, cfg) == "kernel"
                else ssd_scan)
        y = scan(x, b_in, c_in, delta, log_a, layer["ssm_d"], cfg.ssm_chunk,
                 g)
    with jax.named_scope(scopes.ATTN_OUT):
        with jax.named_scope(scopes.SSM_GATE_NORM):
            if norm_path(u, cfg) == "kernel":
                y = norm_kernels.gated_norm(
                    y, z, layer["ssm_norm_scale"], group=inner // g,
                    gate_first=True, eps=cfg.norm_eps)
            else:
                y = gated_norm(y, z, layer["ssm_norm_scale"], g,
                               cfg.norm_eps)
        with jax.named_scope(scopes.SSM_OUT):
            return y @ layer["ssm_w_out"].astype(dt)


def record_chunks(layer: int, x, cfg) -> None:
    """Trace-time series (what was compiled into the step, like
    ``hvd_gdn_blocks_total``): the chunks of the recurrence layer
    ``layer`` walks per step on one device over the batch and heads of
    its input ``x`` [B, T, d], by what runs them
    (:func:`recurrence_path`), and the bytes of chunk states its backward
    keeps."""
    if not telemetry.enabled():
        return
    batch, t = x.shape[:2]
    telemetry.counter(
        "hvd_ssm_chunks_total",
        "Chunks of the chunked Mamba-2 recurrence the traced state-space "
        "layer computes per step on one device (batch x heads x T / "
        "chunk), by what runs them (path: kernel | xla)",
        layer=str(layer), path=recurrence_path(x, cfg)).inc(
            batch * cfg.ssm_heads * (t // cfg.ssm_chunk))
    telemetry.gauge(
        "hvd_ssm_saved_state_bytes",
        "Bytes of chunk states the backward pass of the traced "
        "state-space layer's recurrence keeps",
        layer=str(layer)).set(saved_state_bytes(batch, t, cfg))
    short_conv.record_rows(layer, batch * t, conv_path(x, cfg))
    norm_kernels.record_rows(layer, batch * t, norm_path(x, cfg))


# --- the mixer as a part (models/parts.py) ----------------------------------

_FIELDS = ("ssm_heads", "ssm_head_dim", "ssm_state", "ssm_groups",
           "ssm_conv_kernel", "ssm_chunk")


def _validate(cfg, used):
    sizes = tuple(getattr(cfg, name) for name in _FIELDS)
    if not used:
        if any(sizes):
            raise ValueError("the ssm_* fields mean nothing without a "
                             "'mamba2' entry in layer_types")
    elif min(sizes) <= 0 or cfg.ssm_heads % cfg.ssm_groups:
        raise ValueError(
            "a 'mamba2' layer needs ssm_heads, ssm_head_dim, ssm_state, "
            "ssm_groups (a divisor of ssm_heads), ssm_conv_kernel and "
            "ssm_chunk")


# As the linear mixer: the state crosses the sequence in order, and every
# leaf is whole on every chip.
PART = parts.Part(
    name="mamba2", fields=_FIELDS,
    validate=parts.refuses_post_norm(_validate, "a Mamba-2 mixer"),
    init=lambda k, cfg: dict(init_layer(k[0], cfg, parts.dense),
                             ln1_scale=parts.ones(cfg.d_model)),
    specs=lambda cfg, model_axis: parts.whole("ln1_scale", *LEAVES),
    apply=parts.normed_mixer(mixer, scopes.SSM_PROJ, scopes.SSM_OUT),
    record=lambda name, x, layer, cfg, ctx: record_chunks(name, x, cfg),
    unsupported=parts.everywhere("layer_types"))
