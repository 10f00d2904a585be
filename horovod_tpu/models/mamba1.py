"""Mamba-1 selective state-space mixer, the sequence mixer of a ``"mamba"``
layer: what :mod:`horovod_tpu.models.transformer` runs where
``TransformerConfig.layer_types`` says so (Jamba and the other ``jamba`` /
``mamba`` configs).  Unlike a ``"mamba2"`` layer, the config's feed-forward
form follows it.

The mixer, as Mamba (arXiv:2312.00752) and the public ``jamba`` mixer
state it; ``u`` the normed input, ``C`` inner channels, a state of ``N`` a
channel, ``R`` the rank of the step's projection, ``K`` taps::

    [xs | z]     = u W_in                        widths C, C
    xs           = silu(causal_depthwise_conv1d(xs, K taps) + b_conv)
    [r | B | Cm] = xs W_x                        widths R, N, N
    r = RMSNorm_R(r), B = RMSNorm_N(B), Cm = RMSNorm_N(Cm)     (Jamba's)
    delta        = softplus(r W_dt + b_dt)       [T, C]
    A            = -exp(A_log)                   [C, N]
    h_t[c, n]    = exp(delta_t[c] A[c, n]) h_{t-1}[c, n]
                   + delta_t[c] B_t[n] xs_t[c]                 h_0 = 0
    y_t[c]       = sum_n Cm_t[n] h_t[c, n] + D[c] xs_t[c]
    out          = (y * silu(z)) W_out           no norm between

**The decay is a channel's and a state index's own**, ``exp(delta_t[c]
A[c, n])``: it does not factor into products of ``B`` and ``Cm`` as
Mamba-2's one scalar a head does, so the recurrence is no matrix product
and runs token by token on the vector unit.

**What runs it** is read from the operand (:func:`scan_path`), not from a
switch: the Pallas kernels of :mod:`horovod_tpu.ops.selective_scan`
wherever they can run (compiled on a TPU mesh, interpreted elsewhere): a
slab's state stays in registers over a tile of tokens and in VMEM over the
sequence, and the ``[T, C, N]`` states never go through HBM but as what
the backward keeps, one state a tile.  :func:`scan_xla` is the same
function as ``jax.numpy``, a :func:`jax.lax.scan` over the tokens inside a
checkpointed scan over blocks of them (its backward holds one block's
states), for the operands the kernels do not take (channels that are not
whole slabs of 1024; on the CPU, inside ``shard_map(check_vma=True)``, so
a training step traces it there) and as the tests' second oracle.
``hvd_mamba_scan_tokens_total{path}`` says which was traced.

**The short convolution** with its bias and ``silu`` is one pass: the
Pallas kernels of :mod:`horovod_tpu.ops.short_conv` wherever they can run
(:func:`conv_path`), else ``causal_conv`` and what follows it as
``jax.numpy``.  ``hvd_short_conv_rows_total{path}`` says which was traced.

**The gate** ``y * silu(z)`` stands between two layouts: the scan's
kernels leave ``y`` a token's 1024 channels a register, the out projection
wants a token a sublane.  The Pallas kernels of
:mod:`horovod_tpu.ops.mamba_gate` make that move while they gate, wherever
they can run (:func:`gate_path`: the scan on its kernels' path): ``y`` and
``z`` are read once and the out projection's operand written once, and the
backward writes ``dy`` as the scan's backward reads it.  Else
:func:`gate_xla`, the line as ``jax.numpy``.
``hvd_mamba_gate_rows_total{path}`` says which was traced.

``softplus`` runs inside the scan, either way (the kernels read the
step's pre-activation and keep nothing else of it for the backward).

Precision: the three inner norms, ``softplus``, ``delta``, ``B``, ``Cm``,
every decay, the state and the gate are float32; every matmul takes
operands in the model dtype and accumulates in float32 (``x_proj`` and
``dt_proj`` leave float32).

What the backward keeps of the scan is the float32 state at each tile's
start (:func:`saved_state_bytes`), either way.

Not here: ``segment_ids`` (the state's reset at a document boundary and
the convolution's mask: ROADMAP R11), a model or sequence axis, decode
(ROADMAP R13).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu import telemetry
from horovod_tpu.models import parts
from horovod_tpu.models.linear_attention import causal_conv
from horovod_tpu.ops import mamba_gate
from horovod_tpu.ops import selective_scan as kernels
from horovod_tpu.ops import short_conv
from horovod_tpu.parallel._vma import pin_to, vma_of
from horovod_tpu.telemetry import scopes

# Mamba's initialisation of the step: delta log-uniform in [0.001, 0.1]
# floored at 1e-4, stored as softplus^-1(delta); A = 1..N for every
# channel (S4D-real), stored as log A.
DT_INIT_RANGE = (1e-3, 0.1)
DT_INIT_FLOOR = 1e-4

LEAVES = ("mamba_w_in", "mamba_conv", "mamba_conv_bias", "mamba_w_x",
          "mamba_dt_norm_scale", "mamba_b_norm_scale", "mamba_c_norm_scale",
          "mamba_w_dt", "mamba_dt_bias", "mamba_a_log", "mamba_d",
          "mamba_w_out")


def init_layer(key, cfg, dense):
    """The mixer's leaves of one Mamba layer; ``dense(key, shape)`` is the
    caller's matrix initialiser."""
    c, n, r = cfg.mamba_inner, cfg.mamba_state, cfg.mamba_dt_rank
    k = jax.random.split(key, 7)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        k[4], (c,), jnp.float32,
        *(math.log(x) for x in DT_INIT_RANGE))), DT_INIT_FLOOR)
    bound = cfg.mamba_conv_kernel ** -0.5       # torch's Conv1d, fan-in K
    return {
        "mamba_w_in": dense(k[0], (cfg.d_model, 2 * c)),
        "mamba_conv": jax.random.uniform(
            k[1], (cfg.mamba_conv_kernel, c), jnp.float32, -bound, bound),
        "mamba_conv_bias": jax.random.uniform(
            k[2], (c,), jnp.float32, -bound, bound),
        "mamba_w_x": dense(k[3], (c, r + 2 * n)),
        "mamba_dt_norm_scale": parts.ones(r),
        "mamba_b_norm_scale": parts.ones(n),
        "mamba_c_norm_scale": parts.ones(n),
        "mamba_w_dt": dense(k[5], (r, c)),
        # softplus^-1(dt) = dt + log(1 - exp(-dt))
        "mamba_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "mamba_a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (c, n)),
        "mamba_d": parts.ones(c),
        "mamba_w_out": dense(k[6], (c, cfg.d_model)),
    }


def _block(t: int, channels: int, state: int) -> int:
    """Tokens between two kept states: the kernels' tile where they have
    one, else the largest divisor of ``t`` up to their largest."""
    return kernels.tiles(t, channels, state) or next(
        b for b in range(min(t, kernels.TILE), 0, -1) if t % b == 0)


def scan_xla(x, dt, a, b_in, c_in, d):
    """The recurrence of the module's docstring with the ``D x`` skip and
    the step ``delta = softplus(dt)``, on the operands of
    :func:`horovod_tpu.ops.selective_scan.mamba_scan`, whose signature
    this is: ``x`` [B, T, C] in the model dtype, ``dt`` [B, T, C]
    float32, ``a`` [C, N] (``< 0``), ``b_in``, ``c_in`` [B, T, N], ``d``
    [C] float32 -> ``y`` [B, T, C] float32.  Token by token
    inside a checkpointed scan over blocks: the backward keeps the state
    at each block's start and recomputes a block's ``[block, B, C, N]``
    states."""
    bsz, t, c = x.shape
    n = a.shape[1]
    block = _block(t, c, n)
    x32 = x.astype(jnp.float32)
    delta = jax.nn.softplus(dt.astype(jnp.float32))

    def token(h, inputs):
        xt, step, bt, ct = inputs
        h = (jnp.exp(step[..., None] * a) * h
             + (step * xt)[..., None] * bt[:, None, :])
        return h, jnp.sum(h * ct[:, None, :], axis=-1)

    def blocks(v):      # [B, T, w] -> [T / block, block, B, w]
        return jnp.moveaxis(v.astype(jnp.float32), 1, 0).reshape(
            t // block, block, bsz, v.shape[2])

    # Inside shard_map the state varies over the axes its inputs do.
    state = pin_to(vma_of(x) | vma_of(dt) | vma_of(b_in))(
        jnp.zeros((bsz, c, n), jnp.float32))
    _, y = lax.scan(jax.checkpoint(lambda h, rows: lax.scan(token, h, rows)),
                    state, (blocks(x32), blocks(delta), blocks(b_in),
                            blocks(c_in)))
    return jnp.moveaxis(y.reshape(t, bsz, c), 0, 1) + d * x32


def scan_path(x, cfg) -> str:
    """What runs the scan of a layer of ``cfg`` over an operand ``x`` [B,
    T, ...]: ``"kernel"``, the Pallas kernels of
    :mod:`horovod_tpu.ops.selective_scan`, compiled where the mesh that
    executes ``x`` is TPU and interpreted elsewhere; or ``"xla"``,
    :func:`scan_xla`, where the kernels cannot run (channels that are not
    whole slabs, and the interpreter inside
    ``shard_map(check_vma=True)``: ``selective_scan.takes``)."""
    return ("kernel" if kernels.takes(x, cfg.mamba_inner, cfg.mamba_state)
            else "xla")


def conv_path(u, cfg) -> str:
    """What runs the short convolution of a layer of ``cfg`` over the
    projection of ``u`` [B, T, d], read as :func:`scan_path` reads its:
    ``"kernel"``, the Pallas kernels of :mod:`horovod_tpu.ops.short_conv`
    (convolution, bias and ``silu`` in one pass); ``"xla"``,
    ``causal_conv`` and what follows it as ``jax.numpy``, where they
    cannot run (``short_conv.takes``)."""
    return "kernel" if short_conv.takes(
        u, cfg.mamba_conv_kernel, channels=cfg.mamba_inner) else "xla"


def gate_xla(y, z):
    """``y * silu(z)`` in float32, rounded once to ``z``'s dtype: ``y``
    [B, T, C] float32, ``z`` [B, T, C] in the model dtype."""
    return (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)


def gate_path(u, cfg) -> str:
    """What runs the gate of a layer of ``cfg`` over what is projected
    from ``u`` [B, T, d], read as :func:`scan_path` reads its:
    ``"kernel"``, the Pallas kernels of :mod:`horovod_tpu.ops.mamba_gate`,
    which read ``y`` in the layout the scan's kernels leave it in, so only
    where those run and for sizes ``mamba_gate.takes`` accepts;
    ``"xla"``, :func:`gate_xla`."""
    return "kernel" if scan_path(u, cfg) == "kernel" and mamba_gate.takes(
        u, cfg.mamba_inner) else "xla"


def saved_state_bytes(batch: int, t: int, cfg) -> int:
    """Bytes of states the backward of one layer's scan keeps: the
    float32 state at the start of each tile (each block of
    :func:`scan_xla`)."""
    c, n = cfg.mamba_inner, cfg.mamba_state
    return batch * (t // _block(t, c, n)) * c * n * 4


def _norm32(v, scale, eps):
    """RMSNorm of the float32 ``v`` over its last axis, in float32."""
    return v * lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps) * scale


def mixer(u, layer, cfg):
    """The whole mixer on the normed ``u`` [B, T, d] -> [B, T, d] (the
    caller adds the residual).  Opens its parts as bare components under
    ``attn/qkv`` and ``attn/out`` and the scan as a route of its own
    (``telemetry/scopes.py``)."""
    dt = cfg.dtype
    c, n, r = cfg.mamba_inner, cfg.mamba_state, cfg.mamba_dt_rank
    with jax.named_scope(scopes.ATTN_QKV):
        with jax.named_scope(scopes.MAMBA_PROJ):
            w_in = layer["mamba_w_in"]
            xs = u @ w_in[:, :c].astype(dt)
            z = u @ w_in[:, c:].astype(dt)
        with jax.named_scope(scopes.MAMBA_CONV):
            if conv_path(u, cfg) == "kernel":
                xs = short_conv.short_conv(xs, layer["mamba_conv"],
                                           layer["mamba_conv_bias"])
            else:
                xs = jax.nn.silu(causal_conv(xs, layer["mamba_conv"])
                                 + layer["mamba_conv_bias"]).astype(dt)
        with jax.named_scope(scopes.MAMBA_DT_BC):
            # Both small projections leave their matmul in float32.
            rbc = jnp.matmul(xs, layer["mamba_w_x"].astype(dt),
                             preferred_element_type=jnp.float32)
            eps = cfg.norm_eps
            low = _norm32(rbc[..., :r], layer["mamba_dt_norm_scale"], eps)
            b_in = _norm32(rbc[..., r:r + n], layer["mamba_b_norm_scale"],
                           eps)
            c_in = _norm32(rbc[..., r + n:], layer["mamba_c_norm_scale"], eps)
            # The step's pre-activation: the scan takes softplus itself.
            step = jnp.matmul(
                low.astype(dt), layer["mamba_w_dt"].astype(dt),
                preferred_element_type=jnp.float32) + layer["mamba_dt_bias"]
            a = -jnp.exp(layer["mamba_a_log"])
    with jax.named_scope(scopes.ATTN_MAMBA_SCAN):
        scan = (kernels.mamba_scan if scan_path(u, cfg) == "kernel"
                else scan_xla)
        y = scan(xs, step, a, b_in, c_in, layer["mamba_d"])
    with jax.named_scope(scopes.ATTN_OUT):
        with jax.named_scope(scopes.MAMBA_GATE):
            gate = (mamba_gate.mamba_gate if gate_path(u, cfg) == "kernel"
                    else gate_xla)
            y = gate(y, z)
        with jax.named_scope(scopes.MAMBA_OUT):
            return y @ layer["mamba_w_out"].astype(dt)


def record_tokens(layer, x, cfg) -> None:
    """Trace-time series (what was compiled into the step, like
    ``hvd_ssm_chunks_total``): the tokens the scan of layer ``layer``
    walks per step on one device over the batch of its input ``x`` [B, T,
    d], by what runs them (:func:`scan_path`), and the bytes of states
    its backward keeps; and the rows of its convolution and of its gate."""
    if not telemetry.enabled():
        return
    batch, t = x.shape[:2]
    telemetry.counter(
        "hvd_mamba_scan_tokens_total",
        "Tokens the selective scan of the traced Mamba-1 layer walks per "
        "step on one device (batch x T), by what runs them (path: kernel "
        "| xla)",
        layer=str(layer), path=scan_path(x, cfg)).inc(batch * t)
    telemetry.gauge(
        "hvd_mamba_saved_state_bytes",
        "Bytes of tile states the backward pass of the traced Mamba-1 "
        "layer's scan keeps",
        layer=str(layer)).set(saved_state_bytes(batch, t, cfg))
    short_conv.record_rows(layer, batch * t, conv_path(x, cfg))
    mamba_gate.record_rows(layer, batch * t, gate_path(x, cfg))


# --- the mixer as a part (models/parts.py) ----------------------------------

_FIELDS = ("mamba_inner", "mamba_state", "mamba_dt_rank", "mamba_conv_kernel")


def _validate(cfg, used):
    sizes = tuple(getattr(cfg, name) for name in _FIELDS)
    if not used:
        if any(sizes):
            raise ValueError("the mamba_* fields mean nothing without a "
                             "'mamba' entry in layer_types")
    elif min(sizes) <= 0:
        raise ValueError(
            "a 'mamba' layer needs mamba_inner, mamba_state, mamba_dt_rank "
            "and mamba_conv_kernel")


# As the other recurrent mixers: the state crosses the sequence in order,
# and every leaf is whole on every chip.
PART = parts.Part(
    name="mamba1", fields=_FIELDS,
    validate=parts.refuses_post_norm(_validate, "a Mamba-1 mixer"),
    init=lambda k, cfg: dict(init_layer(k[0], cfg, parts.dense),
                             ln1_scale=parts.ones(cfg.d_model)),
    specs=lambda cfg, model_axis: parts.whole("ln1_scale", *LEAVES),
    apply=parts.normed_mixer(mixer, scopes.MAMBA_PROJ, scopes.MAMBA_OUT),
    record=lambda name, x, layer, cfg, ctx: record_tokens(name, x, cfg),
    unsupported=parts.everywhere("layer_types"))
