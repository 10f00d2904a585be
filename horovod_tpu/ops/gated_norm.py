"""The gated norm of a recurrent mixer as a Pallas TPU kernel pair: the
gate ``silu(z)`` and the RMS norm over a group of channels that stand
between the recurrence's output and the out projection, in one pass.

Per token, ``x`` the recurrence's output, ``z`` the gate's projection,
``G`` a norm group of ``group`` channels with its ``scale``, the two
forms the two mixers define (the mathematics is the ``jax.numpy`` lines
of :func:`horovod_tpu.models.mamba2.gated_norm` and
:func:`horovod_tpu.models.linear_attention.gated_norm`, the oracles and
what runs where these kernels do not)::

    u = x silu(z);  out = u / sqrt(mean_G(u^2) + eps) * scale   (gate first:
                                                                 Mamba-2)
    out = x / sqrt(mean_G(x^2) + eps) * scale * silu(z)         (norm first:
                                                                 the gated
                                                                 delta rule)

**One body a pass**, with what differs read from static arguments:
``gate_first`` (the architecture's definition), the group's width, and
``head_major``: whether ``x`` comes token-major ``[B, T, C]`` (``y`` of
the Mamba-2 scan, float32) or head-major ``[B * H, T, d]`` as the
delta rule's kernels leave ``o``, a head a norm group.  ``z`` and what is
written are token-major ``[B, T, C]`` either way, so the move from the
recurrence's layout to the out projection's happens in registers: a
slab's heads are read apart and joined along the lanes, and the backward
cuts ``dx`` back into heads (``short_conv``'s lane moves, the other way).

**Grid.**  ``(batch, T / tile)``; a grid step holds a tile of tokens at
the full width.  Inside it the columns are cut into slabs of whole groups
and whole lanes (``short_conv``'s: 1024 lanes for Mamba-2's groups of
1024, a pair of heads of 192 = 3 x 128 lanes for the delta rule), a
slab's first column a loop variable, and a loop walks a slab's rows a
chunk of :func:`_rows` at a time: the operands are read once, everything
is float32 in registers, and what leaves is rounded once.

**Backward.**  One kernel reads ``x``, ``z``, ``scale`` and the out
projection's ``d out``, recomputes the gate and the group's statistic,
and writes ``dx`` (in ``x``'s layout and dtype) and ``dz``; the scale's
gradient is a sum over every token: eight partial sums a channel (a
sublane each) in a float32 output block that stays in VMEM over the
whole grid; the caller adds the eight.  Nothing is kept for the backward
but the operands.

**Precision.**  Float32 from the operands to the one rounding to ``z``'s
dtype (the model's) at the end, in the ``jax.numpy`` forms' order of
operations; the scale's gradient is summed in float32.

**Where it runs.**  As ``short_conv``: compiled by Mosaic where the
executing mesh is TPU, in the Pallas interpreter elsewhere; :func:`takes`
says whether the kernels can run on an operand (:func:`tiles` has an
answer for its sizes, and not the interpreter inside
``shard_map(check_vma=True)``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu import telemetry
from horovod_tpu.ops.grouped_matmul import _interpret, _vma
from horovod_tpu.ops.short_conv import (
    _MAX_UNIT, CARRY, HALO, LANES, VMEM_LIMIT, _chunk_rows, _fold,
    _for_each_slab, _head_lanes, _head_sums, _lanes, _runs, _sigmoid, _unit)
from horovod_tpu.telemetry import scopes

# Tokens a grid step holds at most, and float32 registers a chunk of a
# slab's rows fills (docs/kernels.md, "Gated norm": the sweep on the
# chip).
TILE = 256
CHUNK_REGISTERS = 64

_F32 = jnp.float32


class _Plan(NamedTuple):
    """What is static of a call: the token-major width, the norm group's
    width, which comes first, ``x``'s layout, and the norm's epsilon."""
    width: int
    group: int
    gate_first: bool
    head_major: bool
    eps: float

    # What ``short_conv._runs`` reads: one token-major output of the
    # whole width, slabs of whole groups.
    @property
    def widths(self):
        return (self.width,)

    @property
    def head_dim(self):
        return self.group


def _rows(width: int, tile: int) -> int:
    """Rows of a chunk of a slab ``width`` lanes wide: the power of two
    that fills :data:`CHUNK_REGISTERS` float32 registers, a 16-bit
    dtype's sublane tile at least and the tile at most."""
    rows = HALO
    while 2 * rows * _lanes(width) <= CHUNK_REGISTERS * CARRY * LANES:
        rows *= 2
    return min(rows, tile)


def vmem_bytes(tile: int, width: int, group: int, head_major: bool,
               x_itemsize: int, itemsize: int) -> int:
    """VMEM the backward kernel, the larger of the two, takes for a grid
    step of ``tile`` tokens: an estimate from above.  Twice (the
    pipeline's two buffers) a tile of ``x`` and of ``dx`` (a head's lanes
    padded to whole registers where they are head-major), of ``z``, ``d
    out`` and ``dz``; the scale and its gradient's partial sums, twice;
    a slab's chunk in float32 a score of times over for what the loop's
    body spills; and an eighth more."""
    wide = width // group * _lanes(group) if head_major else _lanes(width)
    blocks = tile * (2 * wide * x_itemsize + 3 * _lanes(width) * itemsize)
    sums = (CARRY + 1) * _lanes(width) * 4
    return (2 * blocks + 2 * sums
            + 20 * CHUNK_REGISTERS * CARRY * LANES * 4) * 9 // 8


def tiles(t: int, width: int, group: int, head_major: bool = False,
          x_itemsize: int = 4, itemsize: int = 2):
    """Tokens a grid step holds for ``t`` tokens of ``width`` channels in
    norm groups of ``group``: the largest power of two from a 16-bit
    dtype's sublane tile up to :data:`TILE` that divides ``t`` and that
    :data:`VMEM_LIMIT` holds.  None where the kernels cannot run these
    sizes: the length has to be whole sublane tiles, and the groups have
    to divide the width into groups whose common multiple with a
    register's lanes is a slab."""
    if (t <= 0 or t % HALO or group <= 0 or width % group
            or _unit(group) > _MAX_UNIT):
        return None
    tile = TILE
    while tile >= HALO:
        if t % tile == 0 and vmem_bytes(tile, width, group, head_major,
                                        x_itemsize, itemsize) <= VMEM_LIMIT:
            return tile
        tile //= 2
    return None


def _shape(x, z, group: int, head_major: bool):
    """``(tokens, width)`` of a call's operands, or None where they are
    not a call's: ``z`` [B, T, C] and ``x`` either the same shape or
    head-major [B * C / group, T, group]."""
    if x.ndim != 3 or z.ndim != 3 or group <= 0 or z.shape[2] % group:
        return None
    bsz, t, width = z.shape
    want = (bsz * (width // group), t, group) if head_major else z.shape
    return (t, width) if tuple(x.shape) == want else None


def takes(u, group: int, width=None, head_major: bool = False,
          x_dtype=None) -> bool:
    """Whether the kernels can run the gated norm over groups of
    ``group`` of the ``width`` channels of an operand ``u`` [B, T, C] (or
    of what is projected from it: the gate ``z`` in ``u``'s dtype, ``x``
    in ``x_dtype``, head-major or not), read for its length and dtype,
    the mesh that executes it and the axes it varies over: sizes
    :func:`tiles` has an answer for, and not the interpreter inside
    ``shard_map(check_vma=True)`` (``short_conv``'s reason)."""
    x_itemsize = jnp.dtype(x_dtype or u.dtype).itemsize
    return (u.ndim == 3 and tiles(
        u.shape[1], width or u.shape[2], group, head_major, x_itemsize,
        u.dtype.itemsize) is not None and not (_interpret(u) and _vma(u)))


def _group_sums(v, d: int):
    """``v`` [rows, W] summed over each group's ``d`` lanes, every lane
    holding its own group's sum (to broadcast against ``v``)."""
    if v.shape[1] == d:
        return jnp.sum(v, axis=-1, keepdims=True)
    return _head_sums(v, d)


def _read(x_ref, r, cols, off, width: int, plan: _Plan):
    """Rows ``r`` of the slab at columns ``cols`` of ``x``, float32
    [rows, width]: as they lie, or a slab's heads joined along the
    lanes."""
    if not plan.head_major:
        return x_ref[r, cols].astype(_F32)
    d = plan.group
    heads = [x_ref[off // d + a, r, :].astype(_F32)
             for a in range(width // d)]
    return heads[0] if len(heads) == 1 else jnp.concatenate(heads, axis=1)


def _write(dx_ref, dx, r, cols, off, width: int, plan: _Plan):
    """The other way: ``dx`` [rows, width] float32 into ``x``'s layout."""
    if not plan.head_major:
        dx_ref[r, cols] = dx.astype(dx_ref.dtype)
        return
    d = plan.group
    blocks = [dx[:, at:at + LANES] for at in range(0, width, LANES)]
    for a in range(width // d):
        dx_ref[off // d + a, r, :] = _head_lanes(blocks, a * d, d).astype(
            dx_ref.dtype)


def _fwd_kernel(x_ref, z_ref, scale_ref, out_ref, *, plan: _Plan):
    d, tile = plan.group, z_ref.shape[0]
    for run in _runs(plan):
        width = run[1]
        rows = _rows(width, tile)

        def slab(cols, off, width=width, rows=rows):
            scale = scale_ref[:, cols]

            def chunk(i, carry):
                r = _chunk_rows(i, rows)
                x = _read(x_ref, r, cols, off, width, plan)
                z = z_ref[r, cols].astype(_F32)
                gate = z * _sigmoid(z)
                if plan.gate_first:
                    x = x * gate
                out = x * lax.rsqrt(_group_sums(x * x, d) * (1.0 / d)
                                    + plan.eps) * scale
                if not plan.gate_first:
                    out = out * gate
                out_ref[r, cols] = out.astype(out_ref.dtype)
                return carry

            lax.fori_loop(0, tile // rows, chunk, None)

        _for_each_slab(run, slab)


def _bwd_kernel(x_ref, z_ref, scale_ref, dout_ref, dx_ref, dz_ref,
                dscale_ref, *, plan: _Plan):
    d, tile = plan.group, z_ref.shape[0]

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _start():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    for run in _runs(plan):
        width = run[1]
        rows = _rows(width, tile)

        def slab(cols, off, width=width, rows=rows):
            scale = scale_ref[:, cols]

            def chunk(i, dscale):
                r = _chunk_rows(i, rows)
                x = _read(x_ref, r, cols, off, width, plan)
                z = z_ref[r, cols].astype(_F32)
                sig = _sigmoid(z)
                gate = z * sig
                # out = n scale (gate first) or n scale gate, n = u inv,
                # inv = (mean u^2 + eps)^-1/2 a group: g is the gradient
                # of n scale.
                g = dout_ref[r, cols].astype(_F32)
                if plan.gate_first:
                    u = x * gate
                else:
                    u, dout = x, g
                    g = g * gate
                inv = lax.rsqrt(_group_sums(u * u, d) * (1.0 / d) + plan.eps)
                n = u * inv
                dscale = dscale + _fold(g * n)
                g = g * scale
                du = inv * (g - n * (_group_sums(g * n, d) * (1.0 / d)))
                if plan.gate_first:
                    dx, dgate = du * gate, du * x
                else:
                    dx, dgate = du, dout * (n * scale)
                _write(dx_ref, dx, r, cols, off, width, plan)
                dz_ref[r, cols] = (
                    dgate * (sig * (1.0 + z * (1.0 - sig)))).astype(
                        dz_ref.dtype)
                return dscale

            dscale_ref[:, cols] += lax.fori_loop(
                0, tile // rows, chunk, jnp.zeros((CARRY, width), _F32))

        _for_each_slab(run, slab)


def _specs(plan: _Plan, tile: int):
    """Block specs of a tile of ``x``, of a token-major tile and of what
    is whole in every grid step."""
    rows = pl.BlockSpec((None, tile, plan.width), lambda b, t: (b, t, 0))
    x_spec = rows if not plan.head_major else pl.BlockSpec(
        (plan.width // plan.group, tile, plan.group), lambda b, t: (b, t, 0))

    def whole(n):
        return pl.BlockSpec((n, plan.width), lambda b, t: (0, 0))

    return x_spec, rows, whole


def _tile(plan: _Plan, x, z) -> int:
    return tiles(z.shape[1], plan.width, plan.group, plan.head_major,
                 x.dtype.itemsize, z.dtype.itemsize)


# The calls are jitted with what is static among their arguments, and
# inlined: the mixers of a step, each traced forward, recomputed and
# backward, share one traced kernel and one lowering a kind.

@functools.partial(jax.jit, static_argnames=("plan", "interpret"),
                   inline=True)
def _fwd_call(x, z, scale, *, plan: _Plan, interpret: bool):
    tile = _tile(plan, x, z)
    x_spec, rows, whole = _specs(plan, tile)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan),
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype,
                                       vma=_vma(x, z, scale)),
        grid=(z.shape[0], z.shape[1] // tile),
        in_specs=[x_spec, rows, whole(1)],
        out_specs=rows,
        interpret=interpret, name=scopes.GATED_NORM_FWD,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
    )(x, z, scale)


@functools.partial(jax.jit, static_argnames=("plan", "interpret"),
                   inline=True)
def _bwd_call(x, z, scale, dout, *, plan: _Plan, interpret: bool):
    tile = _tile(plan, x, z)
    x_spec, rows, whole = _specs(plan, tile)
    vma = _vma(x, z, scale, dout)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma),
                   jax.ShapeDtypeStruct(z.shape, z.dtype, vma=vma),
                   jax.ShapeDtypeStruct((CARRY, plan.width), _F32, vma=vma)],
        grid=(z.shape[0], z.shape[1] // tile),
        in_specs=[x_spec, rows, whole(1), rows],
        out_specs=[x_spec, rows, whole(CARRY)],
        interpret=interpret, name=scopes.GATED_NORM_BWD,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
    )(x, z, scale, dout)


def _forward(x, z, scale, plan):
    return _fwd_call(x, z, scale, plan=plan, interpret=_interpret(z))


_norm = jax.custom_vjp(_forward, nondiff_argnums=(3,))


def _norm_fwd(x, z, scale, plan):
    # Nothing is kept for the backward but the operands.
    return _forward(x, z, scale, plan), (x, z, scale)


def _norm_bwd(plan, residuals, dout):
    x, z, scale = residuals
    dx, dz, dscale = _bwd_call(x, z, scale, dout, plan=plan,
                               interpret=_interpret(z))
    return dx, dz, dscale.sum(axis=0, keepdims=True).astype(scale.dtype)


_norm.defvjp(_norm_fwd, _norm_bwd)


def gated_norm(x, z, scale, *, group: int, gate_first: bool,
               head_major: bool = False, eps: float = 1e-6):
    """The gated norm of the module's docstring: ``z`` [B, T, C] in the
    model dtype, ``x`` in any float dtype, [B, T, C] or, with
    ``head_major``, [B * H, T, group] (``H = C / group``), ``scale`` [C]
    (or [group] with ``head_major``: every head's) in float32 -> [B, T,
    C] in ``z``'s dtype.  Sizes are ones that :func:`takes` accepts.
    Differentiable in ``x``, ``z`` and ``scale``."""
    shape = _shape(x, z, group, head_major)
    if shape is None or tiles(*shape, group, head_major, x.dtype.itemsize,
                              z.dtype.itemsize) is None:
        raise ValueError(
            "gated norm: the kernels do not take (x, z, group, head major)"
            f" = {(x.shape, z.shape, group, head_major)}: tiles(), takes()")
    width = z.shape[2]
    # One scale a column; a scale every head shares collects its
    # gradient from all of them through the tiling's own transpose.
    scale = jnp.tile(scale, width // scale.shape[0]).reshape(1, width)
    return _norm(x, z, scale,
                 _Plan(width, group, gate_first, head_major, float(eps)))


def record_rows(layer, rows: int, path: str) -> None:
    """Trace-time series (what was compiled into the step, beside
    ``hvd_short_conv_rows_total``): the rows the gated norm of mixer
    layer ``layer`` runs over per step on one device (batch x T), by what
    runs it (the mixer's ``norm_path``)."""
    if not telemetry.enabled():
        return
    telemetry.counter(
        "hvd_gated_norm_rows_total",
        "Rows the gated norm of the traced mixer layer runs over per step "
        "on one device (batch x T), by what runs it (path: kernel | xla)",
        layer=str(layer), path=path).inc(rows)
