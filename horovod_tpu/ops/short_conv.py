"""The short causal convolution of a recurrent mixer as a Pallas TPU kernel
pair: the depthwise convolution along the sequence, the bias, ``silu``
and, where the layer normalises per head, the L2 norm, from the
projection's output to the recurrence's operands in one pass.

Per channel ``c`` and token ``t``, with ``K`` taps ``w`` [K, C] and zeros
before the sequence's start (the mathematics is
:func:`horovod_tpu.models.linear_attention.causal_conv`'s, the oracle and
what runs where these kernels do not)::

    pre_t = sum_j w_j x_{t-K+1+j} (+ bias)
    z_t   = silu(pre_t)
    y_t   = z_t                                            (no norm)
    y_t   = z_t / sqrt(sum_head z_t^2 + eps) * scale       (a head's norm)

**Grid.**  ``(batch, T / tile)``; a grid step holds a tile of tokens at
the full width, so that a block is whole heads and whole lanes whatever
the head width (2880 = 30 x 96 is neither a multiple of 128 nor of 4
heads' 384 lanes).  The :data:`CARRY` rows before the tile come through a
second block spec on the same array (the :data:`HALO` rows that end where
the tile starts; the first tile's are taken as zero), so nothing is
padded in HBM and the forward's tiles do not depend on one another.

**Inside a grid step** the columns are cut into slabs of whole heads and
whole lanes (:data:`SLAB`, :data:`HEAD_SLAB`): one loop walks the equal
slabs of an output, a slab's first column its variable (so the kernels'
size, and what tracing and lowering them costs the step's set-up, does
not grow with the width), and inside it a loop walks the slab's rows
:data:`ROWS` at a time with the last rows of the chunk before in
registers: the model dtype is read once, the taps are float32
multiply-adds on rows shifted along the sublanes, and what leaves is
rounded once.

**What is written is what the recurrence reads.**  Without a head width:
token-major arrays ``[B, T, width]``, one for each of ``widths`` (the
Mamba-2 mixer's ``x``, ``B``, ``C`` through three output specs).  With
one: head-major ``[B * H, T, d]``, a head's lanes cut out of the slab and
written apart, which is the layout
:func:`horovod_tpu.ops.gated_delta_rule.gated_delta_rule_head_major`
takes; the head's sum of squares is a lane reduction of that cut.

**Precision.**  Float32 from the taps through ``silu`` and the norm, one
rounding to the model dtype at the end: :func:`causal_conv`'s, in its
order of operations.  The backward accumulates the taps' and the bias's
gradient in float32.

**Backward.**  One kernel walks the tiles from the last to the first and
a tile's chunks likewise: it reads ``x`` and ``dy``, recomputes the
pre-activation, and writes ``dx`` (the taps reversed; the
pre-activation's gradient of the rows after the chunk is carried, in
registers within a tile and in a VMEM scratch across tiles).  The taps'
and the bias's gradient are sums over every token: eight partial sums a
channel (a sublane each, so no reduction inside the loop) in float32
output blocks that stay in VMEM over the whole grid; the caller adds the
eight.  Nothing is kept for the backward but the operands.

**Where it runs.**  Compiled by Mosaic where the executing mesh is TPU,
in the Pallas interpreter (the same code) elsewhere:
``topology.exec_on_tpu``.  :func:`takes` says whether the kernels can run
on an operand: :func:`tiles` has to have an answer for its sizes, and the
interpreter cannot run them inside ``shard_map(check_vma=True)`` (their
loops carry values made from constants beside values read from the
operands, which vary over the batch axes).  The caller runs
``silu(causal_conv(...))`` where they cannot.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu import telemetry
from horovod_tpu.ops.grouped_matmul import _interpret, _vma
from horovod_tpu.telemetry import scopes

LANES = 128
# Rows before a tile that a grid step reads beside it: a sublane tile of
# a 16-bit dtype.  Of them the last CARRY, a float32 sublane tile, hold
# the K - 1 rows the first tokens' taps reach back to.
HALO, CARRY = 16, 8
# Rows a loop iteration works on (the forward that writes heads apart
# takes HEAD_ROWS: what it builds once an iteration, the lane masks, is
# spread over more rows, and it holds fewer values at once than the
# backward), lanes of a column slab (with a head width a slab is whole
# heads and whole lanes: a multiple of lcm(head width, 128) up to
# HEAD_SLAB, or that once) and tokens a grid step holds at most
# (docs/kernels.md, "Short convolution": the sweep on the chip).
ROWS, HEAD_ROWS = 64, 128
SLAB, HEAD_SLAB = 256, 384
TILE = 512
# What a kernel may use of a v5e's 128 MiB of VMEM (the compiler's default
# allowance is 16 MiB).
VMEM_LIMIT = 64 * 2 ** 20
# The widest slab's unit: lcm(head width, 128) lanes.
_MAX_UNIT = 1024

_F32 = jnp.float32


class _Plan(NamedTuple):
    """What is static of a call: the widths of the token-major outputs
    (one, the whole width, with a head width), the head width or None,
    the norm's scale or None, its epsilon, and whether there is a bias."""
    widths: tuple
    head_dim: Optional[int]
    scale: Optional[float]
    eps: float
    bias: bool


def _unit(head_dim) -> int:
    return math.lcm(head_dim, LANES) if head_dim else LANES


def _slab(head_dim) -> int:
    """Lanes of a whole column slab."""
    if not head_dim:
        return SLAB
    unit = _unit(head_dim)
    return unit * max(1, HEAD_SLAB // unit)


def _runs(plan: _Plan):
    """The column slabs as runs ``(first column, slab width, slabs,
    output, first column in that output)`` of equal slabs of one output:
    whole lanes and whole heads each; what a width leaves past its whole
    slabs is a run of one.  A run is one loop in the kernels, its slab's
    first column a loop variable, so the kernels' size does not grow with
    the width."""
    step = _slab(plan.head_dim)
    runs, first = [], 0
    for out, width in enumerate(plan.widths):
        whole, rest = divmod(width, step)
        if whole:
            runs.append((first, step, whole, out, 0))
        if rest:
            runs.append((first + whole * step, rest, 1, out, whole * step))
        first += width
    return runs


def _for_each_slab(run, body):
    """``body(cols, off)`` for every slab of ``run``: ``cols`` its columns
    of ``x``, ``off`` its first column in its output."""
    first, width, slabs, _, off = run
    if slabs == 1:
        body(slice(first, first + width), off)
        return

    def slab(c, carry):
        body(pl.ds(pl.multiple_of(first + c * width, LANES), width),
             pl.multiple_of(off + c * width, LANES))
        return carry

    lax.fori_loop(0, slabs, slab, None)


def _lanes(width: int) -> int:
    return -(-width // LANES) * LANES


def vmem_bytes(tile: int, channels: int, taps: int, head_dim,
               itemsize: int) -> int:
    """VMEM the backward kernel, the larger of the two, takes for a grid
    step of ``tile`` tokens of ``channels`` channels: an estimate from
    above (docs/kernels.md, "Short convolution": what Mosaic asked for at
    eight sizes).  Twice (the pipeline's two buffers) a tile of ``x``, of
    ``dy`` and of ``dx`` and the halo, a head's lanes padded to whole
    registers where the operand is head-major; the gradients' partial
    sums, twice, and the carried rows; a slab's chunk in float32 a score
    of times over for what the loop's body spills; and an eighth more."""
    heads = channels // head_dim if head_dim else 1
    wide = heads * _lanes(head_dim) if head_dim else _lanes(channels)
    blocks = (tile * (2 * _lanes(channels) + wide) + HALO * _lanes(channels)
              ) * itemsize
    sums = (taps + 1) * CARRY * _lanes(channels) * 4
    return (2 * blocks + 2 * sums + CARRY * _lanes(channels) * 4
            + 20 * max(ROWS, HEAD_ROWS) * _slab(head_dim) * 4) * 9 // 8


def tiles(t: int, channels: int, taps: int, head_dim=None, widths=None,
          itemsize: int = 2):
    """Tokens a grid step holds for ``t`` tokens of ``channels`` channels
    under ``taps`` taps: the largest power of two from :data:`HALO` up to
    :data:`TILE` that divides ``t`` and that :data:`VMEM_LIMIT` holds.
    None where the kernels cannot run these sizes: the taps have to reach
    back no further than :data:`CARRY` rows, the length has to be whole
    halos, a head width has to divide the channels into heads whose
    common multiple with a register's lanes is a slab, and without one
    every output's width has to be whole lanes."""
    if not 1 <= taps <= CARRY + 1 or t % HALO or t <= 0:
        return None
    if head_dim:
        if (widths is not None and tuple(widths) != (channels,)
                or channels % head_dim or _unit(head_dim) > _MAX_UNIT):
            return None
    elif any(w % LANES or w <= 0 for w in widths or (channels,)) or (
            widths is not None and sum(widths) != channels):
        return None
    tile = TILE
    while tile >= HALO:
        if t % tile == 0 and vmem_bytes(
                tile, channels, taps, head_dim, itemsize) <= VMEM_LIMIT:
            return tile
        tile //= 2
    return None


def takes(x, taps: int, head_dim=None, widths=None, channels=None) -> bool:
    """Whether the kernels can run the convolution of ``taps`` taps over
    an operand ``x`` [B, T, C] (or over ``channels`` channels of what is
    projected from it) into heads of ``head_dim`` or outputs of
    ``widths``, read for its length and dtype, the mesh that executes it
    and the axes it varies over: sizes :func:`tiles` has an answer for,
    and not the interpreter inside ``shard_map(check_vma=True)`` (the
    module's docstring)."""
    return (x.ndim == 3 and tiles(
        x.shape[1], channels or x.shape[2], taps, head_dim, widths,
        x.dtype.itemsize) is not None and not (_interpret(x) and _vma(x)))


def _sigmoid(v):
    """``1 / (1 + exp(-v))`` to float32's precision from the reciprocal
    unit's estimate and two Newton steps (whatever the estimate's
    precision: the interpreter's is bfloat16's): a float32 division costs
    the vector unit half as many operations again (docs/kernels.md).  The
    exponent is held where ``exp`` stays finite, so that no ``inf * 0`` is
    made."""
    d = 1.0 + jnp.exp(jnp.minimum(-v, 80.0))
    r = pl.reciprocal(d, approx=True)
    r = r * (2.0 - d * r)
    return r * (2.0 - d * r)


def _window(ext, first: int, rows: int):
    """``rows`` rows of ``ext`` from row ``first``: a rotation along the
    sublanes and a cut at a register's edge (a cut off it leaves Mosaic
    values at different sublane offsets to realign wherever they meet:
    docs/kernels.md)."""
    if first % CARRY == 0:
        return ext[first:first + rows]
    return pltpu.roll(ext, ext.shape[0] - first, 0)[:rows]


def _shifted(ext, rows: int, taps: int):
    """The ``taps`` views of ``ext`` [CARRY + rows, W] (the rows before a
    chunk, then the chunk) that the taps multiply: view ``j`` holds, at
    the chunk's row ``i``, row ``i - (taps - 1) + j``."""
    return [_window(ext, CARRY - (taps - 1) + j, rows) for j in range(taps)]


def _pre(before, xf, w, bias):
    """The pre-activation of a chunk ``xf`` [rows, W] float32 with the
    :data:`CARRY` rows ``before`` it, in ``causal_conv``'s order of
    operations, and the taps' views."""
    views = _shifted(jnp.concatenate([before, xf], axis=0), xf.shape[0],
                     w.shape[0])
    acc = w[0:1] * views[0]
    for j in range(1, w.shape[0]):
        acc = acc + w[j:j + 1] * views[j]
    return (acc if bias is None else acc + bias), views


def _head_sums(v, d: int):
    """``v`` [rows, W] summed over each head's ``d`` lanes, every lane
    holding its own head's sum: the slab stays token-major (no head is
    cut out of it), a head's lanes are masked in the registers it
    touches and reduced across the lanes once, and a register's lanes
    take the sums of the heads that share it."""
    rows, width = v.shape
    sums = []
    for lo in range(0, width, d):
        first, last = lo // LANES * LANES, min(_lanes(lo + d), width)
        lane = first + lax.broadcasted_iota(jnp.int32, (rows, last - first), 1)
        sums.append(jnp.sum(
            jnp.where((lane >= lo) & (lane < lo + d), v[:, first:last], 0.0),
            axis=-1, keepdims=True))
    blocks = []
    for first in range(0, width, LANES):
        last = min(first + LANES, width)
        lane = first + lax.broadcasted_iota(jnp.int32, (rows, last - first), 1)
        heads = range(first // d, -(-last // d))
        block = jnp.broadcast_to(sums[heads[0]], lane.shape)
        for a in heads[1:]:
            block = jnp.where(lane >= a * d, sums[a], block)
        blocks.append(block)
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)


def _head_lanes(blocks, lo: int, d: int):
    """Lanes ``lo`` to ``lo + d`` of a slab given as its registers' worth
    of columns (``blocks[b]`` [rows, <= 128] holds lanes ``128 b`` up),
    moved to lane 0: [rows, d].  A head that starts inside a register
    takes that register's upper lanes and the next one's lower lanes by
    one select, then one rotation along the lanes."""
    shift, out = lo % LANES, []
    for at in range(lo - shift, lo - shift + _lanes(d), LANES):
        block = blocks[at // LANES]
        if shift:
            if at + LANES < lo + d:      # the head goes on in the next one
                lane = lax.broadcasted_iota(jnp.int32, block.shape, 1)
                block = jnp.where(lane >= shift, block,
                                  _full(blocks[at // LANES + 1]))
            block = pltpu.roll(_full(block), LANES - shift, 1)
        out.append(block)
    out = out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)
    return out[:, :d]


def _full(block):
    """``block`` [rows, <= 128] with its lanes filled up to a register's."""
    short = LANES - block.shape[1]
    return block if not short else jnp.concatenate(
        [block, jnp.zeros((block.shape[0], short), block.dtype)], axis=1)


def _chunk_rows(i, rows: int):
    return pl.ds(pl.multiple_of(i * rows, rows), rows)


def _fold(v):
    """``v`` [rows, W] summed down to :data:`CARRY` rows: row ``r`` holds
    the sum of the rows ``r`` mod :data:`CARRY`."""
    return sum(v[g:g + CARRY] for g in range(0, v.shape[0], CARRY))


def _unpack(refs, plan: _Plan, outputs: int):
    """``refs`` as (x, halo, taps, bias or None, the rest)."""
    x_ref, halo_ref, w_ref, *rest = refs
    bias_ref = rest.pop(0) if plan.bias else None
    return x_ref, halo_ref, w_ref, bias_ref, rest[:outputs], rest[outputs:]


def _halo(halo_ref, cols, none):
    """The :data:`CARRY` rows before the tile, float32; zeros where
    ``none`` (the sequence's first tile)."""
    rows = halo_ref[:, cols].astype(_F32)[HALO - CARRY:]
    return jnp.where(none, 0.0, rows)


def _fwd_kernel(*refs, plan: _Plan, rows: int):
    x_ref, halo_ref, w_ref, bias_ref, out_refs, _ = _unpack(
        refs, plan, len(plan.widths))
    d = plan.head_dim
    first_tile = pl.program_id(1) == 0
    for run in _runs(plan):
        width, out_ref = run[1], out_refs[run[3]]

        def slab(cols, off, width=width, out_ref=out_ref):
            w = w_ref[:, cols]
            bias = None if bias_ref is None else bias_ref[:, cols]

            def chunk(i, before):
                r = _chunk_rows(i, rows)
                xf = x_ref[r, cols].astype(_F32)
                pre, _ = _pre(before, xf, w, bias)
                y = pre * _sigmoid(pre)
                if plan.scale is not None:
                    y = y * lax.rsqrt(_head_sums(y * y, d) + plan.eps)
                    if plan.scale != 1.0:
                        y = y * plan.scale
                if d is None:
                    out_ref[r, pl.ds(off, width)] = y.astype(out_ref.dtype)
                else:
                    blocks = [y[:, at:at + LANES]
                              for at in range(0, width, LANES)]
                    for a in range(width // d):
                        out_ref[off // d + a, r, :] = _head_lanes(
                            blocks, a * d, d).astype(out_ref.dtype)
                return xf[rows - CARRY:]

            lax.fori_loop(0, x_ref.shape[0] // rows, chunk,
                          _halo(halo_ref, cols, first_tile))

        _for_each_slab(run, slab)


def _bwd_kernel(*refs, plan: _Plan, rows: int):
    x_ref, halo_ref, w_ref, bias_ref, dy_refs, rest = _unpack(
        refs, plan, len(plan.widths))
    dx_ref, dw_ref, *db_ref, after_ref = rest
    d, taps = plan.head_dim, w_ref.shape[0]
    chunks = x_ref.shape[0] // rows
    step = pl.program_id(1)
    # The tiles are walked from the last: grid step 0 has no rows after
    # it, the last grid step none before.
    last_tile, first_tile = step == 0, step == pl.num_programs(1) - 1

    @pl.when((pl.program_id(0) == 0) & last_tile)
    def _start():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        for ref in db_ref:
            ref[...] = jnp.zeros_like(ref)

    for run in _runs(plan):
        width, dy_ref = run[1], dy_refs[run[3]]

        def slab(cols, off, width=width, dy_ref=dy_ref):
            w = w_ref[:, cols]
            bias = None if bias_ref is None else bias_ref[:, cols]
            halo = _halo(halo_ref, cols, first_tile)

            def chunk(n, carry):
                after, dw, db = carry
                i = chunks - 1 - n           # the tile's last chunk first
                r = _chunk_rows(i, rows)
                xf = x_ref[r, cols].astype(_F32)
                prev = x_ref[pl.ds(pl.multiple_of(
                    jnp.maximum(i * rows - HALO, 0), HALO), HALO), cols]
                before = jnp.where(i > 0, prev.astype(_F32)[HALO - CARRY:],
                                   halo)
                pre, views = _pre(before, xf, w, bias)
                sig = _sigmoid(pre)
                if d is None:
                    dz = dy_ref[r, pl.ds(off, width)].astype(_F32)
                else:
                    heads = [dy_ref[off // d + a, r, :].astype(_F32)
                             for a in range(width // d)]
                    dz = heads[0] if len(heads) == 1 else jnp.concatenate(
                        heads, axis=1)
                if plan.scale is not None:
                    # y = scale z inv, inv = (sum z^2 + eps)^-1/2 a head.
                    z = pre * sig
                    inv = lax.rsqrt(_head_sums(z * z, d) + plan.eps)
                    if plan.scale != 1.0:
                        dz = dz * plan.scale
                    dz = inv * (dz - z * (inv * inv * _head_sums(dz * z, d)))
                dpre = dz * (sig * (1.0 + pre * (1.0 - sig)))
                # x_{t-K+1+j} met w_j in pre_t: dx_t collects
                # w_j dpre_{t+K-1-j}.
                dext = jnp.concatenate([dpre, after], axis=0)
                dx = w[0:1] * _window(dext, taps - 1, rows)
                for j in range(1, taps):
                    dx = dx + w[j:j + 1] * _window(dext, taps - 1 - j, rows)
                dx_ref[r, cols] = dx.astype(dx_ref.dtype)
                dw = tuple(acc + _fold(dpre * view)
                           for acc, view in zip(dw, views))
                if db_ref:
                    db = db + _fold(dpre)
                return dpre[:CARRY], dw, db

            zero = jnp.zeros((CARRY, width), _F32)
            after, dw, db = lax.fori_loop(
                0, chunks, chunk,
                (jnp.where(last_tile, 0.0, after_ref[:, cols]),
                 (zero,) * taps, zero if db_ref else None))
            after_ref[:, cols] = after
            for j in range(taps):
                dw_ref[j * CARRY:(j + 1) * CARRY, cols] += dw[j]
            for ref in db_ref:
                ref[:, cols] += db

        _for_each_slab(run, slab)


def _specs(plan: _Plan, tile: int, channels: int, taps: int, tile_of):
    """Block specs of a tile of ``x``, of its halo, of the taps and the
    bias, and of each output; ``tile_of(t)`` is the tile grid step ``t``
    works on."""
    per = tile // HALO

    def rows(width):
        return pl.BlockSpec((None, tile, width),
                            lambda b, t: (b, tile_of(t), 0))

    halo = pl.BlockSpec(
        (None, HALO, channels),
        lambda b, t: (b, jnp.maximum(tile_of(t) * per - 1, 0), 0))
    whole = [pl.BlockSpec((taps, channels), lambda b, t: (0, 0))]
    if plan.bias:
        whole.append(pl.BlockSpec((1, channels), lambda b, t: (0, 0)))
    if plan.head_dim:
        heads = channels // plan.head_dim
        outs = [pl.BlockSpec((heads, tile, plan.head_dim),
                             lambda b, t: (b, tile_of(t), 0))]
    else:
        outs = [rows(w) for w in plan.widths]
    return rows(channels), halo, whole, outs


def _out_shapes(plan: _Plan, x, vma):
    bsz, t, channels = x.shape
    if plan.head_dim:
        return [jax.ShapeDtypeStruct(
            (bsz * (channels // plan.head_dim), t, plan.head_dim), x.dtype,
            vma=vma)]
    return [jax.ShapeDtypeStruct((bsz, t, w), x.dtype, vma=vma)
            for w in plan.widths]


def _sizes(plan: _Plan, x, w, rows: int):
    (_, t, channels), taps = x.shape, w.shape[0]
    tile = tiles(t, channels, taps, plan.head_dim, plan.widths,
                 x.dtype.itemsize)
    return t, channels, taps, tile, min(rows, tile)


# The calls are jitted with what is static among their arguments, and
# inlined: the mixers of a step, each traced forward, recomputed and
# backward, share one traced kernel and one lowering a kind.

@functools.partial(jax.jit, static_argnames=("plan", "interpret"),
                   inline=True)
def _fwd_call(x, w, bias, *, plan: _Plan, interpret: bool):
    t, channels, taps, tile, rows = _sizes(
        plan, x, w, HEAD_ROWS if plan.head_dim else ROWS)
    operands = (x, x, w) + ((bias,) if plan.bias else ())
    tile_spec, halo, whole, outs = _specs(plan, tile, channels, taps,
                                          lambda t_i: t_i)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan, rows=rows),
        out_shape=_out_shapes(plan, x, _vma(*operands)),
        grid=(x.shape[0], t // tile),
        in_specs=[tile_spec, halo, *whole],
        out_specs=outs,
        interpret=interpret, name=scopes.SHORT_CONV_FWD,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
    )(*operands)


@functools.partial(jax.jit, static_argnames=("plan", "interpret"),
                   inline=True)
def _bwd_call(x, w, bias, dys, *, plan: _Plan, interpret: bool):
    t, channels, taps, tile, rows = _sizes(plan, x, w, ROWS)
    operands = (x, x, w) + ((bias,) if plan.bias else ()) + tuple(dys)
    vma = _vma(*operands)
    last = t // tile - 1
    tile_spec, halo, whole, outs = _specs(plan, tile, channels, taps,
                                          lambda t_i: last - t_i)

    def sums(n):
        return (jax.ShapeDtypeStruct((n * CARRY, channels), _F32, vma=vma),
                pl.BlockSpec((n * CARRY, channels), lambda b, t: (0, 0)))

    partial_sums = [sums(taps)] + ([sums(1)] if plan.bias else [])
    return pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan, rows=rows),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma)]
        + [shape for shape, _ in partial_sums],
        grid=(x.shape[0], last + 1),
        in_specs=[tile_spec, halo, *whole, *outs],
        out_specs=[tile_spec] + [spec for _, spec in partial_sums],
        scratch_shapes=[pltpu.VMEM((CARRY, channels), _F32)],
        interpret=interpret, name=scopes.SHORT_CONV_BWD,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
    )(*operands)


def _forward(x, w, bias, plan):
    return tuple(_fwd_call(x, w, bias, plan=plan, interpret=_interpret(x)))


_conv = jax.custom_vjp(_forward, nondiff_argnums=(3,))


def _conv_fwd(x, w, bias, plan):
    # Nothing is kept for the backward but the operands.
    return _forward(x, w, bias, plan), (x, w, bias)


def _conv_bwd(plan, residuals, dys):
    x, w, bias = residuals
    dx, dw, *db = _bwd_call(x, w, bias, dys, plan=plan,
                            interpret=_interpret(x))
    dw = dw.reshape(w.shape[0], CARRY, -1).sum(axis=1).astype(w.dtype)
    db = (db[0].sum(axis=0).reshape(bias.shape).astype(bias.dtype)
          if db else None)
    return dx, dw, db


_conv.defvjp(_conv_fwd, _conv_bwd)


def short_conv(x, w, bias=None, *, widths=None, head_dim=None,
               norm_scale=None, eps: float = 1e-6):
    """``silu(causal_conv(x, w) + bias)`` and what follows it up to the
    rounding: ``x`` [B, T, C] in the model dtype, ``w`` [K, C] and
    ``bias`` [C] or None in float32.

    Without ``head_dim``: a tuple of token-major arrays ``[B, T, width]``
    in ``x``'s dtype, the columns cut at ``widths`` (one array of the
    whole width where it is None).  With it: one head-major array ``[B *
    H, T, head_dim]``, ``H = C / head_dim``, each head's vector divided by
    its L2 norm (``sqrt(sum of squares + eps)``) and multiplied by
    ``norm_scale`` where that is not None.  Sizes are ones that
    :func:`takes` accepts.  Differentiable in ``x``, ``w`` and ``bias``."""
    if norm_scale is not None and not head_dim:
        raise ValueError("short conv: a norm needs the head width")
    channels = x.shape[2]
    plan = _Plan(tuple(widths) if widths is not None else (channels,),
                 head_dim, None if norm_scale is None else float(norm_scale),
                 float(eps), bias is not None)
    if tiles(x.shape[1], channels, w.shape[0], head_dim, plan.widths,
             x.dtype.itemsize) is None:
        raise ValueError(
            "short conv: the kernels do not take (tokens, channels, taps, "
            f"head width, widths) = {(x.shape[1], channels, w.shape[0])}"
            f" + {(head_dim, widths)}: tiles(), takes()")
    if bias is not None:
        bias = bias.reshape(1, channels)
    out = _conv(x, w, bias, plan)
    return out[0] if head_dim or widths is None else out


def record_rows(layer, rows: int, path: str) -> None:
    """Trace-time series (what was compiled into the step, beside
    ``hvd_gdn_blocks_total`` and ``hvd_ssm_chunks_total``): the rows the
    short convolutions of mixer layer ``layer`` run over per step on one
    device (batch x T a convolution: three in a linear-attention layer,
    one in a Mamba-2 layer), by what runs them (the mixer's
    ``conv_path``)."""
    if not telemetry.enabled():
        return
    telemetry.counter(
        "hvd_short_conv_rows_total",
        "Rows the short causal convolutions of the traced mixer layer run "
        "over per step on one device (batch x T a convolution), by what "
        "runs them (path: kernel | xla)",
        layer=str(layer), path=path).inc(rows)
