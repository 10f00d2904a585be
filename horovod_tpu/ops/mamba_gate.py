"""The gate of a Mamba-1 mixer as a Pallas TPU kernel pair that also makes
the move between the selective scan's layout and the out projection's.

Per token and channel, ``y`` the scan's output and ``z`` the gate's
projection (the mathematics is the ``jax.numpy`` line of
:func:`horovod_tpu.models.mamba1.gate_xla`, the oracle and what runs where
these kernels do not)::

    out = y * silu(z)           silu(z) = z * sigmoid(z)

**The layout move is the kernel's reason.**  The scan's kernels
(:mod:`horovod_tpu.ops.selective_scan`) leave ``y`` float32 ``[B, T,
C / 128, 128]``: a token's 1024 channels one ``[8, 128]`` register.  The
out projection wants its operand ``[B, T, C]`` in the model dtype: a token
one sublane.  On a TPU the two tiled layouts differ, so the move is a real
copy; left to XLA it landed with the gate's float32 ``exp`` inside the
fusions of the out projection's matmuls (docs/kernels.md, "Mamba-1
gate").  Here ``y`` is read **as the scan leaves it**, as rows ``[T *
C / 128, 128]`` (row ``g + (C / 128) t`` holds channels ``128 g ..`` of
token ``t``; the wrapper's reshapes fold into a bitcast), and a register
of the out projection's layout (16 tokens of 128 channels) is one
**strided sublane load**: rows ``g + (C / 128)(t0 + k)``, ``k = 0 .. 15``.
The load slots of an elementwise kernel are idle, so the move is free; no
value crosses sublanes in a register.  The backward writes ``dy`` the same
way with a strided store.

**Grid.**  ``(batch, T / tile)``, both ``parallel``; a grid step holds a
tile of tokens at the full width (:func:`tiles`).  Inside it a loop walks
the tokens sixteen at a time (a 16-bit dtype's sublane tile) and, unrolled,
a token's ``C / 128`` lane groups: ``y`` and ``z`` are read once,
everything is float32 in registers, and what leaves is rounded once.

**Backward.**  One kernel reads ``y``, ``z`` and the out projection's ``d
out``, recomputes ``sigmoid(z)``, and writes ``dy`` (float32, the scan's
layout: what ``mamba_scan_bwd`` reads) and ``dz`` (token-major, ``z``'s
dtype), **in place of** ``y`` and ``z`` (``input_output_aliases``: the
backward pass is their last reader, and the step's buffer assignment packs
0.31 GiB tighter for it: docs/kernels.md).  Nothing is kept for the
backward but the operands.

**Precision.**  Float32 from the operands to the one rounding to ``z``'s
dtype (the model's), in the ``jax.numpy`` line's order of operations;
``dy`` leaves in ``y``'s dtype, float32.

**Where it runs.**  As ``selective_scan``: compiled by Mosaic where the
executing mesh is TPU, in the Pallas interpreter elsewhere; :func:`takes`
says whether the kernels can run on an operand (:func:`tiles` has an
answer for its sizes, and not the interpreter inside
``shard_map(check_vma=True)``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu import telemetry
from horovod_tpu.ops.grouped_matmul import _interpret, _vma
from horovod_tpu.ops.selective_scan import LANES, SLAB, VMEM_LIMIT
from horovod_tpu.ops.short_conv import _sigmoid
from horovod_tpu.telemetry import scopes

# Tokens a grid step holds at most (docs/kernels.md, "Mamba-1 gate"), and
# tokens a step of its loop moves: a 16-bit dtype's sublane tile.
TILE = 256
ROWS = 16

_F32 = jnp.float32


def vmem_bytes(tile: int, channels: int, y_itemsize: int = 4,
               itemsize: int = 2) -> int:
    """VMEM the backward kernel, the larger of the two, takes for a grid
    step of ``tile`` tokens of ``channels`` channels: twice (the
    pipeline's two buffers) a tile of ``y`` and of ``dy``, of ``z``, ``d
    out`` and ``dz``, and a MiB for what the loop's body spills."""
    return 2 * tile * channels * (2 * y_itemsize + 3 * itemsize) + 2 ** 20


def tiles(t: int, channels: int, y_itemsize: int = 4, itemsize: int = 2):
    """Tokens a grid step holds for ``t`` tokens of ``channels`` channels:
    the largest power of two from :data:`ROWS` up to :data:`TILE` that
    divides ``t`` and that :data:`VMEM_LIMIT` holds.  None where the
    kernels cannot run these sizes: the channels have to be whole slabs of
    the scan's (1024: ``y`` is then whole registers a token), the length
    whole sublane tiles of a 16-bit dtype."""
    if channels <= 0 or channels % SLAB or t <= 0 or t % ROWS:
        return None
    tile = TILE
    while tile >= ROWS:
        if t % tile == 0 and vmem_bytes(tile, channels, y_itemsize,
                                        itemsize) <= VMEM_LIMIT:
            return tile
        tile //= 2
    return None


def takes(u, channels: int) -> bool:
    """Whether the kernels can run the gate over the ``channels`` channels
    projected from an operand ``u`` [B, T, d] (the gate ``z`` in ``u``'s
    dtype, ``y`` float32 as the scan leaves it), read for its length and
    dtype, the mesh that executes it and the axes it varies over: sizes
    :func:`tiles` has an answer for, and not the interpreter inside
    ``shard_map(check_vma=True)`` (``selective_scan``'s reason)."""
    return (u.ndim == 3 and tiles(
        u.shape[1], channels, itemsize=u.dtype.itemsize) is not None
            and not (_interpret(u) and _vma(u)))


def _for_each_register(tile: int, groups: int, body) -> None:
    """``body(scan rows, tokens, lanes)`` for every 16 tokens x 128
    channels of a tile: the strided rows of the scan's layout that hold
    them, and where they lie token-major."""
    def step(i, carry):
        t0 = pl.multiple_of(i * ROWS, ROWS)
        for g in range(groups):
            body(pl.ds(t0 * groups + g, ROWS, stride=groups),
                 pl.ds(t0, ROWS), pl.ds(g * LANES, LANES))
        return carry

    lax.fori_loop(0, tile // ROWS, step, None)


def _fwd_kernel(y_ref, z_ref, out_ref):
    tile, channels = z_ref.shape

    def body(rows, tokens, lanes):
        z = z_ref[tokens, lanes].astype(_F32)
        out = y_ref[rows, :].astype(_F32) * (z * _sigmoid(z))
        out_ref[tokens, lanes] = out.astype(out_ref.dtype)

    _for_each_register(tile, channels // LANES, body)


def _bwd_kernel(y_ref, z_ref, dout_ref, dy_ref, dz_ref):
    tile, channels = z_ref.shape

    def body(rows, tokens, lanes):
        z = z_ref[tokens, lanes].astype(_F32)
        g = dout_ref[tokens, lanes].astype(_F32)
        sig = _sigmoid(z)
        dy_ref[rows, :] = (g * (z * sig)).astype(dy_ref.dtype)
        dz_ref[tokens, lanes] = (
            g * y_ref[rows, :].astype(_F32)
            * (sig * (1.0 + z * (1.0 - sig)))).astype(dz_ref.dtype)

    _for_each_register(tile, channels // LANES, body)


def _specs(tile: int, channels: int):
    """Block specs of a tile in the scan's layout (as rows of 128 lanes)
    and of a token-major tile."""
    groups = channels // LANES
    return (pl.BlockSpec((None, tile * groups, LANES),
                         lambda b, t: (b, t, 0)),
            pl.BlockSpec((None, tile, channels), lambda b, t: (b, t, 0)))


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"),
    vmem_limit_bytes=VMEM_LIMIT)


# The calls are jitted with what is static among their arguments, and
# inlined: the Mamba layers of a step, each traced forward, recomputed and
# backward, share one traced kernel and one lowering a kind.

@functools.partial(jax.jit, static_argnames=("tile", "interpret"),
                   inline=True)
def _fwd_call(y, z, *, tile: int, interpret: bool):
    bsz, t, channels = z.shape
    scan, rows = _specs(tile, channels)
    return pl.pallas_call(
        _fwd_kernel,
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype, vma=_vma(y, z)),
        grid=(bsz, t // tile),
        in_specs=[scan, rows],
        out_specs=rows,
        interpret=interpret, name=scopes.MAMBA_GATE_FWD,
        compiler_params=_COMPILER_PARAMS,
    )(y, z)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"),
                   inline=True)
def _bwd_call(y, z, dout, *, tile: int, interpret: bool):
    bsz, t, channels = z.shape
    scan, rows = _specs(tile, channels)
    vma = _vma(y, z, dout)
    return pl.pallas_call(
        _bwd_kernel,
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype, vma=vma),
                   jax.ShapeDtypeStruct(z.shape, z.dtype, vma=vma)],
        grid=(bsz, t // tile),
        in_specs=[scan, rows, rows],
        out_specs=[scan, rows],
        # dy over y, dz over z: a block is read before its grid step and
        # written after it, and nothing reads either operand later.
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret, name=scopes.MAMBA_GATE_BWD,
        compiler_params=_COMPILER_PARAMS,
    )(y, z, dout)


def _forward(y, z, tile):
    return _fwd_call(y, z, tile=tile, interpret=_interpret(z))


_gate = jax.custom_vjp(_forward, nondiff_argnums=(2,))


def _gate_fwd(y, z, tile):
    # Nothing is kept for the backward but the operands.
    return _forward(y, z, tile), (y, z)


def _gate_bwd(tile, residuals, dout):
    y, z = residuals
    return tuple(_bwd_call(y, z, dout, tile=tile, interpret=_interpret(z)))


_gate.defvjp(_gate_fwd, _gate_bwd)


def mamba_gate(y, z):
    """``y * silu(z)`` of the module's docstring: ``y`` [B, T, C] float32
    as :func:`horovod_tpu.ops.selective_scan.mamba_scan` returns it (a
    reshape of the scan's ``[B, T, C / 128, 128]``, which this one's
    folds away), ``z`` [B, T, C] in the model dtype -> [B, T, C] in
    ``z``'s dtype.  Sizes are ones that :func:`takes` accepts.
    Differentiable in both."""
    tile = (tiles(z.shape[1], z.shape[2], y.dtype.itemsize, z.dtype.itemsize)
            if y.shape == z.shape and z.ndim == 3 else None)
    if tile is None:
        raise ValueError(
            "mamba gate: the kernels do not take (y, z) = "
            f"{(y.shape, z.shape)}: tiles(), takes()")
    bsz, t, channels = z.shape
    return _gate(y.reshape(bsz, t * (channels // LANES), LANES), z, tile)


def record_rows(layer, rows: int, path: str) -> None:
    """Trace-time series (what was compiled into the step, beside
    ``hvd_mamba_scan_tokens_total``): the rows the gate of Mamba-1 layer
    ``layer`` runs over per step on one device (batch x T), by what runs
    it (the mixer's ``gate_path``)."""
    if not telemetry.enabled():
        return
    telemetry.counter(
        "hvd_mamba_gate_rows_total",
        "Rows the gate of the traced Mamba-1 layer runs over per step on "
        "one device (batch x T), by what runs it (path: kernel | xla)",
        layer=str(layer), path=path).inc(rows)
