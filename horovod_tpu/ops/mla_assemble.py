"""Latent attention's heads put together in the flash kernels' layout: a
Pallas TPU kernel pair that rotates, assembles and moves in one read and
one write.

Latent attention (:func:`horovod_tpu.models.attention.latent_qkv`) leaves
its projections token-major: ``q_proj`` ``[B, T, H * hd]``, a head ``[q_n
(nope) | q_r (rope)]``; ``up`` ``[B, T, H * (nope + hd)]``, a head ``[k_n
(nope) | v (hd)]``; and ONE rotary key ``k_r`` ``[B, T, rope]`` that every
head's key ends in.  The flash kernels
(:mod:`horovod_tpu.ops.flash_attention`) take ``[B * H, T, hd]``.  Between
the two lie, per head ``h`` (the mathematics is
:func:`horovod_tpu.models.attention.assemble_xla`, the oracle and what
runs where these kernels do not)::

    q[h] = [q_n[h] | rot(q_r[h])]    k[h] = [k_n[h] | rot(k_r)]    v[h]

**The move is the kernel's reason.**  Left to XLA, the two rotations, the
two concatenations, the slices of ``up`` and the transposes to head-major
are copies of 84 MB arrays, several of them fused into the operands of
the projections' matmuls (docs/kernels.md, "Latent attention's
assembly").  Token stays on sublanes and a head's width on lanes on both
sides, so here a grid step reads a tile of tokens at the full width and
writes ``[H, tile, hd]`` blocks of the three outputs: every element of
``q_proj`` and ``up`` is read once and every element of q, k, v written
once.  A head of ``up`` is ``nope + hd`` wide (448 = 3.5 x 128 lanes in
GLM-4.7-Flash), so every other head starts mid-register: the kernel reads
at the lane offset and Mosaic shifts (the kernels are bound by memory: the
shifts are hidden, docs/kernels.md), and ``w_kvb``'s columns stay as the
model has them.

**Rotation.**  Angles, ``cos`` and ``sin`` are made by XLA in float32 from
``positions`` exactly as :func:`horovod_tpu.models.attention.rotary` makes
them (``[T, rope / 2]``, 1 MB each at T 8192); the kernels rotate the
``rope`` lanes in float32, rotate-half, and round once: ``rotary``'s
arithmetic operation for operation.  The one rotated key is computed once
a tile and written into every head's tail.

**Grid.**  ``(batch, T / tile)``, both ``parallel`` (:func:`tiles`).

**Backward.**  One kernel reads ``dq``, ``dk``, ``dv`` in the flash
kernels' layout and writes ``d q_proj`` and ``d up`` whole, token-major
(the inverse rotation on ``dq``'s tails), and ``d k_r``: the float32 sum
over heads of ``dk``'s tails, rotated back once.  Nothing is kept for the
backward but ``positions``.

**Where it runs.**  Compiled by Mosaic where the executing mesh is TPU, in
the Pallas interpreter elsewhere; :func:`takes` says whether the kernels
can run on an operand.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from horovod_tpu import telemetry
from horovod_tpu.ops.grouped_matmul import _interpret, _vma
# (TILE and VMEM_LIMIT: what ``tiles`` is held to, named here for its
# readers.)
from horovod_tpu.ops.head_major import (COMPILER_PARAMS, TILE, rotate,  # noqa: F401
                                        tables, token_tile)
from horovod_tpu.ops.selective_scan import LANES, VMEM_LIMIT  # noqa: F401
from horovod_tpu.telemetry import scopes

_F32 = jnp.float32


def vmem_bytes(tile: int, heads: int, hd: int, rope: int,
               itemsize: int = 2) -> int:
    """VMEM either kernel takes for a grid step of ``tile`` tokens: twice
    (the pipeline's two buffers) a tile of ``q_proj``, of ``up`` and of q,
    k, v (their gradients, backward), the rotary key or its float32
    gradient and the two float32 tables, and a MiB for what the body
    spills."""
    row = heads * (6 * hd - rope) * itemsize + rope * 4 + rope * 4
    return 2 * tile * row + 2 ** 20


def tiles(t: int, heads: int, hd: int, rope: int, itemsize: int = 2):
    """Tokens a grid step holds for ``t`` tokens of ``heads`` heads of
    ``hd`` (``rope`` of them rotary): :func:`head_major.token_tile`'s of
    :func:`vmem_bytes`, up to :data:`TILE` and within :data:`VMEM_LIMIT`.
    None where the kernels cannot run these sizes: a head has to be whole
    registers wide (the flash kernels' blocks), the rotary part an even
    tail of at most one register and not the whole head, the length whole
    sublane tiles of a 16-bit dtype."""
    if (heads <= 0 or hd <= 0 or hd % LANES or rope <= 0 or rope % 2
            or rope > LANES or rope >= hd):
        return None
    return token_tile(
        t, lambda tile: vmem_bytes(tile, heads, hd, rope, itemsize))


def takes(h, heads: int, hd: int, rope: int) -> bool:
    """Whether the kernels can assemble the ``heads`` heads of ``hd``
    projected from an operand ``h`` [B, T, d], read for its length and
    dtype, the mesh that executes it and the axes it varies over: sizes
    :func:`tiles` has an answer for, and not the interpreter inside
    ``shard_map(check_vma=True)`` (``selective_scan.takes``'s reason)."""
    return (h.ndim == 3 and tiles(h.shape[1], heads, hd, rope,
                                  h.dtype.itemsize) is not None
            and not (_interpret(h) and _vma(h)))


def _fwd_kernel(q_ref, up_ref, kr_ref, cos_ref, sin_ref, qo_ref, ko_ref,
                vo_ref):
    heads, _, hd = qo_ref.shape
    rope = kr_ref.shape[-1]
    nope = hd - rope
    cos, sin = cos_ref[...], sin_ref[...]
    dt = qo_ref.dtype
    k_r = rotate(kr_ref[...].astype(_F32), cos, sin).astype(dt)
    for h in range(heads):
        at, up_at = h * hd, h * (nope + hd)
        qo_ref[h, :, :nope] = q_ref[:, at:at + nope]
        qo_ref[h, :, nope:] = rotate(
            q_ref[:, at + nope:at + hd].astype(_F32), cos, sin).astype(dt)
        ko_ref[h, :, :nope] = up_ref[:, up_at:up_at + nope]
        ko_ref[h, :, nope:] = k_r
        vo_ref[h] = up_ref[:, up_at + nope:up_at + nope + hd]


def _bwd_kernel(dq_ref, dk_ref, dv_ref, cos_ref, sin_ref, dqp_ref, dup_ref,
                dkr_ref):
    heads, tile, hd = dq_ref.shape
    rope = dkr_ref.shape[-1]
    nope = hd - rope
    cos, sin = cos_ref[...], -sin_ref[...]
    dt = dqp_ref.dtype
    d_kr = jnp.zeros((tile, rope), _F32)
    for h in range(heads):
        at, up_at = h * hd, h * (nope + hd)
        dqp_ref[:, at:at + nope] = dq_ref[h, :, :nope]
        dqp_ref[:, at + nope:at + hd] = rotate(
            dq_ref[h, :, nope:].astype(_F32), cos, sin).astype(dt)
        dup_ref[:, up_at:up_at + nope] = dk_ref[h, :, :nope]
        dup_ref[:, up_at + nope:up_at + nope + hd] = dv_ref[h]
        d_kr = d_kr + dk_ref[h, :, nope:].astype(_F32)
    dkr_ref[...] = rotate(d_kr, cos, sin)


def _specs(tile: int, heads: int, hd: int, rope: int):
    """Block specs of a token-major tile ``width`` wide, of a tile of the
    heads' ``[H, tile, hd]`` and of a tile of the tables."""
    def rows(width):
        return pl.BlockSpec((None, tile, width), lambda b, t: (b, t, 0))

    return (rows, pl.BlockSpec((heads, tile, hd), lambda b, t: (b, t, 0)),
            pl.BlockSpec((tile, rope // 2), lambda b, t: (t, 0)))


# The calls are jitted with what is static among their arguments, and
# inlined: the latent-attention layers of a step, each traced forward,
# recomputed and backward, share one traced kernel and one lowering a kind.

@functools.partial(jax.jit, static_argnames=("heads", "tile", "interpret"),
                   inline=True)
def _fwd_call(q_proj, up, k_r, cos, sin, *, heads: int, tile: int,
              interpret: bool):
    bsz, t, wide = q_proj.shape
    hd, rope = wide // heads, k_r.shape[-1]
    rows, folded, table = _specs(tile, heads, hd, rope)
    out = jax.ShapeDtypeStruct((bsz * heads, t, hd), q_proj.dtype,
                               vma=_vma(q_proj, up, k_r))
    return pl.pallas_call(
        _fwd_kernel,
        out_shape=[out, out, out],
        grid=(bsz, t // tile),
        in_specs=[rows(wide), rows(up.shape[-1]), rows(rope), table, table],
        out_specs=[folded, folded, folded],
        interpret=interpret, name=scopes.MLA_ASSEMBLE_FWD,
        compiler_params=COMPILER_PARAMS,
    )(q_proj, up, k_r, cos, sin)


@functools.partial(jax.jit, static_argnames=("heads", "tile", "interpret"),
                   inline=True)
def _bwd_call(dq, dk, dv, cos, sin, *, heads: int, tile: int,
              interpret: bool):
    bh, t, hd = dq.shape
    bsz, rope = bh // heads, 2 * cos.shape[-1]
    rows, folded, table = _specs(tile, heads, hd, rope)
    vma = _vma(dq, dk, dv)
    wide, up_wide = heads * hd, heads * (2 * hd - rope)
    return pl.pallas_call(
        _bwd_kernel,
        out_shape=[jax.ShapeDtypeStruct((bsz, t, wide), dq.dtype, vma=vma),
                   jax.ShapeDtypeStruct((bsz, t, up_wide), dq.dtype,
                                        vma=vma),
                   jax.ShapeDtypeStruct((bsz, t, rope), _F32, vma=vma)],
        grid=(bsz, t // tile),
        in_specs=[folded, folded, folded, table, table],
        out_specs=[rows(wide), rows(up_wide), rows(rope)],
        interpret=interpret, name=scopes.MLA_ASSEMBLE_BWD,
        compiler_params=COMPILER_PARAMS,
    )(dq, dk, dv, cos, sin)


def _forward(q_proj, up, k_r, positions, heads, rope, theta, tile):
    cos, sin = tables(positions, rope, theta)
    return tuple(_fwd_call(q_proj, up, k_r, cos, sin, heads=heads, tile=tile,
                           interpret=_interpret(q_proj)))


_assemble = jax.custom_vjp(_forward, nondiff_argnums=(4, 5, 6, 7))


def _assemble_fwd(q_proj, up, k_r, positions, heads, rope, theta, tile):
    # Nothing is kept for the backward but the positions.
    return (_forward(q_proj, up, k_r, positions, heads, rope, theta, tile),
            positions)


def _assemble_bwd(heads, rope, theta, tile, positions, cotangents):
    dq, dk, dv = cotangents
    cos, sin = tables(positions, rope, theta)
    d_q, d_up, d_kr = _bwd_call(dq, dk, dv, cos, sin, heads=heads, tile=tile,
                                interpret=_interpret(dq))
    return (d_q, d_up, d_kr.astype(dq.dtype),
            np.zeros(positions.shape, jax.dtypes.float0))


_assemble.defvjp(_assemble_fwd, _assemble_bwd)


def mla_assemble(q_proj, up, k_r, positions, heads: int, theta: float):
    """q, k, v ``[B * H, T, hd]`` of the module's docstring from
    ``q_proj`` [B, T, H * hd], ``up`` [B, T, H * (2 hd - rope)] and the
    rotary key ``k_r`` [B, T, rope] before its rotation, all in the model
    dtype, at ``positions`` [T] under the base ``theta``.  Sizes are ones
    that :func:`takes` accepts.  Differentiable in the three."""
    bsz, t, wide = q_proj.shape
    hd, rope = wide // heads, k_r.shape[-1]
    tile = (tiles(t, heads, hd, rope, q_proj.dtype.itemsize)
            if (wide == heads * hd and up.shape == (
                bsz, t, heads * (2 * hd - rope))
                and k_r.shape[:2] == (bsz, t)
                and q_proj.dtype == up.dtype == k_r.dtype) else None)
    if tile is None:
        raise ValueError(
            "latent attention's assembly: the kernels do not take (q_proj, "
            f"up, k_r) = {(q_proj.shape, up.shape, k_r.shape)} of "
            f"{heads} heads: tiles(), takes()")
    return _assemble(q_proj, up, k_r, positions, heads, rope, float(theta),
                     tile)


def record_rows(layer, rows: int, path: str) -> None:
    """Trace-time series (what was compiled into the step, beside
    ``hvd_flash_blocks_total``): the rows latent-attention layer ``layer``
    assembles per step on one device (batch x T), by what runs it (the
    part's ``assemble_path``)."""
    if not telemetry.enabled():
        return
    telemetry.counter(
        "hvd_mla_assemble_rows_total",
        "Rows the traced latent-attention layer rotates and assembles into "
        "heads per step on one device (batch x T), by what runs it (path: "
        "kernel | xla)",
        layer=str(layer), path=path).inc(rows)
