"""Plain attention's heads born in the attention kernels' layout: a Pallas
TPU kernel pair that norms a head, rotates it and moves it in one read and
one write.

Plain attention with a per-head QK-norm and rotary positions
(:func:`horovod_tpu.models.attention.qkv_proj`) leaves its projections
token-major: ``q_proj`` ``[B, T, H * hd]`` and ``k_proj``, ``v_proj``
``[B, T, Hkv * hd]``.  The attention kernels
(:mod:`horovod_tpu.ops.flash_attention`,
:mod:`horovod_tpu.ops.sparse_attention`) take ``[B * H, T, hd]`` and ``[B *
Hkv, T, hd]``.  Between the two lie, per head (the mathematics is
``qkv_proj``'s lines, the oracle and what runs where these kernels do
not)::

    q[h] = rot(rmsnorm(q_proj[h]) * q_scale)
    k[g] = rot(rmsnorm(k_proj[g]) * k_scale)        v[g] = v_proj[g]

**The move is the kernel's reason.**  Left to XLA, the norm over 128 lanes
in float32, the rotate-half concatenation and the transpose to head-major
are passes of their own over arrays of 128 MiB (docs/kernels.md, "Plain
attention's assembly").  Token stays on sublanes and a head's width on
lanes on both sides (:mod:`horovod_tpu.ops.head_major`), so a grid step
reads a tile of tokens at the full width and writes ``[H, tile, hd]``
blocks: every element of the three projections is read once and every
element of q, k, v written once.

**Arithmetic.**  ``parts.rmsnorm``'s and ``rotary``'s, operation for
operation: the statistics in float32, a rounding to the model dtype after
the normalisation, after the scale and after the rotation; the tables are
made by XLA in float32 from ``positions`` as ``rotary`` makes its angles.

**Grid.**  ``(batch, T / tile)``, both ``parallel`` (:func:`tiles`).

**Backward.**  One kernel reads ``dq``, ``dk``, ``dv`` in the attention
kernels' layout and ``q_proj``, ``k_proj`` (the norm's backward needs its
input), works in float32 and writes ``d q_proj``, ``d k_proj``, ``d
v_proj`` whole, token-major, rounded once; the two scales' gradients leave
as one float32 partial sum a grid step, which XLA adds.

**Where it runs.**  Compiled by Mosaic where the executing mesh is TPU, in
the Pallas interpreter elsewhere; :func:`takes` says whether the kernels
can run on an operand.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from horovod_tpu import telemetry
from horovod_tpu.ops.grouped_matmul import _interpret, _vma
from horovod_tpu.ops.head_major import (COMPILER_PARAMS, rotate, tables,
                                        token_tile)
from horovod_tpu.ops.selective_scan import LANES
from horovod_tpu.telemetry import scopes

# Tokens a grid step holds at most: the norm and the three roundings make
# the kernels the vector unit's work as much as the memory's, and a step's
# own cost shows (docs/kernels.md, "Plain attention's assembly": 1.01 ms a
# forward call at 128, 0.74 at 256, 0.63 at 512).
TILE = 512

_F32 = jnp.float32


def vmem_bytes(tile: int, heads: int, kv_heads: int, hd: int,
               itemsize: int = 2) -> int:
    """VMEM either kernel takes at most for a grid step of ``tile``
    tokens, which is the backward's with every key-value head once a query
    head: twice (the pipeline's two buffers) a tile of ``dq``, ``dk``,
    ``dv``, of the two projections the norm reads and of the three
    gradients out, the two float32 tables, and a MiB for what the body
    spills."""
    row = (5 * heads + 3 * kv_heads) * hd * itemsize + hd * 4
    return 2 * tile * row + 2 ** 20


def tiles(t: int, heads: int, kv_heads: int, hd: int, itemsize: int = 2):
    """Tokens a grid step holds for ``t`` tokens of ``heads`` query heads
    over ``kv_heads`` key-value heads of ``hd``:
    :func:`head_major.token_tile`'s of :func:`vmem_bytes`, up to
    :data:`TILE`.  None where the
    kernels cannot run these sizes: a head has to be whole registers wide
    (the attention kernels' blocks), the operands of a 16-bit dtype (the
    roundings between the steps are the model dtype's), the key-value heads
    a divisor of the heads, the length whole sublane tiles."""
    if (heads <= 0 or kv_heads <= 0 or heads % kv_heads or hd <= 0
            or hd % LANES or itemsize != 2):
        return None
    return token_tile(
        t, lambda tile: vmem_bytes(tile, heads, kv_heads, hd, itemsize), TILE)


def takes(h, heads: int, kv_heads: int, hd: int) -> bool:
    """Whether the kernels can make the ``heads`` query heads and
    ``kv_heads`` key-value heads of ``hd`` projected from an operand ``h``
    [B, T, d], read for its length and dtype, the mesh that executes it
    and the axes it varies over: sizes :func:`tiles` has an answer for, and
    not the interpreter inside ``shard_map(check_vma=True)``
    (``selective_scan.takes``'s reason)."""
    return (h.ndim == 3 and tiles(h.shape[1], heads, kv_heads, hd,
                                  h.dtype.itemsize) is not None
            and not (_interpret(h) and _vma(h)))


def _normed(x, eps):
    """``parts.rmsnorm``'s statistics on one head's tile ``x`` [tile, hd]:
    the float32 ``x / rms(x)`` before its rounding, and ``1 / rms(x)``."""
    x = x.astype(_F32)
    rms_inv = lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x * rms_inv, rms_inv


def _head(x, scale, cos, sin, eps):
    """A head of q or k from its projection's tile ``x`` in the model
    dtype, under the float32 ``scale`` [1, hd] (the model dtype's values)."""
    dt = x.dtype
    n = _normed(x, eps)[0].astype(dt)
    y = (n.astype(_F32) * scale).astype(dt)
    return rotate(y.astype(_F32), cos, sin).astype(dt)


def _head_bwd(g, x, scale, cos, sin, eps):
    """The projection's gradient (float32) and the scale's partial sum [1,
    hd] from a head's float32 cotangent ``g`` and the projection's tile
    ``x``."""
    dy = rotate(g, cos, -sin)
    n, rms_inv = _normed(x, eps)
    # The scale multiplied the rounded head; the norm's own gradient is
    # taken at the unrounded one.
    d_scale = jnp.sum(dy * n.astype(x.dtype).astype(_F32), axis=0,
                      keepdims=True)
    dn = dy * scale
    dx = rms_inv * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
    return dx, d_scale


def _fwd_kernel(q_ref, k_ref, v_ref, qs_ref, ks_ref, cos_ref, sin_ref,
                qo_ref, ko_ref, vo_ref, *, eps: float):
    heads, _, hd = qo_ref.shape
    kv_heads = k_ref.shape[-1] // hd
    # Each key-value head once a query head that reads it, or once.
    copies = ko_ref.shape[0] // kv_heads
    cos, sin = cos_ref[...], sin_ref[...]
    q_scale, k_scale = qs_ref[...], ks_ref[...]
    for h in range(heads):
        qo_ref[h] = _head(q_ref[:, h * hd:(h + 1) * hd], q_scale, cos, sin,
                          eps)
    for g in range(kv_heads):
        at = slice(g * hd, (g + 1) * hd)
        k = _head(k_ref[:, at], k_scale, cos, sin, eps)
        v = v_ref[:, at]
        for c in range(copies):
            ko_ref[g * copies + c] = k
            vo_ref[g * copies + c] = v


def _bwd_kernel(dq_ref, dk_ref, dv_ref, q_ref, k_ref, qs_ref, ks_ref,
                cos_ref, sin_ref, dqp_ref, dkp_ref, dvp_ref, dqs_ref,
                dks_ref, *, eps: float):
    heads, _, hd = dq_ref.shape
    kv_heads = k_ref.shape[-1] // hd
    copies = dk_ref.shape[0] // kv_heads
    cos, sin = cos_ref[...], sin_ref[...]
    q_scale, k_scale = qs_ref[...], ks_ref[...]
    dt = dqp_ref.dtype
    d_qs = jnp.zeros((1, hd), _F32)
    d_ks = jnp.zeros((1, hd), _F32)
    for h in range(heads):
        at = slice(h * hd, (h + 1) * hd)
        dx, ds = _head_bwd(dq_ref[h].astype(_F32), q_ref[:, at], q_scale,
                           cos, sin, eps)
        dqp_ref[:, at] = dx.astype(dt)
        d_qs = d_qs + ds
    for g in range(kv_heads):
        at = slice(g * hd, (g + 1) * hd)
        # dK and dV of a key-value head sum over its copies, in float32.
        dk = dk_ref[g * copies].astype(_F32)
        dv = dv_ref[g * copies].astype(_F32)
        for c in range(1, copies):
            dk = dk + dk_ref[g * copies + c].astype(_F32)
            dv = dv + dv_ref[g * copies + c].astype(_F32)
        dx, ds = _head_bwd(dk, k_ref[:, at], k_scale, cos, sin, eps)
        dkp_ref[:, at] = dx.astype(dt)
        dvp_ref[:, at] = dv.astype(dt)
        d_ks = d_ks + ds
    dqs_ref[...] = d_qs
    dks_ref[...] = d_ks


def _specs(tile: int, hd: int):
    """Block specs of a token-major tile ``width`` wide, of a tile of
    ``n`` heads ``[n, tile, hd]``, of a tile of the tables, of a scale and
    of a grid step's partial sum of a scale's gradient."""
    def rows(width):
        return pl.BlockSpec((None, tile, width), lambda b, t: (b, t, 0))

    def folded(n):
        return pl.BlockSpec((n, tile, hd), lambda b, t: (b, t, 0))

    return (rows, folded,
            pl.BlockSpec((tile, hd // 2), lambda b, t: (t, 0)),
            pl.BlockSpec((1, hd), lambda b, t: (0, 0)),
            pl.BlockSpec((None, None, 1, hd), lambda b, t: (b, t, 0, 0)))


# The calls are jitted with what is static among their arguments, and
# inlined: the attention layers of a step, each traced forward, recomputed
# and backward, share one traced kernel and one lowering a kind.

@functools.partial(jax.jit, static_argnames=("heads", "copies", "eps", "tile",
                                             "interpret"), inline=True)
def _fwd_call(q_proj, k_proj, v_proj, q_scale, k_scale, cos, sin, *,
              heads: int, copies: int, eps: float, tile: int,
              interpret: bool):
    bsz, t, wide = q_proj.shape
    kv_wide = k_proj.shape[-1]
    hd = wide // heads
    kv_out = kv_wide // hd * copies
    rows, folded, table, scale, _ = _specs(tile, hd)
    vma = _vma(q_proj, k_proj, v_proj)

    def out(n):
        return jax.ShapeDtypeStruct((bsz * n, t, hd), q_proj.dtype, vma=vma)

    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        out_shape=[out(heads), out(kv_out), out(kv_out)],
        grid=(bsz, t // tile),
        in_specs=[rows(wide), rows(kv_wide), rows(kv_wide), scale, scale,
                  table, table],
        out_specs=[folded(heads), folded(kv_out), folded(kv_out)],
        interpret=interpret, name=scopes.QK_ASSEMBLE_FWD,
        compiler_params=COMPILER_PARAMS,
    )(q_proj, k_proj, v_proj, q_scale, k_scale, cos, sin)


@functools.partial(jax.jit, static_argnames=("eps", "tile", "interpret"),
                   inline=True)
def _bwd_call(dq, dk, dv, q_proj, k_proj, q_scale, k_scale, cos, sin, *,
              eps: float, tile: int, interpret: bool):
    bsz, t, wide = q_proj.shape
    kv_wide = k_proj.shape[-1]
    hd = dq.shape[-1]
    rows, folded, table, scale, partial = _specs(tile, hd)
    vma = _vma(dq, dk, dv, q_proj, k_proj)

    def out(width):
        return jax.ShapeDtypeStruct((bsz, t, width), q_proj.dtype, vma=vma)

    d_scale = jax.ShapeDtypeStruct((bsz, t // tile, 1, hd), _F32, vma=vma)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        out_shape=[out(wide), out(kv_wide), out(kv_wide), d_scale, d_scale],
        grid=(bsz, t // tile),
        in_specs=[folded(dq.shape[0] // bsz), folded(dk.shape[0] // bsz),
                  folded(dv.shape[0] // bsz), rows(wide), rows(kv_wide),
                  scale, scale, table, table],
        out_specs=[rows(wide), rows(kv_wide), rows(kv_wide), partial,
                   partial],
        interpret=interpret, name=scopes.QK_ASSEMBLE_BWD,
        compiler_params=COMPILER_PARAMS,
    )(dq, dk, dv, q_proj, k_proj, q_scale, k_scale, cos, sin)


def _rounded(scale, dtype):
    """A norm's scale as the kernels take it: ``parts.rmsnorm``'s cast to
    the model dtype, held in float32, one row."""
    return scale.astype(dtype).astype(_F32).reshape(1, -1)


def _forward(q_proj, k_proj, v_proj, q_scale, k_scale, positions, heads,
             copies, theta, eps, tile):
    dt = q_proj.dtype
    cos, sin = tables(positions, q_proj.shape[-1] // heads, theta)
    return tuple(_fwd_call(
        q_proj, k_proj, v_proj, _rounded(q_scale, dt), _rounded(k_scale, dt),
        cos, sin, heads=heads, copies=copies, eps=eps, tile=tile,
        interpret=_interpret(q_proj)))


_assemble = jax.custom_vjp(_forward, nondiff_argnums=(6, 7, 8, 9, 10))


def _assemble_fwd(q_proj, k_proj, v_proj, q_scale, k_scale, positions,
                  heads, copies, theta, eps, tile):
    # The norm's backward needs its input; under ``remat`` ``full`` the
    # projections are recomputed anyway.
    return (_forward(q_proj, k_proj, v_proj, q_scale, k_scale, positions,
                     heads, copies, theta, eps, tile),
            (q_proj, k_proj, q_scale, k_scale, positions))


def _assemble_bwd(heads, copies, theta, eps, tile, residuals, cotangents):
    q_proj, k_proj, q_scale, k_scale, positions = residuals
    dq, dk, dv = cotangents
    dt = q_proj.dtype
    cos, sin = tables(positions, dq.shape[-1], theta)
    d_q, d_k, d_v, d_qs, d_ks = _bwd_call(
        dq, dk, dv, q_proj, k_proj, _rounded(q_scale, dt),
        _rounded(k_scale, dt), cos, sin, eps=eps, tile=tile,
        interpret=_interpret(dq))
    return (d_q, d_k, d_v,
            jnp.sum(d_qs, axis=(0, 1, 2)).astype(q_scale.dtype),
            jnp.sum(d_ks, axis=(0, 1, 2)).astype(k_scale.dtype),
            np.zeros(positions.shape, jax.dtypes.float0))


_assemble.defvjp(_assemble_fwd, _assemble_bwd)


def qk_assemble(q_proj, k_proj, v_proj, q_scale, k_scale, positions,
                heads: int, theta: float, eps: float, repeat: bool = False):
    """q ``[B * H, T, hd]`` and k, v ``[B * Hkv, T, hd]`` of the module's
    docstring from ``q_proj`` [B, T, H * hd] and ``k_proj``, ``v_proj`` [B,
    T, Hkv * hd] in the model dtype, the norms' scales ``[hd]``, at
    ``positions`` [T] under the base ``theta``.  With ``repeat`` k and v
    are ``[B * H, T, hd]``: each key-value head written once a query head
    that reads it, for kernels that want equal head counts (its gradient
    then sums over them).  Sizes are ones that :func:`takes` accepts.
    Differentiable in the projections and the scales."""
    bsz, t, wide = q_proj.shape
    hd = wide // heads
    kv_heads = k_proj.shape[-1] // hd
    tile = (tiles(t, heads, kv_heads, hd, q_proj.dtype.itemsize)
            if (wide == heads * hd and k_proj.shape == v_proj.shape == (
                bsz, t, kv_heads * hd)
                and q_scale.shape == k_scale.shape == (hd,)
                and q_proj.dtype == k_proj.dtype == v_proj.dtype) else None)
    if tile is None:
        raise ValueError(
            "plain attention's assembly: the kernels do not take (q_proj, "
            f"k_proj, v_proj) = {(q_proj.shape, k_proj.shape, v_proj.shape)} "
            f"{q_proj.dtype} of {heads} heads: tiles(), takes()")
    return _assemble(q_proj, k_proj, v_proj, q_scale, k_scale, positions,
                     heads, heads // kv_heads if repeat else 1, float(theta),
                     float(eps), tile)


def record_rows(layer, rows: int, path: str) -> None:
    """Trace-time series (what was compiled into the step, beside
    ``hvd_mla_assemble_rows_total``): the rows attention layer ``layer``
    norms a head at a time and rotates per step on one device (batch x T),
    by what runs it (``attention.qk_path``)."""
    if not telemetry.enabled():
        return
    telemetry.counter(
        "hvd_qk_assemble_rows_total",
        "Rows the traced attention layer norms a head at a time, rotates and "
        "lays out as heads per step on one device (batch x T), by what runs "
        "it (path: kernel | xla)",
        layer=str(layer), path=path).inc(rows)
