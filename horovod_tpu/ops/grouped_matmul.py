"""Grouped (ragged) matmul for the mixture-of-experts layer: Pallas TPU
kernels for ``rows[start_g:end_g] @ weights[g]`` over ``G`` groups of
consecutive rows whose sizes are known only at run time.

The algorithm is that of JAX's ``megablox``
(``jax.experimental.pallas.ops.tpu.megablox``): the rows are cut into
tiles of ``tm``; a *visit* is a (row tile, group) pair in which the group
has rows, visits are enumerated on the device from the group sizes and
handed to the kernel as scalar-prefetch arrays that its index maps read;
a tile that two groups share is visited twice and each visit writes only
its own rows.  Nothing is padded to a capacity: a step costs
``M / tm + (groups that start inside a tile)`` visits whatever the sizes.

Why not the library call: its ``pallas_call`` carries no ``vma`` on its
outputs, so it cannot be traced inside the training step's
``shard_map(check_vma=True)``; it takes no ``name=``; and its default
tiling of 128 is ten times slower at the OLMoE shapes than the one chosen
here (PERF.md, PR 26, where :func:`jax.lax.ragged_dot` is measured too).
This file keeps what the layer needs — no group offset, no sharded
groups, no existing output — and adds those three things.

Three kernels, named for the trace (``horovod_tpu/telemetry/scopes.py``):
``moe_gmm`` ([M, K] x [G, K, N] -> [M, N], the forward), ``moe_gmm_nt``
(the same against the transposed weights, the backward's gradient of the
rows) and ``moe_tgmm`` ([M, K]^T x [M, N] per group -> [G, K, N], the
gradient of the weights; a group without rows gets zeros).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.telemetry import scopes

# Row, contraction and output tile of all three kernels, chosen on the
# chip at 65,536 rows in 64 groups, K/N = 2048/1024 and 1024/2048, bf16
# (PERF.md, PR 26).  A smaller dimension is one tile.
TILE_M, TILE_K, TILE_N = 512, 1024, 1024


def _tile(dim: int, tile: int, what: str) -> int:
    tile = min(tile, dim)
    if dim % tile:
        raise ValueError(f"grouped matmul: {what}={dim} is not a multiple "
                         f"of its tile {tile}")
    return tile


def _visits(group_sizes, m: int, tm: int, visit_empty: bool):
    """``(offsets [G+1], visit_group [V], visit_tile [V], n_visits)``:
    group ``g`` holds rows ``offsets[g]:offsets[g+1]``; visit ``v`` works
    on row tile ``visit_tile[v]`` for group ``visit_group[v]``; the first
    ``n_visits`` of the static ``V = M/tm + G - 1`` are real.  Groups in
    order, a group's tiles in order, so a tile that two groups share is
    visited twice in a row.  ``visit_empty``: a group without rows is
    visited once (for its zeros)."""
    groups = group_sizes.shape[0]
    tiles = m // tm
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first = jnp.minimum(starts // tm, tiles - 1)
    count = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1,
                      1 if visit_empty else 0)
    total = tiles + groups - 1
    visit_group = jnp.repeat(jnp.arange(groups, dtype=jnp.int32), count,
                             total_repeat_length=total)
    begin = jnp.cumsum(count) - count
    index = jnp.arange(total, dtype=jnp.int32)
    visit_tile = jnp.minimum(
        first[visit_group] + index - begin[visit_group], tiles - 1)
    return (offsets.astype(jnp.int32), visit_group,
            visit_tile.astype(jnp.int32), jnp.sum(count))


def _own_rows(offsets, group, tile, tm: int, shape):
    """Mask of ``shape`` ([tm, 1] or [tm, n]): the rows of row tile
    ``tile`` that belong to ``group``."""
    rows = tile * tm + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= offsets[group]) & (rows < offsets[group + 1])


def _gmm_kernel(offsets, visit_group, visit_tile, lhs, rhs, out, acc, *,
                tm, tiles_k, transpose_rhs):
    visit, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    contract = ((1,), (1,)) if transpose_rhs else ((1,), (0,))
    acc[...] += lax.dot_general(lhs[...], rhs[...], (contract, ((), ())),
                                preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        # Only this group's rows: the tile's other rows are another
        # visit's, before or after this one.
        mask = _own_rows(offsets, visit_group[visit], visit_tile[visit],
                         tm, acc.shape)
        out[...] = jnp.where(mask, acc[...],
                             out[...].astype(jnp.float32)).astype(out.dtype)


def _tgmm_kernel(offsets, visit_group, visit_tile, lhs, rhs, out, acc, *,
                 tm):
    visit, last = pl.program_id(2), pl.num_programs(2) - 1
    group = visit_group[visit]
    before = visit_group[jnp.maximum(visit - 1, 0)]
    after = visit_group[jnp.minimum(visit + 1, last)]

    @pl.when((visit == 0) | (before != group))
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(offsets[group + 1] > offsets[group])
    def _accumulate():
        mask = _own_rows(offsets, group, visit_tile[visit], tm,
                         (lhs.shape[0], 1))
        own = jnp.where(mask, lhs[...].astype(jnp.float32), 0.0)
        acc[...] += lax.dot(own.T.astype(rhs.dtype), rhs[...],
                            preferred_element_type=jnp.float32)

    @pl.when((visit == last) | (after != group))
    def _store():
        out[...] = acc[...].astype(out.dtype)


def _vma(*arrays):
    # Inside shard_map(check_vma=True) a pallas output must say over which
    # axes it varies: every axis an input varies over.
    from horovod_tpu.parallel._vma import vma_of
    return frozenset().union(*(vma_of(a) for a in arrays))


def _interpret(x) -> bool:
    # On anything but a TPU mesh (the CPU tests) the kernels run in the
    # Pallas interpreter: the same code.  (Inside shard_map(check_vma=True)
    # the interpreter cannot index the visit arrays, which vary over the
    # batch axes, with its own loop counter, which does not; the training
    # step turns the checker off for a model with experts.)
    from horovod_tpu.topology import exec_on_tpu
    return not exec_on_tpu(x)


def _gmm(lhs, rhs, group_sizes, transpose_rhs: bool):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = (_tile(m, TILE_M, "rows"), _tile(k, TILE_K, "K"),
                  _tile(n, TILE_N, "N"))
    *metadata, n_visits = _visits(group_sizes, m, tm, visit_empty=False)
    interpret, vma = _interpret(lhs), _vma(lhs, rhs, group_sizes)
    rhs_spec = (
        pl.BlockSpec((None, tn, tk),
                     lambda n_i, v, k_i, off, vg, vt: (vg[v], n_i, k_i))
        if transpose_rhs else
        pl.BlockSpec((None, tk, tn),
                     lambda n_i, v, k_i, off, vg, vt: (vg[v], k_i, n_i)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=k // tk,
                          transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype, vma=vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec(
                    (tm, tk),
                    lambda n_i, v, k_i, off, vg, vt: (vt[v], k_i)),
                rhs_spec],
            out_specs=pl.BlockSpec(
                (tm, tn),
                lambda n_i, v, k_i, off, vg, vt: (vt[v], n_i)),
            grid=(n // tn, n_visits, k // tk),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=scopes.MOE_GMM_NT if transpose_rhs else scopes.MOE_GMM,
    )(*metadata, lhs, rhs)


def _tgmm(lhs, rhs, group_sizes):
    m, k = lhs.shape
    n = rhs.shape[1]
    groups = group_sizes.shape[0]
    tm, tk, tn = (_tile(m, TILE_M, "rows"), _tile(k, TILE_K, "K"),
                  _tile(n, TILE_N, "N"))
    *metadata, n_visits = _visits(group_sizes, m, tm, visit_empty=True)
    interpret, vma = _interpret(lhs), _vma(lhs, rhs, group_sizes)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype, vma=vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec(
                    (tm, tk),
                    lambda n_i, k_i, v, off, vg, vt: (vt[v], k_i)),
                pl.BlockSpec(
                    (tm, tn),
                    lambda n_i, k_i, v, off, vg, vt: (vt[v], n_i))],
            out_specs=pl.BlockSpec(
                (None, tk, tn),
                lambda n_i, k_i, v, off, vg, vt: (vg[v], k_i, n_i)),
            grid=(n // tn, k // tk, n_visits),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=scopes.MOE_TGMM,
    )(*metadata, lhs, rhs)


@jax.custom_vjp
def grouped_matmul(rows, weights, group_sizes):
    """``rows[start_g:end_g] @ weights[g]`` for each group ``g`` of
    consecutive rows: [M, K] x [G, K, N] -> [M, N] in ``rows.dtype``,
    float32 accumulation.  ``group_sizes`` [G] int32 sums to M; a group
    may be empty.  M, K and N are multiples of their tiles (or smaller
    than one).  Differentiable in ``rows`` and ``weights``."""
    return _gmm(rows, weights, group_sizes, transpose_rhs=False)


def _grouped_matmul_fwd(rows, weights, group_sizes):
    return grouped_matmul(rows, weights, group_sizes), (rows, weights,
                                                        group_sizes)


def _grouped_matmul_bwd(residuals, g):
    rows, weights, group_sizes = residuals
    return (_gmm(g, weights, group_sizes, transpose_rhs=True),
            _tgmm(rows, g, group_sizes).astype(weights.dtype), None)


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
