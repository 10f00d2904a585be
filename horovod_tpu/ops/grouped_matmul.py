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

That is true of visits, not of work.  A visit whose group owns the whole
tile multiplies it in one piece.  A visit to a tile its group shares works
on *sub-tiles* of ``SUB_M`` rows and multiplies only those in which the
group has rows: one that is wholly the group's is multiplied and stored (or
accumulated) as it is, one that holds a group boundary is multiplied and
then masked, one that is another visit's costs nothing.  A tile with ``b``
boundaries inside it costs ``tm / SUB_M + b`` sub-tile matmuls over its
``b + 1`` visits at most, where whole-tile visits would cost
``(tm / SUB_M) (b + 1)``; :func:`matmul_rows` is the exact count.

Why not the library call: its ``pallas_call`` carries no ``vma`` on its
outputs, so it cannot be traced inside the training step's
``shard_map(check_vma=True)``; it takes no ``name=``; and its default
tiling of 128 is ten times slower at the OLMoE shapes than the one chosen
here (PERF.md, PR 26, where :func:`jax.lax.ragged_dot` is measured too;
the sweeps are in ``docs/kernels.md``).
This file keeps what the layer needs — no group offset, no sharded
groups, no existing output — and adds those three things.

Three kernels, named for the trace (``horovod_tpu/telemetry/scopes.py``):
``moe_gmm`` ([M, K] x [G, K, N] -> [M, N], the forward), ``moe_gmm_nt``
(the same against the transposed weights, the backward's gradient of the
rows) and ``moe_tgmm`` ([M, K]^T x [M, N] per group -> [G, K, N], the
gradient of the weights; a group without rows gets zeros).

The weights come in the dtype they are stored in.  Where that is the
rows' dtype the two kernels that read them are as they were.  Where it is
wider (float32 masters under bf16 rows) they round a group's block to the
rows' dtype in VMEM, for each piece they multiply, to nearest even: the
bits a cast before the call gives, without a second copy of every group's
weights in HBM that is written each step, kept for the backward pass and
read twice.  A float32 block is twice as long on the way, longer than
the one visit the grid's pipeline gives it, so with K as one tile the
kernel fetches the blocks itself, a group ahead
(:func:`_weights_a_group_ahead`; PERF.md, PR 30).
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
# (PERF.md, PR 26 and PR 28).  A smaller dimension is one tile; a K or N
# that its tile does not divide takes a narrower one (:func:`_tile`).  K and N
# as wide as the OLMoE matrices: a group's weights then stay in VMEM over
# its visits, where a K tile of 1024 fetched them anew on every grid step
# and made a visit that multiplies little wait for 3 MB all the same.
TILE_M, TILE_K, TILE_N = 512, 2048, 2048
# Rows of a sub-tile, the unit in which a visit to a shared tile skips
# rows that are not its group's: the MXU's height, and whole (16, 128)
# tiles of bf16.  A smaller row tile is one sub-tile.
SUB_M = 128
# The blocks of those tiles, double-buffered, and the accumulator take up
# to 40 MB (the weight gradient at K = N = 2048), 52 with float32 weight
# blocks of that size and their rounded copy; the OLMoE products, 2048 x
# 1024, take 32.  The compiler's default allowance is 16 MiB of the
# core's 128.
VMEM_LIMIT_BYTES = 96 * 2 ** 20


def _tile(dim: int, tile: int, what: str) -> int:
    """The tile of a dimension: ``tile`` where it divides ``dim`` (a
    smaller ``dim`` is one tile); else, for K and N, the largest multiple
    of a lane row of 128 under ``tile`` that does (2688 = 21 x 128 takes
    896)."""
    largest = min(tile, dim)
    candidates = [largest]
    if what in ("K", "N"):
        candidates += range(largest // 128 * 128, 0, -128)
    for candidate in candidates:
        if dim % candidate == 0:
            return candidate
    raise ValueError(f"grouped matmul: {what}={dim} is not a multiple "
                     f"of its tile {largest}")


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


def _row_tiles(m: int):
    """``(tm, sub)``: the row tile of ``m`` rows and its sub-tile."""
    tm = _tile(m, TILE_M, "rows")
    return tm, _tile(tm, SUB_M, "the row tile")


def matmul_rows(group_sizes, m: int):
    """Rows the matmuls of one kernel run over, per K and N tile, for
    these ``group_sizes`` [G] (they sum to ``m``): a group is multiplied
    in every sub-tile in which it has rows, so ``m`` when every group
    starts on a sub-tile's edge and at most ``m + (G - 1) * sub``
    (:func:`worst_matmul_rows`).  The exact model of the kernels'
    skipping rule, the same for all three; jit-able."""
    _, sub = _row_tiles(m)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    spans = jnp.where(group_sizes > 0,
                      (ends - 1) // sub - starts // sub + 1, 0)
    return sub * jnp.sum(spans)


def worst_matmul_rows(groups: int, m: int) -> int:
    """The most :func:`matmul_rows` can return: every group but the
    first starts inside a sub-tile."""
    return m + (groups - 1) * _row_tiles(m)[1]


def _for_each_piece(offsets, group, tile, tm: int, sub: int, work):
    """Row tile ``tile`` as ``group``'s visit works on it, in pieces:
    calls ``work(rows, whole, split, mask)`` for each.  ``rows`` slices
    the piece out of a block.  ``whole``: the visit multiplies the piece
    and every row of it is the group's.  ``split``: it multiplies the
    piece and only the rows that ``mask(n)`` ([rows, n]) marks are the
    group's.  Neither: the piece is another visit's.  First the tile
    itself, ``whole`` for the group that owns all of it (one matmul, as
    high as feeds the MXU best) and never ``split`` (no ``mask``); then,
    for a group that shares the tile, its sub-tiles."""
    lo, hi = offsets[group], offsets[group + 1]
    owns_tile = (lo <= tile * tm) & (hi >= tile * tm + tm)
    work(pl.ds(0, tm), owns_tile, jnp.bool_(False), None)

    # A loop, not tm / sub copies: the body is as long as the matmul is
    # wide.
    def sub_tile(s, carry):
        start = pl.multiple_of(s * sub, sub)
        first = tile * tm + start
        whole = (lo <= first) & (hi >= first + sub)
        owned = jnp.maximum(lo, first) < jnp.minimum(hi, first + sub)

        def mask(n):
            rows = first + lax.broadcasted_iota(jnp.int32, (sub, n), 0)
            return (rows >= lo) & (rows < hi)

        work(pl.ds(start, sub), whole & ~owns_tile, owned & ~whole, mask)
        return carry

    lax.fori_loop(0, tm // sub, sub_tile, None)


def _dot(rows, other, contract):
    """MXU matmul of a piece's ``rows`` with float32 accumulation."""
    return lax.dot_general(rows, other, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


def _gmm_kernel(offsets, visit_group, visit_tile, lhs, rhs, out, acc,
                *ahead, tm, sub, tiles_k, transpose_rhs):
    visit, k_i = pl.program_id(1), pl.program_id(2)
    group = visit_group[visit]
    contract = ((1,), (1,)) if transpose_rhs else ((1,), (0,))
    last_k = k_i == tiles_k - 1

    @pl.when(k_i == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    if ahead:
        weights = _weights_a_group_ahead(offsets, visit_group, group, rhs,
                                         *ahead, tm, transpose_rhs)
    else:
        weights = rhs

    def work(rows, whole, split, mask):
        @pl.when(whole | split)
        def _multiply():
            # Weights stored wider than the rows are rounded here, per
            # piece: on the way to the MXU it hides, where once a group
            # into a scratch it stood before the group's first matmul.
            acc[rows, :] += _dot(lhs[rows, :],
                                 weights[...].astype(lhs.dtype), contract)

        @pl.when(last_k & whole)
        def _store():
            out[rows, :] = acc[rows, :].astype(out.dtype)

        if mask is None:
            return

        @pl.when(last_k & split)
        def _store_own_rows():
            # The piece's other rows are another visit's, before or
            # after this one.
            kept = out[rows, :].astype(jnp.float32)
            out[rows, :] = jnp.where(mask(kept.shape[1]), acc[rows, :],
                                     kept).astype(out.dtype)

    _for_each_piece(offsets, group, visit_tile[visit], tm, sub, work)


def _weights_a_group_ahead(offsets, visit_group, group, rhs, buffers,
                           arrived, slot, tm, transpose_rhs):
    """``group``'s block of ``rhs`` (in HBM; K is one tile) in VMEM,
    fetched by this kernel and not by the grid's pipeline, which looks one
    grid step ahead: the block of the group after this one is asked for
    when this group's first visit starts and has all of the group's
    visits to arrive, where the pipeline gives it the last of them.  A
    float32 block of the OLMoE experts is 8 MiB, longer on the way than a
    visit lasts: through the pipeline the kernels lost to the wider fetch
    most of what the step gained from not casting (PERF.md, PR 30).
    ``buffers`` [2, ...] and the DMA semaphores ``arrived`` [2] take the
    groups in turn; ``slot`` (SMEM) is the current group's.  Every fetch
    that is started is waited for: the first group's at the first visit
    of each N tile, another's only where a visit to that group follows."""
    visit, visits = pl.program_id(1), pl.num_programs(1)
    tn = buffers.shape[1] if transpose_rhs else buffers.shape[2]
    columns = pl.ds(pl.multiple_of(pl.program_id(0) * tn, tn), tn)

    def fetch(g, into):
        block = (rhs.at[g, columns, :] if transpose_rhs
                 else rhs.at[g, :, columns])
        return pltpu.make_async_copy(block, buffers.at[into],
                                     arrived.at[into])

    first = visit == 0
    fresh = first | (visit_group[jnp.maximum(visit - 1, 0)] != group)

    @pl.when(first)
    def _cold_start():
        slot[0] = 0
        fetch(group, 0).start()

    @pl.when(fresh & ~first)
    def _take_turns():
        slot[0] = 1 - slot[0]

    now = slot[0]

    @pl.when(fresh)
    def _arrive_and_ask_ahead():
        fetch(group, now).wait()
        lo, hi = offsets[group], offsets[group + 1]
        after = visit + (hi - 1) // tm - lo // tm + 1  # _visits' count

        @pl.when(after < visits)
        def _ask():
            fetch(visit_group[after], 1 - now).start()

    return buffers.at[now]


def _tgmm_kernel(offsets, visit_group, visit_tile, lhs, rhs, out, acc, *,
                 tm, sub):
    visit, last = pl.program_id(2), pl.num_programs(2) - 1
    group = visit_group[visit]
    before = visit_group[jnp.maximum(visit - 1, 0)]
    after = visit_group[jnp.minimum(visit + 1, last)]

    @pl.when((visit == 0) | (before != group))
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    # The rows are the contraction: a piece is so many of its terms.
    def work(rows, whole, split, mask):
        @pl.when(whole)
        def _accumulate():
            acc[...] += _dot(lhs[rows, :], rhs[rows, :], ((0,), (0,)))

        if mask is None:
            return

        @pl.when(split)
        def _accumulate_own_rows():
            own = jnp.where(mask(1), lhs[rows, :].astype(jnp.float32), 0.0)
            acc[...] += _dot(own.astype(lhs.dtype), rhs[rows, :],
                             ((0,), (0,)))

    _for_each_piece(offsets, group, visit_tile[visit], tm, sub, work)

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


def _tiles(m: int, k: int, n: int):
    """``(tm, sub, tk, tn)`` of an [m, k] x [k, n] product."""
    return (*_row_tiles(m), _tile(k, TILE_K, "K"), _tile(n, TILE_N, "N"))


# The calls are jitted with the tiles among their static arguments, and
# inlined: every product of one shape in a step (gate and up, every
# layer) is then one traced kernel and one lowering, not one each.

def _gmm(lhs, rhs, group_sizes, transpose_rhs: bool):
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return _gmm_call(lhs, rhs, group_sizes, transpose_rhs=transpose_rhs,
                     tiles=_tiles(*lhs.shape, n))


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "tiles"),
                   inline=True)
def _gmm_call(lhs, rhs, group_sizes, *, transpose_rhs, tiles):
    (m, k), (tm, sub, tk, tn) = lhs.shape, tiles
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    *metadata, n_visits = _visits(group_sizes, m, tm, visit_empty=False)
    interpret, vma = _interpret(lhs), _vma(lhs, rhs, group_sizes)
    rhs_block = (tn, tk) if transpose_rhs else (tk, tn)
    scratch = [pltpu.VMEM((tm, tn), jnp.float32)]
    if rhs.dtype != lhs.dtype and tk == k:
        # Wider weights, K one tile: the kernel fetches a group's block
        # itself, a group ahead (_weights_a_group_ahead).
        rhs_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch += [pltpu.VMEM((2, *rhs_block), rhs.dtype),
                    pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)]
    else:
        rhs_spec = pl.BlockSpec(
            (None, *rhs_block),
            (lambda n_i, v, k_i, off, vg, vt: (vg[v], n_i, k_i))
            if transpose_rhs else
            (lambda n_i, v, k_i, off, vg, vt: (vg[v], k_i, n_i)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, sub=sub, tiles_k=k // tk,
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
            scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=scopes.MOE_GMM_NT if transpose_rhs else scopes.MOE_GMM,
    )(*metadata, lhs, rhs)


def _tgmm(lhs, rhs, group_sizes):
    return _tgmm_call(lhs, rhs, group_sizes,
                      tiles=_tiles(*lhs.shape, rhs.shape[1]))


@functools.partial(jax.jit, static_argnames="tiles", inline=True)
def _tgmm_call(lhs, rhs, group_sizes, *, tiles):
    (m, k), n, (tm, sub, tk, tn) = lhs.shape, rhs.shape[1], tiles
    groups = group_sizes.shape[0]
    *metadata, n_visits = _visits(group_sizes, m, tm, visit_empty=True)
    interpret, vma = _interpret(lhs), _vma(lhs, rhs, group_sizes)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, sub=sub),
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
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=scopes.MOE_TGMM,
    )(*metadata, lhs, rhs)


@jax.custom_vjp
def grouped_matmul(rows, weights, group_sizes):
    """``rows[start_g:end_g] @ weights[g]`` for each group ``g`` of
    consecutive rows: [M, K] x [G, K, N] -> [M, N] in ``rows.dtype``,
    float32 accumulation.  ``weights`` as they are stored: a dtype other
    than the rows' is rounded to it inside the kernels, and the weights'
    gradient comes back in it.  ``group_sizes`` [G] int32 sums to M **or
    to less**; a group may be empty.  Rows past the last group belong to
    no one: no visit reads them, their tiles are not visited, and what
    the result holds there is not defined (the caller masks it,
    ``models/moe.experts_ffn``).  M is a multiple of its tile (or
    smaller than one), K and N of 128 where their tile does not divide
    them.  Differentiable in ``rows`` and ``weights``."""
    return _gmm(rows, weights, group_sizes, transpose_rhs=False)


def _grouped_matmul_fwd(rows, weights, group_sizes):
    return grouped_matmul(rows, weights, group_sizes), (rows, weights,
                                                        group_sizes)


def _grouped_matmul_bwd(residuals, g):
    rows, weights, group_sizes = residuals
    return (_gmm(g, weights, group_sizes, transpose_rhs=True),
            _tgmm(rows, g, group_sizes).astype(weights.dtype), None)


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
