"""The sum into the tokens of a share of an expert layer as Pallas TPU
kernels whose work follows the batch's live rows, not the static prefix.

On a share (:func:`horovod_tpu.models.moe._head_ffn`) the held rows are the
head of the sorted buffer: ``live = sum(group_sizes)`` rows of a static
``prefix`` four times as long.  The two row moves are each other's
transposes::

    rows out    x[j] = h[token[j]]                       j < prefix
    rows back   y[t] = sum of w[a] * out[place[a]]       over token t's live slots a

**Rows out stays XLA's gather**: at ``[16384, 2048]`` bf16 into 65,536
rows it runs at the memory's speed (0.44 ms; a kernel that lays the source
out and moves a row a DMA took 0.75 at a quarter live: docs/kernels.md,
"Row moves of a share").  **Rows back is what costs**: as ``jax.numpy`` it
is a float32 product the prefix long and a scatter-add of the prefix's
rows (7.3 ms there), forward, and again as the gather's gradient.  Here it
is two kernels (:func:`sum_by_token`), and the same two run as the
gather's gradient with unit weights (:func:`rows_by_token`).

**A row is moved by one DMA, and a DMA moves whole tiles.**  A bf16 ``[m,
d]`` array lies in HBM in tiles of ``(8, 128)`` with two *rows* to a 32-bit
word, and Mosaic slices neither it nor a float32 array by one row ("slice
shape must be aligned to tiling (8)").  So the source is first laid out as
**tiles a row** (:func:`row_tiles`, the kernel ``moe_row_tiles``): float32
``[m, d / 128, 128]``, a row's ``d / 128`` lane groups the sublanes of its
own ``d / 1024`` tiles, contiguous, so that ``src.at[r]`` is a legal slice
and one descriptor.  A bf16 is the top half of its float32, so the
widening is exact.  One pass with strided sublane stores (a register holds
16 rows of one lane group; its sublanes go to 16 rows' tiles) over **the
tiles that hold a live row**, their count read on the device (a dynamic
grid, as the grouped matmuls' visits are).

**The sum** (``moe_rows_back``): a grid over token tiles (an SMEM block of
slots each, ``1024 / s`` tokens).  What a step walks is the list of its
tokens' *live* slots and nothing else: the live rows' slots sorted by
token (:func:`by_token`: one key-value sort of the prefix's ``order`` on
the device, and the tiles' offsets into it), so a slot that is not live
costs nothing, not even a test (131,072 scalar tests and conditional DMA
starts were 2.5 of a first form's 3.3 ms).  A live slot starts one DMA of
its row into VMEM; when all have arrived, each is multiplied by its
weight (a scalar from SMEM) and added to its token's accumulator **in the
tiles' own layout** (float32, plain loads and stores); the tile's tokens
are then moved to the ``[tokens, d]`` block by strided loads and rounded
once.  A token with no live slot writes zeros.  There is no scatter-add
and no float32 array of the prefix's length.

**Where it runs.**  Compiled by Mosaic where the executing mesh is TPU, in
the Pallas interpreter elsewhere; :func:`takes` says whether the kernels
can run on an operand (bf16 rows of whole tiles, slots that divide an
SMEM block, and not the interpreter inside ``shard_map(check_vma=True)``).
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
# Sixteen rows' lane group among rows laid out as tiles (a strided sublane
# access) and in a [rows, d] block: the gate's kernels make the same move.
from horovod_tpu.ops.mamba_gate import LANES, ROWS, _for_each_register
from horovod_tpu.telemetry import scopes

# Elements of an SMEM block of a 1-D 32-bit array: XLA tiles such an array
# by 1024, and Mosaic takes a block of whole tiles only.  A grid step of
# the sum holds a block's slots: 1024 / s tokens.
SMEM_BLOCK = 1024
# Elements of the least row: eight lane groups, one (8, 128) tile of
# float32.
ROW_UNIT = 8 * LANES
# Widest row the kernels take: the sum's row buffer is SMEM_BLOCK rows of
# 4 d bytes (16 MiB at 4096), and with the accumulator and the pipeline's
# blocks beside it VMEM_LIMIT holds it.
MAX_WIDTH = 4096
VMEM_LIMIT = 64 * 2 ** 20
# Rows a grid step of the layout kernel holds.
LAYOUT_TILE = 256
_F32 = jnp.float32


def takes(h, slots: int) -> bool:
    """Whether the kernels can sum rows into the tokens of ``h`` [tokens,
    d] for ``slots`` slots a token, read for its dtype and sizes, the mesh
    that executes it and the axes it varies over: bf16 (the top half of
    the float32 a row travels as), ``d`` whole tiles (:data:`ROW_UNIT`)
    and no wider than :data:`MAX_WIDTH`, slots that divide an SMEM block
    into whole sublane tiles of tokens, and not the interpreter inside
    ``shard_map(check_vma=True)`` (``grouped_matmul``'s reason)."""
    tokens, d = h.shape
    return (h.dtype == jnp.bfloat16 and d % ROW_UNIT == 0 and d <= MAX_WIDTH
            and SMEM_BLOCK % slots == 0
            and (SMEM_BLOCK // slots) % ROWS == 0
            and tokens % (SMEM_BLOCK // slots) == 0
            and not (_interpret(h) and _vma(h)))


class ByToken(NamedTuple):
    """The live rows' slots in token order (:func:`by_token`)."""

    slot: jax.Array      # [m] int32: t * s + a ascending; past live, n * s
    place: jax.Array     # [m] int32: the sorted place of that slot's row
    starts: jax.Array    # [n * s / 1024 + 1] int32: a token tile's first


def by_token(head, live, n_slots: int) -> ByToken:
    """``head`` [m] int32 (the slot ``t * s + a`` at each of the buffer's
    first ``m`` sorted places) and the count ``live`` of places that are
    some held expert's -> the live slots ascending, each with its place,
    in whole SMEM blocks, and where each token tile's (an SMEM block of
    the ``n_slots`` slots) begin among them."""
    place = jnp.arange(head.shape[0], dtype=jnp.int32)
    slot, place = lax.sort((jnp.where(place < live, head, n_slots), place),
                           num_keys=1)
    edges = jnp.arange(0, n_slots + 1, SMEM_BLOCK, dtype=jnp.int32)
    starts = jnp.sum(slot[None, :] < edges[:, None], axis=1, dtype=jnp.int32)
    pad = (0, -head.shape[0] % SMEM_BLOCK)
    return ByToken(jnp.pad(slot, pad, constant_values=n_slots),
                   jnp.pad(place, pad), starts)


def _take(x, rows):
    # In bounds by construction (the indices come from a sort of iota).
    return x.at[rows].get(mode="promise_in_bounds")


def _row_tiles_kernel(live, x, tiles):
    del live                         # the grid's length is all it says here

    def move(as_tiles, rows, lanes):
        tiles[as_tiles, :] = x[rows, lanes].astype(_F32)

    _for_each_register(x.shape[0], x.shape[1] // LANES, move)


def _first_block(starts, i, blocks: int):
    """The SMEM block of the live slots' list that holds token tile
    ``i``'s first."""
    return jnp.minimum(starts[i] // SMEM_BLOCK, blocks - 1)


def _rows_back_kernel(starts, slot_a, slot_b, place_a, place_b, weights, src,
                      out, rows, sums, arrived, *, slots, blocks):
    groups = src.shape[1]
    tokens = out.shape[0]
    i = pl.program_id(0)
    first, end = starts[i], starts[i + 1]
    # A tile has at most a block's slots, so its live ones lie in the two
    # blocks from the one that holds the first.
    base = _first_block(starts, i, blocks) * SMEM_BLOCK

    def listed(in_a, in_b, j):
        at = (j - base) % SMEM_BLOCK
        return jnp.where(j - base < SMEM_BLOCK, in_a[at], in_b[at])

    def tiles_of(row):
        return pl.ds(pl.multiple_of(row * groups, groups), groups)

    def fetch(j):
        return pltpu.make_async_copy(src.at[listed(place_a, place_b, j)],
                                     rows.at[tiles_of(j - first)],
                                     arrived.at[0])

    def start(j, carry):
        fetch(j).start()
        return carry

    lax.fori_loop(first, end, start, None)
    sums[...] = jnp.zeros(sums.shape, _F32)

    def wait(j, carry):
        # Each worth one row; in what order they arrive is not promised,
        # so all are waited for before one is read.
        fetch(first).wait()
        return carry

    lax.fori_loop(first, end, wait, None)

    def add(j, carry):
        # The slot within this tile's block of slots: its weight's place,
        # and its token's.
        slot = listed(slot_a, slot_b, j) - i * SMEM_BLOCK
        token = tiles_of(slot // slots)
        sums[token, :] += weights[slot] * rows[tiles_of(j - first), :]
        return carry

    lax.fori_loop(first, end, add, None)

    def unpack(as_tiles, rows, lanes):
        out[rows, lanes] = sums[as_tiles, :].astype(out.dtype)

    _for_each_register(tokens, groups, unpack)


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT)


# The calls are jitted with what is static among their arguments
# (``models/moe._lowered_once``'s reason): a step holds two of each a
# layer, and each is traced and lowered once a shape.

@functools.partial(jax.jit, static_argnames="interpret")
def _row_tiles_call(x, live, *, interpret: bool):
    m, d = x.shape
    groups, tile = d // LANES, min(LAYOUT_TILE, m)
    tiles = pl.pallas_call(
        _row_tiles_kernel,
        out_shape=jax.ShapeDtypeStruct((m * groups, LANES), _F32,
                                       vma=_vma(x, live)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tile, d), lambda i, live: (i, 0))],
            out_specs=pl.BlockSpec((tile * groups, LANES),
                                   lambda i, live: (i, 0)),
            # The tiles that hold a live row.
            grid=(jnp.minimum(-(-live[0] // tile), m // tile),)),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret, name=scopes.MOE_ROW_TILES,
    )(live, x)
    # The same tiles in the same order: a row's groups are whole tiles.
    return tiles.reshape(m, groups, LANES)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _rows_back_call(src, weights, lists, *, dtype, interpret: bool):
    (n, slots), groups = weights.shape, src.shape[1]
    d, tokens = groups * LANES, SMEM_BLOCK // slots
    blocks = lists.slot.shape[0] // SMEM_BLOCK

    def listed(ahead: int):
        return pl.BlockSpec(
            (SMEM_BLOCK,),
            lambda i, starts: (jnp.minimum(
                _first_block(starts, i, blocks) + ahead, blocks - 1),),
            memory_space=pltpu.SMEM)

    return pl.pallas_call(
        functools.partial(_rows_back_kernel, slots=slots, blocks=blocks),
        out_shape=jax.ShapeDtypeStruct(
            (n, d), dtype, vma=_vma(src, weights, *lists)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                listed(0), listed(1), listed(0), listed(1),
                pl.BlockSpec((SMEM_BLOCK,), lambda i, starts: (i,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tokens, d), lambda i, starts: (i, 0)),
            grid=(n // tokens,),
            scratch_shapes=[
                pltpu.VMEM((SMEM_BLOCK * groups, LANES), _F32),
                pltpu.VMEM((tokens * groups, LANES), _F32),
                pltpu.SemaphoreType.DMA((1,))]),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret, name=scopes.MOE_ROWS_BACK,
    )(lists.starts, lists.slot, lists.slot, lists.place, lists.place,
      weights.reshape(-1), src)


def row_tiles(x, live):
    """``x`` [m, d] bf16 laid out as tiles a row, float32 ``[m, d / 128,
    128]`` (the module's docstring): the rows of the tiles that hold one
    of the first ``live``; the others' are undefined."""
    return _row_tiles_call(x, jnp.reshape(live, (1,)).astype(jnp.int32),
                           interpret=_interpret(x))


def _sum(out, weights, lists, live):
    return _rows_back_call(row_tiles(out, live), weights, lists,
                           dtype=out.dtype, interpret=_interpret(out))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def rows_by_token(slots: int, h, token, lists, live):
    """``h[token]``: ``h`` [n, d] bf16, ``token`` [m] int32 (the token at
    each of the buffer's first ``m`` sorted places) -> [m, d], XLA's
    gather.  What is its own is the gradient: the sum of a token's
    **live** rows (:func:`sum_by_token` with unit weights over its
    ``slots`` slots; ``lists``: :func:`by_token` of the places' slots and
    ``live``), so what ``g`` holds at or past ``live`` is not read.  Sizes
    are ones :func:`takes` accepts.  Differentiable in ``h``."""
    del slots, lists, live
    return _take(h, token)


def _rows_by_token_fwd(slots, h, token, lists, live):
    return _take(h, token), (lists, live)


def _rows_by_token_bwd(slots, residuals, g):
    lists, live = residuals
    tokens = (lists.starts.shape[0] - 1) * SMEM_BLOCK // slots
    return (_sum(g, jnp.ones((tokens, slots), _F32), lists, live), None, None,
            None)


rows_by_token.defvjp(_rows_by_token_fwd, _rows_by_token_bwd)


@jax.custom_vjp
def sum_by_token(out, weights, head, lists, live):
    """``y[t] = sum_a weights[t, a] * out[place of slot (t, a)]`` over
    token ``t``'s live slots: ``out`` [m, d] bf16, the buffer's head (rows
    at or past ``live`` are not read, so they may hold anything),
    ``weights`` [n, s] float32 -> [n, d], summed in float32 and rounded
    once; a token with no live slot reads zeros.  ``lists``:
    :func:`by_token` of ``head`` and ``live``.  Differentiable in ``out``
    (XLA's gather of ``g`` by ``head``'s tokens, scaled by a row's weight;
    zero past ``live``) and in ``weights`` (the float32 dot of a live
    slot's row with its token's ``g``; zero at a slot that is not
    live)."""
    del head
    return _sum(out, weights, lists, live)


def _sum_by_token_fwd(out, weights, head, lists, live):
    return (_sum(out, weights, lists, live), (out, weights, head, live))


def _sum_by_token_bwd(residuals, g):
    out, weights, head, live = residuals
    m, slots = out.shape[0], weights.shape[1]
    rows = _take(g, head // slots).astype(_F32)
    is_live = (jnp.arange(m) < live)[:, None]
    d_out = jnp.where(is_live, rows * _take(weights.reshape(-1), head)[:, None],
                      0).astype(out.dtype)
    dots = jnp.sum(jnp.where(is_live, out.astype(_F32) * rows, 0), axis=-1)
    # The slots at the head's places are distinct.
    d_weights = jnp.zeros(weights.size, _F32).at[head].set(
        dots, unique_indices=True, mode="promise_in_bounds")
    return d_out, d_weights.reshape(weights.shape), None, None, None


sum_by_token.defvjp(_sum_by_token_fwd, _sum_by_token_bwd)


def record_moves(layer, path: str) -> None:
    """Trace-time series beside ``hvd_moe_rows_prefix`` (what was compiled
    into the step): the two row moves of expert layer ``layer``'s share,
    by what runs the sum that each is or has for its gradient (``path``:
    ``kernel`` | ``xla``).  Absent where every expert is held: that
    form's moves are gathers of every slot."""
    if not telemetry.enabled():
        return
    for move in ("out", "back"):
        telemetry.counter(
            "hvd_moe_row_moves_total",
            "Row moves of the traced MoE layer's share that are compiled "
            "into the step, by move (out: the tokens' rows to the sorted "
            "buffer's head, a gather whose gradient is a sum into the "
            "tokens; back: the weighted sum into the tokens) and by what "
            "runs the sum (path: kernel | xla)",
            layer=str(layer), path=path, move=move).inc()
