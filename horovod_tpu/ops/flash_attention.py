"""Pallas flash attention — the TPU kernel for the transformer hot path.

The reference has no attention machinery at all (SURVEY §5.7: Horovod
predates it); this framework makes long-context training first-class, and
the innermost single-device attention is where the FLOPs and the memory
blowup live.  The lax implementation (``parallel/sequence.py
local_attention``) materializes the [B, H, T, T] score matrix in HBM —
O(T^2) memory and two full HBM round trips.  This kernel computes the
same exact attention blockwise in VMEM with online softmax (Dao et al.
2022, FlashAttention), never materializing scores: memory is O(T·D) in
HBM and O(block·D) in VMEM, so sequence length is bounded by HBM, not by
the ~16 MB VMEM.

Layout: ``[B, T, H, D]`` (the repo convention) is folded to
``[B·H, T, D]``; the grid walks (batch·head, query-block, key-block) —
the innermost grid dimension streams one K/V tile at a time through
VMEM (Mosaic double-buffers the fetches), while fp32 accumulators and
the online-softmax m/l state persist across the inner dimension in VMEM
scratch.  Under a mask each kernel specialises a block by what the mask's
static description says of it from the grid indices (``_by_class``; the
causal mask, none, block diffusion's): under the causal mask blocks
above the diagonal are neither computed nor fetched, blocks wholly under
it run without any mask arithmetic, and only the blocks the diagonal
crosses are masked — as 2x2 sub-tiles without the upper-right one where
the blocks are square and at least 256.  The backward pass is
the standard flash recomputation: a per key-block kernel for dK/dV
streaming query tiles, and a per query-block kernel for dQ streaming key
tiles, using the saved row max/denominator.

``interpret=True`` (or ``HOROVOD_FLASH_INTERPRET=1``) runs the kernels
in the Pallas interpreter — exact same code path, CPU-executable — which
is how the CI oracle tests run without a TPU.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import numpy as np
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu import telemetry
from horovod_tpu.telemetry import scopes

NEG_INF = float("-inf")


def _exec_on_tpu(x) -> bool:
    """Executing-mesh platform answer — shared helper, see
    :func:`horovod_tpu.topology.exec_on_tpu` (lives there because the
    collective layer needs the same gate)."""
    from horovod_tpu.topology import exec_on_tpu
    return exec_on_tpu(x)


def _interpret_default(x) -> bool:
    """Interpret-mode default for the kernel: the explicit debug env
    knob wins; otherwise interpret iff the mesh executing ``x`` is not a
    TPU (see :func:`_exec_on_tpu`).  A failure of that query is raised:
    a TPU mesh must never end in the interpreter by accident."""
    if os.environ.get("HOROVOD_FLASH_INTERPRET") == "1":
        return True
    return not _exec_on_tpu(x)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _out_vma(*arrays):
    """vma set for pallas out_shapes: inside a check_vma=True shard_map,
    outputs vary over every axis the inputs vary over (ShapeDtypeStructs
    with vma=None are rejected there); frozenset() outside shard_map."""
    from horovod_tpu.parallel._vma import vma_of
    out = set()
    for a in arrays:
        out |= vma_of(a)
    return frozenset(out)


# ---------------------------------------------------------------------------
# The mask, as one static description
#
# A mask says, from the grid indices alone, which of three classes a (query
# block, key block) pair belongs to, and each class pays only for what its
# position needs:
#   skipped   no query of the block reads any key of it: not computed, not
#             fetched (the index maps point a skipped step at a live block
#             the kernel fetches anyway, so Mosaic elides the DMA);
#   interior  every query reads every key: no iota, compare or select;
#   masked    only some do (called ``diagonal`` in the series, since PR 25):
#             the rectangles ``tiles`` names are computed under the rule
#             each one carries.
# ``flash_attention(..., causal=...)`` takes ``True`` (:data:`CAUSAL`),
# ``False`` (:data:`FULL`) or a description such as :class:`BlockDiffusion`;
# the kernels, the index maps and the series ask the description and branch
# on nothing else.  Segment ids add their compare to every class: a document
# boundary can fall anywhere.
# ---------------------------------------------------------------------------

def _splits_diagonal(block_q: int, block_k: int) -> bool:
    return block_q == block_k and block_q >= 256


def _diagonal_tiles(block_q: int, block_k: int, rule=True):
    """``(row0, rows, col0, cols, rule)`` rectangles a block on a lower
    diagonal computes, in the order the online softmax takes them."""
    if not _splits_diagonal(block_q, block_k):
        return ((0, block_q, 0, block_k, rule),)
    # Upper rows against the left keys, lower rows against all of them:
    # each row is taken once, so the per-row work (running max and sum,
    # the accumulator's rescaling) is that of a whole block.
    h = block_q // 2
    return ((0, h, 0, h, rule), (h, h, 0, block_k, rule))


def _block_class(qi, kj, block_q, block_k):
    """``(interior, diagonal)`` of block (qi, kj) under a causal mask, for
    grid indices in the kernels and plain ints in ``block_classes``:
    interior where every key is at or before every query, diagonal where
    only some are; neither above the diagonal."""
    interior = (kj + 1) * block_k <= qi * block_q + 1
    live = kj * block_k < (qi + 1) * block_q
    return interior, live ^ interior          # interior implies live


def _whole(block_q, block_k):
    return ((0, block_q, 0, block_k, False),)


class Full:
    """No mask: every block interior."""

    def check(self, t, block_q, block_k):
        pass

    def tiled(self, t: int) -> int:
        """The length the kernels' blocks have to divide."""
        return t

    def cases(self, qi, kj, block_q, block_k):
        """``(condition, tiles)`` pairs, at most one of which holds at a
        grid step: ``tiles`` (``(row0, rows, col0, cols, rule)`` each,
        ``rule`` false for an unmasked rectangle) are what block (qi, kj)
        computes where ``condition`` does; where none does it is skipped.
        ``True`` stands for a condition that holds everywhere.  Works on
        grid indices in the kernels and on plain ints in
        :func:`block_classes`."""
        return ((True, _whole(block_q, block_k)),)

    def shown(self, rule, qi, kj, tile, block_q, block_k, shape, q_axis):
        """Bool ``shape``, queries along ``q_axis`` and keys along the
        other: what rectangle ``tile`` of block (qi, kj) shows under
        ``rule``."""
        raise AssertionError("an unmasked block has no rule")

    def may_hide_a_row(self, rule) -> bool:
        """Whether a rectangle under ``rule`` can hide every key of a row
        that has met no key yet (the forward kernel's online softmax then
        guards its exponentials)."""
        return False

    def kv_map(self, block_q, block_k):
        """Index map of a K/V block on the (head, query block, key block)
        grid: a skipped step names a live block, the one last fetched or
        the next to be."""
        return lambda bh_, i, j: (bh_, j, 0)

    def q_map(self, block_q, block_k):
        """The same for a query-side block on the dK+dV kernel's (head,
        key block, query block) grid."""
        return lambda bh_, j, i: (bh_, i, 0)

    def needed(self, t: int) -> int:
        """Score elements a head needs."""
        return t * t

    def visible(self, q_pos, k_pos):
        """The mask itself, dense: whether the query at ``q_pos`` reads the
        key at ``k_pos`` (broadcast), for the ``jax.numpy`` routes and
        the oracles."""
        return jnp.ones(jnp.broadcast_shapes(jnp.shape(q_pos),
                                             jnp.shape(k_pos)), bool)

    def __repr__(self):
        return f"{type(self).__name__}()"


class Causal(Full):
    """Key ``j`` for query ``i`` iff ``j <= i``: one diagonal, the live keys
    of a query block a prefix and the live queries of a key block a
    suffix."""

    def cases(self, qi, kj, block_q, block_k):
        interior, diagonal = _block_class(qi, kj, block_q, block_k)
        return ((interior, _whole(block_q, block_k)),
                (diagonal, _diagonal_tiles(block_q, block_k)))

    def shown(self, rule, qi, kj, tile, block_q, block_k, shape, q_axis):
        # Visible where query position >= key position.  Query index minus
        # key index is a constant of the tile shape; only the offset
        # between the tile's first key and first query depends on the grid
        # step, and not even that where a split block's tiles sit on the
        # diagonal block qi == kj (a constant mask there is worth 0.7% of
        # the kernels' time at T=8192: measured, PERF.md PR 25).
        r0, _, c0, _, _ = tile
        off = (c0 - r0 if _splits_diagonal(block_q, block_k)
               else (kj * block_k + c0) - (qi * block_q + r0))
        ahead = (lax.broadcasted_iota(jnp.int32, shape, q_axis)
                 - lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))
        return ahead >= off

    def kv_map(self, block_q, block_k):
        # Last key block with any unmasked entry for query block i.
        return lambda bh_, i, j: (
            bh_, jnp.minimum(j, ((i + 1) * block_q - 1) // block_k), 0)

    def q_map(self, block_q, block_k):
        # First query block that sees key block j.
        return lambda bh_, j, i: (
            bh_, jnp.maximum(i, (j * block_k) // block_q), 0)

    def needed(self, t: int) -> int:
        return t * (t + 1) // 2

    def visible(self, q_pos, k_pos):
        return k_pos <= q_pos


@dataclasses.dataclass(frozen=True, repr=False)
class BlockDiffusion(Full):
    """Block-diffusion training's mask (Arriola et al., arXiv:2503.09573)
    over ``2 * length`` positions: the clean sequence first, its noised
    copy after it, both in blocks of ``block`` tokens.  With ``beta`` the
    block of a position within its half, query ``i`` reads key ``j`` iff

    ======  ======  =============================================
    i       j
    ======  ======  =============================================
    clean   clean   ``beta(j) <= beta(i)``   (block-causal)
    clean   noised  never
    noised  clean   ``beta(j) < beta(i)``    (finished blocks only)
    noised  noised  ``beta(j) == beta(i)``   (its own block, both ways)
    ======  ======  =============================================

    ``length * (length + block)`` of the ``4 * length ** 2`` score
    elements.  By quadrant of blocks: clean x clean is the causal case
    rounded up to ``block``; clean x noised is skipped; noised x clean is
    the causal case without its block diagonal (rule ``"lt"``); noised x
    noised is one block-diagonal band (rule ``"eq"``).  A noised query
    block's live keys are a prefix of the clean half plus an isolated run
    of the noised half, and a clean key block's live queries are two
    suffixes, one a half: the index maps jump the gaps.  Kernel blocks are
    multiples of ``block`` and divide ``length``, so none straddles the
    halves (docs/kernels.md has the picture)."""

    length: int
    block: int

    def __post_init__(self):
        if self.block < 1 or self.length % self.block:
            raise ValueError(f"BlockDiffusion(length={self.length}, "
                             f"block={self.block}): blocks of `block` "
                             f"tokens must tile `length`")

    def check(self, t, block_q, block_k):
        if t != 2 * self.length:
            raise ValueError(
                f"{self!r} masks 2 * length = {2 * self.length} positions "
                f"(the clean sequence, then its noised copy), got {t}")
        for name, size in (("block_q", block_q), ("block_k", block_k)):
            if size % self.block or self.length % size:
                raise ValueError(
                    f"{name}={size} must be a multiple of {self!r}'s block "
                    f"and divide its length, so that no kernel block "
                    f"straddles a diffusion block or the two halves")

    def tiled(self, t: int) -> int:
        return self.length

    def _splits(self, block_q, block_k):
        return (_splits_diagonal(block_q, block_k)
                and (block_q // 2) % self.block == 0)

    def _corners(self, qi, kj, block_q, block_k):
        """Which half each side lies in, and the block's first and last
        row and column counted within its half."""
        n_q, n_k = self.length // block_q, self.length // block_k
        q_noised, k_noised = qi >= n_q, kj >= n_k
        q0 = qi * block_q - self.length * q_noised
        k0 = kj * block_k - self.length * k_noised
        return (qi < n_q, q_noised, kj < n_k, k_noised,
                q0, q0 + block_q, k0, k0 + block_k)

    def cases(self, qi, kj, block_q, block_k):
        b = self.block
        (q_clean, q_noised, k_clean, k_noised,
         q0, q1, k0, k1) = self._corners(qi, kj, block_q, block_k)
        both_clean = q_clean & k_clean
        finished = q_noised & k_clean
        own = q_noised & k_noised
        # Everything is a multiple of ``block``, so ``beta`` compares as
        # the positions do.
        le_interior = both_clean & (k1 <= q0 + b)
        le = both_clean & (k0 < q1) & (k1 > q0 + b)
        lt_interior = finished & (k1 <= q0)
        lt = finished & (k0 < q1 - b) & (k1 > q0)
        overlap = own & (k0 < q1) & (q0 < k1)
        one_block = block_q == b and block_k == b
        split, h = self._splits(block_q, block_k), block_q // 2

        def tiles(rule):
            return (_diagonal_tiles(block_q, block_k, rule) if split
                    else ((0, block_q, 0, block_k, rule),))

        if one_block:
            return ((le_interior | lt_interior | overlap,
                     _whole(block_q, block_k)),)
        return ((le_interior | lt_interior, _whole(block_q, block_k)),
                (le, tiles("le")), (lt, tiles("lt")),
                # Of a 2x2 cut, the two sub-tiles on the band.
                (overlap, ((0, h, 0, h, "eq"), (h, h, h, h, "eq")) if split
                 else tiles("eq")))

    def shown(self, rule, qi, kj, tile, block_q, block_k, shape, q_axis):
        r0, _, c0, _, _ = tile
        if block_q == block_k:
            # A masked block of a square blocking starts at the same
            # place in its half on both sides, a multiple of ``block``:
            # the rule reads the offsets inside the block alone.
            q_first, k_first = r0, c0
        else:
            _, _, _, _, q0, _, k0, _ = self._corners(qi, kj, block_q,
                                                     block_k)
            q_first, k_first = q0 + r0, k0 + c0

        def beta(first, axis):
            # One row or one column of block numbers: the compare below
            # is the only work a score element pays.
            along = [1, 1]
            along[axis] = shape[axis]
            pos = first + lax.broadcasted_iota(jnp.int32, along, axis)
            log2 = self.block.bit_length() - 1
            return (lax.shift_right_logical(pos, log2)
                    if self.block == 1 << log2 else pos // self.block)

        q_beta, k_beta = beta(q_first, q_axis), beta(k_first, 1 - q_axis)
        return {"le": k_beta <= q_beta, "lt": k_beta < q_beta,
                "eq": k_beta == q_beta}[rule]

    def may_hide_a_row(self, rule) -> bool:
        # A query of the first block of the noised half reads no clean
        # key, and the first block it meets is one of these.
        return rule == "lt"

    def kv_map(self, block_q, block_k):
        b, n_q, n_k = (self.block, self.length // block_q,
                       self.length // block_k)

        def kv_map(bh_, i, j):
            # Clean queries: a prefix of the clean keys.  Noised queries:
            # a prefix of the clean keys (none for the first block), then
            # the noised blocks their own rows overlap.
            i_n = i - n_q
            last_clean = jnp.where(
                i < n_q, ((i + 1) * block_q - 1) // block_k,
                ((i_n + 1) * block_q - b - 1) // block_k)
            own_first = n_k + (i_n * block_q) // block_k
            own_last = n_k + ((i_n + 1) * block_q - 1) // block_k
            return bh_, jnp.where(
                j <= last_clean, j,
                jnp.where(i < n_q, last_clean,
                          jnp.clip(j, own_first, own_last))), 0
        return kv_map

    def q_map(self, block_q, block_k):
        b, n_q, n_k = (self.block, self.length // block_q,
                       self.length // block_k)

        def q_map(bh_, j, i):
            # A clean key block: a suffix of the clean queries, then a
            # suffix of the noised ones.  A noised key block: the noised
            # queries its own rows overlap.
            j_n = j - n_k
            clean_first = (j * block_k) // block_q
            noised_first = n_q + (j * block_k + b) // block_q
            own_first = n_q + (j_n * block_k) // block_q
            own_last = n_q + ((j_n + 1) * block_k - 1) // block_q
            return bh_, jnp.where(
                j < n_k,
                jnp.where(i < n_q, jnp.maximum(i, clean_first),
                          # (none where the last clean block is one
                          # diffusion block: stay on the last clean query)
                          jnp.where(noised_first < 2 * n_q,
                                    jnp.maximum(i, noised_first), n_q - 1)),
                jnp.clip(i, own_first, own_last)), 0
        return q_map

    def needed(self, t: int) -> int:
        return self.length * (self.length + self.block)

    def visible(self, q_pos, k_pos):
        q_noised, k_noised = q_pos >= self.length, k_pos >= self.length
        q_beta = (q_pos % self.length) // self.block
        k_beta = (k_pos % self.length) // self.block
        return jnp.where(
            q_noised,
            jnp.where(k_noised, k_beta == q_beta, k_beta < q_beta),
            ~k_noised & (k_beta <= q_beta))

    def __repr__(self):
        return f"BlockDiffusion(length={self.length}, block={self.block})"


FULL, CAUSAL = Full(), Causal()


def as_mask(causal):
    """The description ``causal`` spells: ``True`` and ``False`` are the
    public names of :data:`CAUSAL` and :data:`FULL`."""
    if isinstance(causal, Full):
        return causal
    return CAUSAL if causal else FULL


def _checked_mask(causal, t, block_q, block_k, segment_ids):
    """:func:`as_mask` of ``causal``, held to the call's sizes."""
    mask = as_mask(causal)
    if segment_ids is not None and isinstance(mask, BlockDiffusion):
        raise NotImplementedError(
            f"segment_ids is not implemented with {mask!r}: packed "
            f"documents under the block-diffusion mask need its blocks "
            f"counted from each document's start")
    mask.check(t, block_q, block_k)
    return mask


def _by_class(mask, qi, kj, block_q, block_k, tile_body):
    """Run ``tile_body(row0, rows, col0, cols, rule)`` over what block
    (qi, kj) needs under ``mask``: nothing where it is skipped, the whole
    block unmasked where it is interior, the masked tiles elsewhere."""
    for condition, tiles in mask.cases(qi, kj, block_q, block_k):
        def run(tiles=tiles):
            for tile in tiles:
                tile_body(*tile)
        if condition is True:
            run()
        else:
            pl.when(condition)(run)


def block_classes(t: int, block_q: int, block_k: int, causal) -> dict:
    """Grid steps of one head by class, and the score elements they
    compute against the elements attention needs — what
    ``hvd_flash_blocks_total`` and ``hvd_flash_computed_over_needed``
    report (shapes are static, so this is counted when a call is traced)."""
    mask = as_mask(causal)
    mask.check(t, block_q, block_k)
    out = {"skipped": 0, "interior": 0, "diagonal": 0, "computed": 0,
           "needed": mask.needed(t)}
    for qi in range(t // block_q):
        for kj in range(t // block_k):
            held = [tiles for condition, tiles
                    in mask.cases(qi, kj, block_q, block_k) if condition]
            if not held:
                out["skipped"] += 1
                continue
            (tiles,) = held
            out["diagonal" if any(tile[4] for tile in tiles)
                else "interior"] += 1
            out["computed"] += sum(rows * cols
                                   for _, rows, _, cols, _ in tiles)
    return out


def _record_blocks(kernel: str, bh: int, t: int, block_q: int,
                   block_k: int, mask) -> None:
    """Trace-time counters of one ``pallas_call`` (like ``hvd_fusion_*``:
    they count what was compiled into the step, not per-step traffic)."""
    if not telemetry.enabled():
        return
    classes = block_classes(t, block_q, block_k, mask)
    for name in ("skipped", "interior", "diagonal"):
        telemetry.counter(
            "hvd_flash_blocks_total",
            "Grid steps of the traced flash kernels by where the block "
            "lies under the mask: skipped, interior (no mask arithmetic) "
            "or diagonal (masked)",
            kernel=kernel, **{"class": name}).inc(bh * classes[name])
    telemetry.gauge(
        "hvd_flash_computed_over_needed",
        "Score elements the most recently traced flash kernel computes "
        "over the elements attention needs (1.0 = no masked work)",
        kernel=kernel).set(classes["computed"] / classes["needed"])


def _dot(a, b, contract):
    """MXU matmul with float32 accumulation.  Operands go in as they are
    stored (bf16 tiles are not upcast: a bf16 x bf16 product is exact in
    float32); a float32 ``p`` or ``ds`` takes the other operand's dtype,
    which is the rounding the MXU applies to float32 operands at default
    precision anyway."""
    return jax.lax.dot_general(a.astype(b.dtype), b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b


def _scores(q, k, scale, qi, kj, tile, block_q, block_k, mask, qseg_ref,
            kseg_ref, keys_by_rows: bool = False):
    """Scores of one tile, masked as its rule says: ``[rows, cols]``
    (queries by keys), or its transpose ``[cols, rows]`` for the dK/dV
    kernel, whose matmuls then contract without transposing a score-sized
    operand.  Segment ids ride a [B, 1, T] layout like the m/l rows;
    tokens attend only within their own segment.  ``kseg_ref`` is the
    q-side ref for self-attention; ring attention passes the ROTATED
    K-side ids."""
    r0, nr, c0, nc, rule = tile
    s = (_dot(k, q, _NT) if keys_by_rows else _dot(q, k, _NT)) * scale
    if rule:
        s = jnp.where(mask.shown(rule, qi, kj, tile, block_q, block_k,
                                 s.shape, 1 if keys_by_rows else 0),
                      s, NEG_INF)
    if qseg_ref is not None:
        qseg = qseg_ref[0, 0, pl.dslice(qi * block_q + r0, nr)]
        kseg = kseg_ref[0, 0, pl.dslice(kj * block_k + c0, nc)]
        same = (kseg[:, None] == qseg[None, :] if keys_by_rows
                else qseg[:, None] == kseg[None, :])
        s = jnp.where(same, s, NEG_INF)
    return s


def _fwd_kernel(q_ref, k_ref, v_ref, *rest,
                block_q: int, block_k: int, num_k: int, mask,
                scale: float, segments: bool):
    if segments:
        (qseg_ref, kseg_ref, o_ref, m_ref, l_ref, acc_ref, m_scr,
         l_scr) = rest
    else:
        o_ref, m_ref, l_ref, acc_ref, m_scr, l_scr = rest
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    # The running max and sum live as [block_q, 1] columns, the layout a
    # row reduction produces and a broadcast over scores consumes; the
    # lane-major m/l rows are written once per query tile, at the end.
    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile_body(*tile):
        r0, nr, c0, nc, rule = tile
        q = q_ref[0, r0:r0 + nr, :]                      # [nr, D]
        k = k_ref[0, c0:c0 + nc, :]                      # [nc, D]
        v = v_ref[0, c0:c0 + nc, :]
        m = m_scr[r0:r0 + nr, :]                         # [nr, 1]
        s = _scores(q, k, scale, qi, kj, tile, block_q, block_k, mask,
                    qseg_ref, kseg_ref)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        if segments or (rule and mask.may_hide_a_row(rule)):
            # A row may have met no key of its segment, or none the mask
            # shows it, yet (m_new = -inf).
            safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
            p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - safe_m))
            corr = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - safe_m))
        else:
            # Every row has seen a key after its first block (key 0 under
            # the causal mask), so m_new is finite and exp(-inf - m_new)
            # is the zero a select would give (also for m = -inf on the
            # first block).
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
        m_scr[r0:r0 + nr, :] = m_new
        l_scr[r0:r0 + nr, :] = (l_scr[r0:r0 + nr, :] * corr
                                + jnp.sum(p, axis=-1, keepdims=True))
        acc_ref[r0:r0 + nr, :] = (acc_ref[r0:r0 + nr, :] * corr
                                  + _dot(p, v, _NN))

    _by_class(mask, qi, kj, block_q, block_k, tile_body)

    @pl.when(kj == num_k - 1)
    def _finalize():
        rows = pl.dslice(qi * block_q, block_q)
        l = l_scr[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)
        m_ref[0, 0, rows] = m_scr[...][:, 0]
        l_ref[0, 0, rows] = l[:, 0]


# ---------------------------------------------------------------------------
# Backward — standard flash recomputation
#   D_i  = rowsum(dO ⊙ O)
#   P    = exp(QKᵀ·scale − lse),  lse = m + log l   (recomputed per tile)
#   dV  += Pᵀ dO
#   dP   = dO Vᵀ
#   dS   = P ⊙ (dP − D_i)
#   dQ  += dS K · scale ;  dK += dSᵀ Q · scale   (scale once, at the end)
# ---------------------------------------------------------------------------

def _row_lse(m, l, segments: bool):
    """log of the softmax denominator per row, from the saved max and
    sum: one subtract per score in place of a subtract and a divide."""
    if segments:
        # A fully masked row saved m = -inf, l = 0; its p is zeroed below.
        m = jnp.where(m == NEG_INF, 0.0, m)
        l = jnp.where(l == 0.0, 1.0, l)
    return m + jnp.log(l)


def _probs(s, lse, segments: bool):
    p = jnp.exp(s - lse)
    return jnp.where(s == NEG_INF, 0.0, p) if segments else p


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, m_ref, l_ref,
                   *rest, block_q: int, block_k: int,
                   num_k: int, mask, scale: float,
                   segments: bool):
    if segments:
        qseg_ref, kseg_ref, dq_ref, acc_ref, lse_ref, di_ref = rest
    else:
        dq_ref, acc_ref, lse_ref, di_ref = rest
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    rows = pl.dslice(qi * block_q, block_q)

    @pl.when(kj == 0)
    def _init():
        # Once per query tile, not per key block: the row statistics.
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lse_ref[...] = _row_lse(m_ref[0, 0, rows], l_ref[0, 0, rows],
                                segments)[:, None]
        di_ref[...] = jnp.sum(
            do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
            axis=-1, keepdims=True)                      # [bq, 1]

    def tile_body(*tile):
        r0, nr, c0, nc, _ = tile
        q = q_ref[0, r0:r0 + nr, :]
        do = do_ref[0, r0:r0 + nr, :]
        k = k_ref[0, c0:c0 + nc, :]                      # [nc, D]
        v = v_ref[0, c0:c0 + nc, :]
        s = _scores(q, k, scale, qi, kj, tile, block_q, block_k, mask,
                    qseg_ref, kseg_ref)
        p = _probs(s, lse_ref[r0:r0 + nr, :], segments)
        dp = _dot(do, v, _NT)                            # [nr, nc]
        ds = p * (dp - di_ref[r0:r0 + nr, :])
        acc_ref[r0:r0 + nr, :] += _dot(ds, k, _NN)

    _by_class(mask, qi, kj, block_q, block_k, tile_body)

    @pl.when(kj == num_k - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, m_ref, l_ref,
                    *rest, block_q: int, block_k: int, num_q: int,
                    mask, scale: float, segments: bool):
    if segments:
        (qseg_ref, kseg_ref, dk_ref, dv_ref, dk_acc_ref,
         dv_acc_ref) = rest
    else:
        dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = rest
        qseg_ref = kseg_ref = None
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def tile_body(*tile):
        # Keys by rows: p and ds come out as [nc, nr], the shape dV and dK
        # contract over without a transpose, and the row statistics are
        # used lane-major, as the m/l rows store them.
        r0, nr, c0, nc, _ = tile
        trows = pl.dslice(qi * block_q + r0, nr)
        k = k_ref[0, c0:c0 + nc, :]                      # [nc, D]
        v = v_ref[0, c0:c0 + nc, :]
        q = q_ref[0, r0:r0 + nr, :]                      # [nr, D]
        do = do_ref[0, r0:r0 + nr, :]
        lse = _row_lse(m_ref[0, 0, trows], l_ref[0, 0, trows], segments)
        di = jnp.sum(do.astype(jnp.float32)
                     * o_ref[0, r0:r0 + nr, :].astype(jnp.float32),
                     axis=-1)                            # [nr]
        s = _scores(q, k, scale, qi, ki, tile, block_q, block_k, mask,
                    qseg_ref, kseg_ref, keys_by_rows=True)
        p = _probs(s, lse[None, :], segments)            # [nc, nr]
        dv_acc_ref[c0:c0 + nc, :] += _dot(p, do, _NN)    # [nc, D]
        dp = _dot(v, do, _NT)                            # [nc, nr]
        ds = p * (dp - di[None, :])
        dk_acc_ref[c0:c0 + nc, :] += _dot(ds, q, _NN)

    # Query blocks strictly left of this key block see none of it.
    _by_class(mask, qi, ki, block_q, block_k, tile_body)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

def _check_shapes(q, k, v, block_q, block_k):
    if q.shape != k.shape or q.shape != v.shape:
        raise ValueError(f"q/k/v shapes must match, got {q.shape} "
                         f"{k.shape} {v.shape}")
    b, t, h, d = q.shape
    if t % block_q != 0 or t % block_k != 0:
        raise ValueError(
            f"sequence length {t} must be divisible by block_q={block_q} "
            f"and block_k={block_k} (pad the sequence)")
    return b, t, h, d


def _fold(x):
    # [B, T, H, D] -> [B*H, T, D]
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unfold(x, b, h):
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _seg_spec(t, h):
    # Segment ids ride a [B, 1, T] layout (same tiling story as m/l);
    # the index map folds the batch*head grid dim back to batch.
    return pl.BlockSpec((1, 1, t), lambda bh_, i, j: (bh_ // h, 0, 0))


# One ``pallas_call`` object per static configuration: every layer of a model
# then calls the same jitted object and the kernel is traced once per step
# program, not once per layer (the kernels are most of what lowering a
# step costs).

@functools.cache
def _fwd_call(bh, t, d, dtype, h, mask, scale, block_q, block_k,
              interpret, segments, vma):
    num_k = t // block_k
    kernel = functools.partial(_fwd_kernel, block_q=block_q,
                               block_k=block_k, num_k=num_k, mask=mask,
                               scale=scale, segments=segments)
    # A skipped step names the K/V block of a live step beside it (under
    # the causal mask the last live one, the preceding step's), so Mosaic
    # elides the DMA instead of fetching a tile whose work pl.when skips.
    kv_map = mask.kv_map(block_q, block_k)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh_, i, j: (bh_, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_k, d), kv_map),
    ]
    if segments:
        in_specs += [_seg_spec(t, h), _seg_spec(t, h)]
    return pl.pallas_call(
        kernel,
        grid=(bh, t // block_q, num_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, i, j: (bh_, i, 0)),
            # TPU tiling: the last two block dims must be (8k, 128k) or
            # equal the array dims — a [bh, 1, T] layout with full
            # (1, 1, T) blocks satisfies that for any block_q.  The block
            # is revisited by every query tile of a head, so it stays
            # resident in VMEM until the head is done.
            pl.BlockSpec((1, 1, t), lambda bh_, i, j: (bh_, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda bh_, i, j: (bh_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32, vma=vma),
        ],
        # Output accumulator, and the running max and sum as columns.
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        interpret=interpret,
        name=scopes.FLASH_FWD,
    )


def _fwd_parts(qf, kf, vf, qsegf, ksegf, h, causal, scale, block_q,
               block_k, interpret):
    """Folded-layout forward: (of, m, l) with m/l the [bh, 1, T] online
    softmax state — the raw pieces ring attention merges across steps.
    ``qsegf``/``ksegf`` are [B, 1, T] (pass the same array for
    self-attention)."""
    bh, t, d = qf.shape
    mask = _checked_mask(causal, t, block_q, block_k, qsegf)
    operands = [qf, kf, vf]
    if qsegf is not None:
        operands += [qsegf, ksegf]
    _record_blocks(scopes.FLASH_FWD, bh, t, block_q, block_k, mask)
    return _fwd_call(bh, t, d, qf.dtype, h, mask, scale, block_q, block_k,
                     interpret, qsegf is not None,
                     _out_vma(*operands))(*operands)


def _fwd(q, k, v, seg, causal, scale, block_q, block_k, interpret):
    b, t, h, d = _check_shapes(q, k, v, block_q, block_k)
    if seg is not None:
        if seg.shape != (b, t):
            raise ValueError(
                f"segment_ids must be [B, T] = {(b, t)} matching q/k/v, "
                f"got {seg.shape} (pad segment ids with the sequence)")
        if not jnp.issubdtype(seg.dtype, jnp.integer):
            raise ValueError(
                f"segment_ids must be integer, got {seg.dtype}")
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    segf = seg.reshape(b, 1, t) if seg is not None else None
    o, m, l = _fwd_parts(qf, kf, vf, segf, segf, h, causal, scale,
                         block_q, block_k, interpret)
    return _unfold(o, b, h), (qf, kf, vf, o, m, l, seg, b, h)


@functools.cache
def _bwd_dq_call(bh, t, d, dtype, h, mask, scale, block_q, block_k,
                 interpret, segments, vma):
    num_k = t // block_k
    kernel = functools.partial(_bwd_dq_kernel, block_q=block_q,
                               block_k=block_k, num_k=num_k, mask=mask,
                               scale=scale, segments=segments)
    kv_map = mask.kv_map(block_q, block_k)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh_, i, j: (bh_, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_q, d), lambda bh_, i, j: (bh_, i, 0)),
        pl.BlockSpec((1, block_q, d), lambda bh_, i, j: (bh_, i, 0)),
        pl.BlockSpec((1, 1, t), lambda bh_, i, j: (bh_, 0, 0)),
        pl.BlockSpec((1, 1, t), lambda bh_, i, j: (bh_, 0, 0)),
    ]
    if segments:
        in_specs += [_seg_spec(t, h), _seg_spec(t, h)]
    return pl.pallas_call(
        kernel,
        grid=(bh, t // block_q, num_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh_, i, j: (bh_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), dtype, vma=vma),
        # dQ accumulator, and the query tile's lse and D_i as columns.
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        interpret=interpret,
        name=scopes.FLASH_BWD_DQ,
    )


@functools.cache
def _bwd_dkv_call(bh, t, d, dtype, h, mask, scale, block_q, block_k,
                  interpret, segments, vma):
    num_q = t // block_q
    kernel = functools.partial(_bwd_dkv_kernel, block_q=block_q,
                               block_k=block_k, num_q=num_q, mask=mask,
                               scale=scale, segments=segments)
    q_map = mask.q_map(block_q, block_k)
    in_specs = [
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, block_k, d), lambda bh_, j, i: (bh_, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh_, j, i: (bh_, j, 0)),
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, 1, t), lambda bh_, j, i: (bh_, 0, 0)),
        pl.BlockSpec((1, 1, t), lambda bh_, j, i: (bh_, 0, 0)),
    ]
    if segments:
        in_specs += [_seg_spec(t, h), _seg_spec(t, h)]
    return pl.pallas_call(
        kernel,
        grid=(bh, t // block_k, num_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh_, j, i: (bh_, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, j, i: (bh_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, t, d), dtype, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name=scopes.FLASH_BWD_DKV,
    )


def _bwd_parts(qf, kf, vf, of, dof, m, l, qsegf, ksegf, h, causal, scale,
               block_q, block_k, interpret):
    """Folded-layout backward: (dqf, dkf, dvf) from the GLOBAL (m, l)
    rows.  Ring attention calls this per rotating block with the final
    accumulated m/l — the per-block contributions are then the exact
    global-softmax gradients (p recomputed as exp(s − m)/l)."""
    bh, t, d = qf.shape
    mask = _checked_mask(causal, t, block_q, block_k, qsegf)
    operands = [qf, kf, vf, of, dof, m, l]
    if qsegf is not None:
        operands += [qsegf, ksegf]
    config = (bh, t, d, qf.dtype, h, mask, scale, block_q, block_k,
              interpret, qsegf is not None, _out_vma(*operands))
    _record_blocks(scopes.FLASH_BWD_DQ, bh, t, block_q, block_k, mask)
    dq = _bwd_dq_call(*config)(*operands)
    _record_blocks(scopes.FLASH_BWD_DKV, bh, t, block_q, block_k, mask)
    dk, dv = _bwd_dkv_call(*config)(*operands)
    return dq, dk, dv


def _bwd(causal, scale, block_q, block_k, interpret, res, do):
    qf, kf, vf, of, m, l, seg, b, h = res
    bh, t, d = qf.shape
    dof = _fold(do)
    segf = seg.reshape(b, 1, t) if seg is not None else None
    dq, dk, dv = _bwd_parts(qf, kf, vf, of, dof, m, l, segf, segf, h,
                            causal, scale, block_q, block_k, interpret)
    dseg = (np.zeros(seg.shape, jax.dtypes.float0)
            if seg is not None else None)
    return (_unfold(dq, b, h), _unfold(dk, b, h), _unfold(dv, b, h),
            dseg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None, segment_ids=None):
    """Exact attention, flash-style, as a Pallas TPU kernel.

    q/k/v: ``[B, T, H, D]``; returns ``[B, T, H, D]``.  ``T`` must be a
    multiple of the block sizes (pad the sequence).  Numerically matches
    ``parallel/sequence.local_attention`` (the lax oracle) to fp32
    accumulation tolerance, forward and backward.

    ``block_q``/``block_k`` default to AUTO: the largest power of two
    ≤ 1024 dividing ``T`` (≤ 512 when ``D > 256``, which no sweep has
    covered, and when ``D > 128`` with ``segment_ids``, where the dK+dV
    kernel at 1024² is refused for VMEM).  Swept on a v5e (docs/kernels.md): at ``D = 128`` (PR 25)
    1024 blocks run the three kernels 1.40× faster than 512 blocks at
    T=8192 and 1.32× at T=2048, and faster than every mixed shape tried
    (bigger tiles amortize the grid/DMA overhead and the per-row work;
    1024×1024 f32 scores ≈ 4 MB of the ~16 MB VMEM; 2048 on either side
    is refused for VMEM); at ``D = 256`` (PR 37, B·H=20, T=8192) all nine
    shapes of {256, 512, 1024}² compile inside the default scoped VMEM
    and 1024² is again the fastest, 1.13× over 512².

    ``causal``: ``True``, ``False`` or a description of another mask
    (:class:`BlockDiffusion`), static like the block sizes; the kernels
    skip, run unmasked or mask each block as the description says.

    ``segment_ids`` ([B, T] int32) enables sequence packing: tokens
    attend only within their own segment (composes with ``causal`` as a
    bool) —
    the block-sparse masking XLA's fused attention cannot express, and
    the reason the kernel scaffold exists (docs/kernels.md).
    """
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                        interpret, segment_ids)
    return out


def _auto_block(t: int, head_dim: Optional[int] = None,
                segments: bool = False) -> int:
    if t < 128:
        # Short sequences (interpret mode / tests): old clamp behavior.
        for b in (64, 32, 16, 8):
            if t % b == 0:
                return b
        raise ValueError(
            f"sequence length {t} must be divisible by 8 for the flash "
            f"kernel (pad the sequence)")
    # Floor at 128: tinier auto blocks (e.g. 8 for T=1992) would explode
    # the grid and run orders of magnitude slower than the error is
    # annoying — same contract as the old fixed-128 default.
    # 1024 preferred over 512: measured 1.32x at T=2048 (B4 H32 D128)
    # and 1.40x at T=8192 (B1 H32) over the three kernels
    # (docs/kernels.md, PR 25); 1024x1024 f32 scores = 4 MB of VMEM.  At
    # head_dim 256 the operand tiles and the accumulators double (the
    # dK+dV kernel's: 5 MB of tiles double-buffered and 2 MB of
    # scratch, beside the scores' 4 MB) and every shape of {256, 512,
    # 1024}^2 still compiles in the default scoped VMEM; swept on the chip
    # at B*H=20, T=8192, 1024^2 is the fastest there too: 19.90 ms for
    # the three kernels against 22.55 at 512^2 (docs/kernels.md, PR 37).
    # With segment ids the dK+dV kernel at head_dim 256 and 1024^2 is
    # refused for VMEM (tests/test_flash_compile.py), and wider heads than
    # 256 have not been swept: 512 at most in both cases (explicit
    # block_q/block_k still override).
    wide = (head_dim or 0) > 128
    capped = (head_dim or 0) > 256 or (wide and segments)
    prefs = (512, 256, 128) if capped else (1024, 512, 256, 128)
    for b in prefs:
        if t % b == 0:
            return b
    raise ValueError(
        f"sequence length {t} must be divisible by 128 for auto block "
        f"sizing (pad the sequence, or pass explicit block_q/block_k)")


def _eff_blocks(t, block_q, block_k, head_dim=None, segments=False):
    # None = auto (largest power of two <= 1024 dividing T — capped at
    # 512 when head_dim > 256, or > 128 with segment ids, see _auto_block
    # — measured fastest); explicit blocks are clamped to T so e.g. T=64
    # works with block 128 (divisibility still enforced after clamping).
    def pick(block):
        return (_auto_block(t, head_dim, segments) if block is None
                else min(block, t))

    return pick(block_q), pick(block_k)


@jax.named_scope(scopes.ATTN_FLASH)
def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               segment_ids=None):
    d = q.shape[-1]
    scale_ = (d ** -0.5) if scale is None else scale
    interp = _interpret_default(q) if interpret is None else interpret
    bq, bk = _eff_blocks(as_mask(causal).tiled(q.shape[1]), block_q,
                         block_k, d, segment_ids is not None)
    return _fwd(q, k, v, segment_ids, causal, scale_, bq, bk, interp)


@jax.named_scope(scopes.ATTN_FLASH)
def _flash_bwd(causal, scale, block_q, block_k, interpret, res, do):
    t, d = res[0].shape[1], res[0].shape[-1]
    scale_ = (d ** -0.5) if scale is None else scale
    interp = _interpret_default(res[0]) if interpret is None else interpret
    bq, bk = _eff_blocks(as_mask(causal).tiled(t), block_q, block_k, d,
                         res[6] is not None)
    return _bwd(causal, scale_, bq, bk, interp, res, do)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# The same kernels for operands that are born in their layout
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_folded(q, k, v, heads: int, causal=True,
                           scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           segment_ids=None):
    """:func:`flash_attention` on q/k/v ``[B * heads, T, D]``, the layout
    the kernels work in (head ``h`` of batch row ``b`` at ``b * heads +
    h``); returns ``[B * heads, T, D]``.  The same three kernels under the
    same names and the same scope, without the moves from and to ``[B, T,
    H, D]`` around them: for a caller whose heads are made head-major
    (:mod:`horovod_tpu.ops.mla_assemble`).  ``segment_ids`` [B, T]."""
    out, _ = _folded_fwd(q, k, v, heads, causal, scale, block_q, block_k,
                         interpret, segment_ids)
    return out


def _folded_config(q, causal, scale, block_q, block_k, interpret, segments):
    _, t, d = q.shape
    return ((d ** -0.5) if scale is None else scale,
            *_eff_blocks(as_mask(causal).tiled(t), block_q, block_k, d,
                         segments),
            _interpret_default(q) if interpret is None else interpret)


def _folded_segments(seg, t):
    return seg.reshape(seg.shape[0], 1, t) if seg is not None else None


@jax.named_scope(scopes.ATTN_FLASH)
def _folded_fwd(q, k, v, heads, causal, scale, block_q, block_k, interpret,
                segment_ids=None):
    if q.shape != k.shape or q.shape != v.shape or q.shape[0] % heads:
        raise ValueError(f"q/k/v [B * {heads}, T, D] must match, got "
                         f"{q.shape} {k.shape} {v.shape}")
    scale_, bq, bk, interp = _folded_config(q, causal, scale, block_q,
                                            block_k, interpret,
                                            segment_ids is not None)
    t = q.shape[1]
    if t % bq or t % bk:
        raise ValueError(
            f"sequence length {t} must be divisible by block_q={bq} "
            f"and block_k={bk} (pad the sequence)")
    segf = _folded_segments(segment_ids, t)
    o, m, l = _fwd_parts(q, k, v, segf, segf, heads, causal, scale_, bq, bk,
                         interp)
    return o, (q, k, v, o, m, l, segment_ids)


@jax.named_scope(scopes.ATTN_FLASH)
def _folded_bwd(heads, causal, scale, block_q, block_k, interpret, res, do):
    q, k, v, o, m, l, seg = res
    scale_, bq, bk, interp = _folded_config(q, causal, scale, block_q,
                                            block_k, interpret,
                                            seg is not None)
    segf = _folded_segments(seg, q.shape[1])
    dq, dk, dv = _bwd_parts(q, k, v, o, do, m, l, segf, segf, heads, causal,
                            scale_, bq, bk, interp)
    dseg = (np.zeros(seg.shape, jax.dtypes.float0)
            if seg is not None else None)
    return dq, dk, dv, dseg


flash_attention_folded.defvjp(_folded_fwd, _folded_bwd)
