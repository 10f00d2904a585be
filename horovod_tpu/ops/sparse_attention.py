"""Learned sparse attention — an indexer chooses each query's keys.

DeepSeek-V3.2's sparse attention: a small *indexer* scores every earlier
key of a query, ``I[t, s] = c * sum_j w[t, j] * relu(qI[t, j] . kI[s])``
over its own few heads ``j`` and ONE key head; the ``topk`` best keys a
query (every key while ``t < topk``; ties to the lower index) are the set
``S_t`` all attention heads of that query read, the softmax runs over
``S_t`` alone, and the indexer learns from its own loss, ``KL(mean over
heads of the attention's probabilities on S_t || softmax over S_t of
I[t, .])``, which reaches nothing but the indexer.

The selection is **data**: no grid index says which keys a query keeps, so
it travels as a mask, one ``int8`` a (query, key) pair, shared by every
head.  Seven Pallas kernels, each with a ``jax.numpy`` form beside it that
the tests hold it to and that a CPU traces in its place (:func:`path`):

``dsa_index_fwd`` / ``dsa_index_bwd``
    the indexer's scores, float32, a [block_q, block_k] tile at a time
    over its heads, and their gradient (the keys' as one partial sum a
    query block, added up outside);
``dsa_select``
    a query block's scores in VMEM, the ``topk``-th largest of each row
    found **exactly** by bisection on the float32 bit pattern (32 counting
    passes, no sort), ties admitted from the lowest index up by a second
    bisection on the index; writes the mask and, by one more pass, the
    log-sum-exp of each row's scores over its set (the normaliser of the
    indexer's softmax);
``dsa_fwd`` / ``dsa_bwd_dq`` / ``dsa_bwd_dkv``
    flash attention under the mask.  A grid step holds one key-value head's
    K/V tile and the ``group`` query heads that read it (grouped-query
    attention without a repeated copy of K and V in HBM), so K, V and the
    mask tile are fetched once for the group.  Tiles above the diagonal are
    skipped as in :mod:`flash_attention`; query blocks whose rows all lie
    under ``topk`` select every earlier key, and their tiles under the
    diagonal run without the mask (*interior*), those on it with it
    (*diagonal*); every other live tile is *masked*;
``dsa_probs``
    the indexer's loss a query: the head-mean of the attention's
    probabilities per (query, key), from the forward's saved log-sum-exp,
    a tile at a time in VMEM, and its cross-entropy against the scores
    summed along the row.

The head-mean probabilities exist a [block_q, block_k] tile at a time, in
the forward pass for the loss (``dsa_probs``) and in the backward pass for
its gradient (``dsa_bwd_dq``, which has every head's probabilities of a
tile in hand and writes the scores' cotangent beside dQ).

Memory: the scores and their gradient are [B, T, T] float32 in HBM, the
mask and its transpose [B, T, T] int8; nothing is [heads, T, T] and the
probabilities are nowhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu import telemetry
from horovod_tpu.ops.flash_attention import (
    NEG_INF, _NN, _NT, _block_class, _dot, _exec_on_tpu, _interpret_default,
    _out_vma)
from horovod_tpu.telemetry import scopes

INT_MIN = -2 ** 31
# The select kernel holds a [block_q, T] block of scores twice (Pallas
# double-buffers the input) and its keys once: 24 MiB at T = 16384.
VMEM_LIMIT = 96 * 1024 * 1024
SELECT_BLOCK_Q = 128
# Columns the select kernel's counting passes take at a time.
SELECT_CHUNK = 2048


def path(x) -> str:
    """``"kernel"`` where the mesh executing ``x`` is a TPU, ``"jnp"``
    elsewhere: the interpreter would take minutes a step on a CPU, and the
    ``jax.numpy`` forms are the same functions."""
    return "kernel" if _exec_on_tpu(x) else "jnp"


def keys_selected(t: int, topk: int) -> int:
    """Keys the queries of one sequence of ``t`` tokens select in all:
    ``sum_t min(t + 1, topk)``."""
    full = min(t, topk)
    return full * (full + 1) // 2 + (t - full) * topk


# ---------------------------------------------------------------------------
# jax.numpy forms: the oracle of every kernel, and what a CPU traces
# ---------------------------------------------------------------------------

def index_scores_jnp(qi, ki, w, scale: float):
    """qi [B, T, HI, DI], ki [B, T, DI], w [B, T, HI] -> [B, T, T] f32."""
    s = jnp.einsum("bthd,bsd->bhts", qi, ki,
                   preferred_element_type=jnp.float32)
    acc = jnp.einsum("bhts,bth->bts", jnp.maximum(s, 0.0),
                     w.astype(jnp.float32)) * scale
    return jnp.where(acc == 0.0, 0.0, acc)       # no -0.0: one key a value


def select_jnp(scores, topk: int):
    """[B, T, T] bool: the ``topk`` largest causal scores a row by
    ``lax.top_k`` (equal scores: the lower index first), every causal key
    of a row that has no more than ``topk``."""
    t = scores.shape[-1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    if topk >= t:
        return jnp.broadcast_to(causal, scores.shape)
    _, chosen = lax.top_k(jnp.where(causal, scores, NEG_INF), topk)
    hit = jnp.zeros(scores.shape, bool)
    hit = jnp.put_along_axis(hit, chosen, True, axis=-1, inplace=False)
    return hit & causal


def attention_jnp(q, k, v, mask, scale: float):
    """q [B, T, H, D], k/v [B, T, Hkv, D], mask [B, T, T] (nonzero: read)
    -> ``(o [B, T, H, D], lse [B, Hkv, G, T], head-mean probabilities [B,
    T, T] f32)``; head ``h`` reads key-value head ``h // G``."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, t, hkv, h // hkv, d)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where((mask != 0)[:, None, None], s, NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return (o.reshape(b, t, h, d).astype(q.dtype), lse,
            jnp.mean(p, axis=(1, 2)))


def indexer_kl(scores, mask, p):
    """[B, T]: ``KL(p[t, .] || softmax over S_t of scores[t, .])``, ``S_t``
    the nonzero entries of ``mask`` and ``p`` a distribution on it."""
    sel = mask != 0
    log_q = scores - jax.nn.logsumexp(jnp.where(sel, scores, NEG_INF),
                                      axis=-1, keepdims=True)
    live = sel & (p > 0.0)
    return jnp.sum(jnp.where(
        live, p * (jnp.log(jnp.where(live, p, 1.0)) - log_q), 0.0), axis=-1)


# ---------------------------------------------------------------------------
# Trace-time counters
# ---------------------------------------------------------------------------

def tile_classes(t: int, block_q: int, block_k: int, topk: int) -> dict:
    """Grid steps of one (batch, key-value head) of a masked kernel by
    class."""
    out = {"skipped": 0, "interior": 0, "diagonal": 0, "masked": 0}
    for qi in range(t // block_q):
        dense = (qi + 1) * block_q <= topk
        for kj in range(t // block_k):
            interior, diagonal = _block_class(qi, kj, block_q, block_k)
            if not (interior or diagonal):
                out["skipped"] += 1
            elif not dense:
                out["masked"] += 1
            else:
                out["interior" if interior else "diagonal"] += 1
    return out


def _record_tiles(kernel: str, bh: int, t: int, block_q: int, block_k: int,
                  topk: int) -> None:
    if not telemetry.enabled():
        return
    for name, n in tile_classes(t, block_q, block_k, topk).items():
        telemetry.counter(
            "hvd_dsa_tiles_total",
            "Grid steps of the traced sparse-attention kernels by class: "
            "skipped (above the diagonal), interior and diagonal (query "
            "rows that select every earlier key), masked (the selection "
            "crosses the tile)",
            kernel=kernel, **{"class": name}).inc(bh * n)


def _record_pass(kernel: str) -> None:
    if telemetry.enabled():
        telemetry.counter(
            "hvd_dsa_probability_passes_total",
            "Traced kernel calls that compute the head-mean of the "
            "attention's probabilities, a tile at a time in VMEM (a call "
            "that dead-code elimination drops afterwards, as "
            "jax.checkpoint's recomputation drops dsa_probs, was traced "
            "and is counted)", kernel=kernel).inc()


def record_path(traced_path: str) -> None:
    """Per traced layer: the path traced.  How many keys the selection
    keeps is data and no trace-time series: the benchmark's adapter counts
    the mask on the chip (``perfbench/adapters/dsa_moe_lm.py``)."""
    if not telemetry.enabled():
        return
    telemetry.counter(
        "hvd_dsa_layers_total",
        "Traced sparse attention layers by path: the Pallas kernels, or "
        "their jax.numpy forms off the chip", path=traced_path).inc()


# ---------------------------------------------------------------------------
# The indexer's scores
# ---------------------------------------------------------------------------

def _live(qi, kj, block_q, block_k):
    """Whether tile (qi, kj) holds any key at or before any query."""
    return kj * block_k < (qi + 1) * block_q


def _index_fwd_kernel(qi_ref, ki_ref, w_ref, out_ref, *, heads, scale,
                      block_q, block_k):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(_live(i, j, block_q, block_k))
    def _compute():
        k = ki_ref[0]                                    # [bk, DI]
        w = w_ref[0]                                     # [bq, HI] f32
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for h in range(heads):
            s = _dot(qi_ref[0, h], k, _NT)               # [bq, bk]
            acc = acc + w[:, h:h + 1] * jnp.maximum(s, 0.0)
        acc = acc * scale
        out_ref[0] = jnp.where(acc == 0.0, 0.0, acc)

    @pl.when(jnp.logical_not(_live(i, j, block_q, block_k)))
    def _above():
        out_ref[0] = jnp.zeros((block_q, block_k), jnp.float32)


def _index_bwd_kernel(qi_ref, ki_ref, w_ref, g_ref, dqi_ref, dw_ref,
                      dkp_ref, dq_acc, dw_acc, *, heads, scale, block_q,
                      block_k, num_k):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when(_live(i, j, block_q, block_k))
    def _compute():
        k = ki_ref[0]
        w = w_ref[0]
        g = g_ref[0] * scale                             # [bq, bk]
        lane = lax.broadcasted_iota(jnp.int32, (block_q, heads), 1)
        dw = jnp.zeros((block_q, heads), jnp.float32)
        dk = jnp.zeros(k.shape, jnp.float32)
        for h in range(heads):
            qh = qi_ref[0, h]                            # [bq, DI]
            s = _dot(qh, k, _NT)
            dw = dw + jnp.where(
                lane == h,
                jnp.sum(g * jnp.maximum(s, 0.0), axis=-1, keepdims=True),
                0.0)
            ds = jnp.where(s > 0.0, g * w[:, h:h + 1], 0.0)
            dq_acc[h] += _dot(ds, k, _NN)
            dk = dk + _dot(ds.T, qh, _NN)                # [bk, DI]
        dw_acc[...] += dw
        dkp_ref[0, 0] = dk

    @pl.when(jnp.logical_not(_live(i, j, block_q, block_k)))
    def _above():
        dkp_ref[0, 0] = jnp.zeros(dkp_ref.shape[2:], jnp.float32)

    @pl.when(j == num_k - 1)
    def _finalize():
        dqi_ref[0] = dq_acc[...].astype(dqi_ref.dtype)
        dw_ref[0] = dw_acc[...]


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


@functools.cache
def _index_fwd_call(b, t, heads, di, scale, block_q, block_k, interpret,
                    vma):
    kernel = functools.partial(_index_fwd_kernel, heads=heads, scale=scale,
                               block_q=block_q, block_k=block_k)
    return pl.pallas_call(
        kernel, grid=(b, t // block_q, t // block_k),
        in_specs=[
            pl.BlockSpec((1, heads, block_q, di),
                         lambda b_, i, j: (b_, 0, i, 0)),
            pl.BlockSpec((1, block_k, di), lambda b_, i, j: (b_, j, 0)),
            pl.BlockSpec((1, block_q, heads), lambda b_, i, j: (b_, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, block_k),
                               lambda b_, i, j: (b_, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, t, t), jnp.float32, vma=vma),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret, name=scopes.DSA_INDEX_FWD)


@functools.cache
def _index_bwd_call(b, t, heads, di, dtype, scale, block_q, block_k,
                    interpret, vma):
    num_q, num_k = t // block_q, t // block_k
    kernel = functools.partial(_index_bwd_kernel, heads=heads, scale=scale,
                               block_q=block_q, block_k=block_k, num_k=num_k)
    return pl.pallas_call(
        kernel, grid=(b, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, heads, block_q, di),
                         lambda b_, i, j: (b_, 0, i, 0)),
            pl.BlockSpec((1, block_k, di), lambda b_, i, j: (b_, j, 0)),
            pl.BlockSpec((1, block_q, heads), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, block_q, block_k), lambda b_, i, j: (b_, i, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, heads, block_q, di),
                         lambda b_, i, j: (b_, 0, i, 0)),
            pl.BlockSpec((1, block_q, heads), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, 1, block_k, di),
                         lambda b_, i, j: (b_, i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, heads, t, di), dtype, vma=vma),
            jax.ShapeDtypeStruct((b, t, heads), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((b, num_q, t, di), jnp.float32, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((heads, block_q, di), jnp.float32),
                        pltpu.VMEM((block_q, heads), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret, name=scopes.DSA_INDEX_BWD)


def attention_block(t: int) -> int:
    """Block of the score and attention kernels, both sides: 512 where
    ``T`` allows (docs/kernels.md: eight query heads a step hold what one
    head holds at 1024 in :mod:`flash_attention`, four times over)."""
    for b in (512, 256, 128, 64, 32, 16, 8):
        if t % b == 0:
            return b
    raise ValueError(f"sequence length {t} must be divisible by 8 for the "
                     f"sparse attention kernels (pad the sequence)")


def index_scores(qi, ki, w, scale: float, interpret: bool):
    """The indexer's scores as a Pallas kernel: qi [B, T, HI, DI], ki [B,
    T, DI], w [B, T, HI] (float32 in the kernel) -> [B, T, T] float32.  Tiles wholly above the
    diagonal are zero and not computed; inside a tile the diagonal crosses
    the entries above it are computed like any other and mean nothing:
    every reader masks them, and their cotangent must be zero."""
    return _index_scores(qi, ki, w.astype(jnp.float32), scale, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _index_scores(qi, ki, w, scale, interpret):
    return _index_fwd(qi, ki, w, scale, interpret)[0]


def _index_fwd(qi, ki, w, scale, interpret):
    b, t, heads, di = qi.shape
    block = attention_block(t)
    qf = qi.transpose(0, 2, 1, 3)                        # [B, HI, T, DI]
    operands = (qf, ki, w)
    out = _index_fwd_call(b, t, heads, di, scale, block, block, interpret,
                          _out_vma(*operands))(*operands)
    return out, operands


def _index_bwd(scale, interpret, res, g):
    qf, ki, w = res
    b, heads, t, di = qf.shape
    block = attention_block(t)
    operands = (qf, ki, w, g)
    dqf, dw, dk_parts = _index_bwd_call(
        b, t, heads, di, qf.dtype, scale, block, block, interpret,
        _out_vma(*operands))(*operands)
    return (dqf.transpose(0, 2, 1, 3),
            jnp.sum(dk_parts, axis=1).astype(ki.dtype), dw)


_index_scores.defvjp(_index_fwd, _index_bwd)


# ---------------------------------------------------------------------------
# The selection
# ---------------------------------------------------------------------------

def _select_kernel(s_ref, mask_ref, lse_ref, key_ref, *, topk, block_q, t,
                   chunk):
    """Rows ``i * block_q ..`` of the mask from their scores, and the
    log-sum-exp of each row's scores over the keys it selects.

    A float32's bit pattern, its low 31 bits flipped where the sign is
    set, orders as the number does under signed integer comparison.  The
    ``topk``-th largest key of a row is built from its top bit down: a bit
    stays set while at least ``topk`` keys reach the candidate.  Keys above
    it are selected; of those equal to it, the lowest-indexed as many as
    are still wanted, the cut found by the same bisection over the index.
    Only the columns at or before the block's last row are looked at."""
    i = pl.program_id(1)
    row0 = i * block_q
    chunks = t // chunk
    live_chunks = jnp.minimum((row0 + block_q + chunk - 1) // chunk, chunks)

    def at(c):
        if isinstance(c, int):
            return slice(c * chunk, (c + 1) * chunk)
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    def position(c):
        row = row0 + lax.broadcasted_iota(jnp.int32, (block_q, chunk), 0)
        col = c * chunk + lax.broadcasted_iota(jnp.int32,
                                               (block_q, chunk), 1)
        return row, col

    def causal(c):
        row, col = position(c)
        return jnp.where(col <= row, s_ref[0, :, at(c)], NEG_INF)

    def highest(c, high):
        return jnp.maximum(high, jnp.max(causal(c), axis=1, keepdims=True))

    lowest = jnp.full((block_q, 1), NEG_INF, jnp.float32)

    def finish(selected, high):
        """The mask, and the rows' log-sum-exp over it in one more pass,
        from ``selected(c)``, chunk ``c``'s [block_q, chunk] selection, and
        ``high``, each row's largest causal score: a row's best key is
        selected whatever ``topk`` is."""
        for c in range(chunks):
            mask_ref[0, :, at(c)] = selected(c).astype(jnp.int32).astype(
                jnp.int8)
        mass = lax.fori_loop(
            0, live_chunks,
            lambda c, l: l + jnp.sum(jnp.where(
                selected(c), jnp.exp(causal(c) - high), 0.0), axis=1,
                keepdims=True),
            jnp.zeros((block_q, 1), jnp.float32))
        lse_ref[0, 0, :] = (high + jnp.log(mass))[:, 0]

    @pl.when(row0 + block_q <= topk)
    def _every_key():
        def selected(c):
            row, col = position(c)
            return col <= row

        finish(selected, lax.fori_loop(0, live_chunks, highest, lowest))

    @pl.when(row0 + block_q > topk)
    def _choose():
        def to_key(c, high):
            row, col = position(c)
            bits = lax.bitcast_convert_type(s_ref[0, :, at(c)], jnp.int32)
            key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
            key_ref[:, at(c)] = jnp.where(col <= row, key, INT_MIN)
            return highest(c, high)

        high = lax.fori_loop(0, live_chunks, to_key, lowest)

        def count(pred):
            def one(c, total):
                return total + jnp.sum(
                    pred(key_ref[:, at(c)], c).astype(jnp.int32), axis=1,
                    keepdims=True)
            return lax.fori_loop(0, live_chunks, one,
                                 jnp.zeros((block_q, 1), jnp.int32))

        def reaches(cand):
            return count(lambda key, c: key >= cand) >= topk

        tau = jnp.where(reaches(jnp.zeros((block_q, 1), jnp.int32)),
                        0, INT_MIN).astype(jnp.int32)

        def value_bit(n, tau):
            cand = tau | jnp.left_shift(jnp.int32(1), 30 - n)
            return jnp.where(reaches(cand), cand, tau)

        tau = lax.fori_loop(0, 31, value_bit, tau)
        wanted = topk - count(lambda key, c: key > tau)      # >= 1

        def ties_before(cut):
            def pred(key, c):
                return (key == tau) & (position(c)[1] < cut)
            return count(pred)

        bits = max(t - 1, 1).bit_length()

        def index_bit(n, cut):
            cand = cut | jnp.left_shift(jnp.int32(1), bits - 1 - n)
            return jnp.where(ties_before(cand) < wanted, cand, cut)

        # The largest index with fewer than ``wanted`` ties before it: the
        # last tie admitted sits there.
        cut = lax.fori_loop(0, bits, index_bit,
                            jnp.zeros((block_q, 1), jnp.int32))

        def selected(c):
            # Past the live chunks ``key_ref`` holds nothing: no column
            # there is at or before a row.
            row, col = position(c)
            key = key_ref[:, at(c)]
            chosen = (key > tau) | ((key == tau) & (col <= cut))
            return (col <= row) & ((row < topk) | chosen)

        finish(selected, high)


@functools.cache
def _select_call(b, t, topk, block_q, chunk, interpret, vma):
    kernel = functools.partial(_select_kernel, topk=topk, block_q=block_q,
                               t=t, chunk=chunk)
    return pl.pallas_call(
        kernel, grid=(b, t // block_q),
        in_specs=[pl.BlockSpec((1, block_q, t), lambda b_, i: (b_, i, 0))],
        out_specs=[pl.BlockSpec((1, block_q, t), lambda b_, i: (b_, i, 0)),
                   pl.BlockSpec((1, 1, block_q), lambda b_, i: (b_, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((b, t, t), jnp.int8, vma=vma),
                   jax.ShapeDtypeStruct((b, 1, t), jnp.float32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((block_q, t), jnp.int32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret, name=scopes.DSA_SELECT_KERNEL)


def select(scores, topk: int, interpret: bool):
    """``(mask [B, T, T] int8, lse [B, T] float32)`` of ``scores`` [B, T,
    T] float32 as a Pallas kernel: the keys each query selects
    (:func:`select_jnp` is the oracle) and the log-sum-exp of its scores
    over them."""
    b, t, _ = scores.shape
    block_q = min(SELECT_BLOCK_Q, t)
    chunk = min(SELECT_CHUNK, t)
    if t % block_q or t % chunk:
        raise ValueError(f"sequence length {t} must be divisible by "
                         f"{block_q} and {chunk} (pad the sequence)")
    mask, lse = _select_call(b, t, topk, block_q, chunk, interpret,
                             _out_vma(scores))(scores)
    return mask, lse[:, 0]


# ---------------------------------------------------------------------------
# Attention under the mask
# ---------------------------------------------------------------------------

def _by_tile_class(qi, kj, block_q, block_k, topk, tile_body):
    """Run ``tile_body(use_mask)`` for tile (qi, kj): not at all above the
    diagonal, without the mask where every row selects every earlier key
    and all of the tile's are earlier, with it anywhere else."""
    interior, diagonal = _block_class(qi, kj, block_q, block_k)
    dense = (qi + 1) * block_q <= topk
    unmasked = jnp.logical_and(interior, dense)

    @pl.when(unmasked)
    def _interior():
        tile_body(False)

    @pl.when(jnp.logical_and(jnp.logical_or(interior, diagonal),
                             jnp.logical_not(unmasked)))
    def _masked():
        tile_body(True)


def _selected(mask_ref):
    return mask_ref[0].astype(jnp.int32) != 0


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, acc_ref,
                m_scr, l_scr, *, group, block_q, block_k, num_k, topk,
                scale):
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile_body(use_mask):
        k, v = k_ref[0], v_ref[0]
        sel = _selected(mask_ref) if use_mask else None
        for g in range(group):
            s = _dot(q_ref[0, g], k, _NT) * scale        # [bq, bk]
            if use_mask:
                s = jnp.where(sel, s, NEG_INF)
            m = m_scr[g]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            # A row may have selected no key of the tiles so far.
            safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
            p = jnp.exp(s - safe)
            corr = jnp.exp(m - safe)
            m_scr[g] = m_new
            l_scr[g] = l_scr[g] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[g] = acc_ref[g] * corr + _dot(p, v, _NN)

    _by_tile_class(qi, kj, block_q, block_k, topk, tile_body)

    @pl.when(kj == num_k - 1)
    def _finalize():
        for g in range(group):
            l = l_scr[g]                   # > 0: every row selects a key
            o_ref[0, g] = (acc_ref[g] / l).astype(o_ref.dtype)
            lse_ref[0, g, :] = (m_scr[g] + jnp.log(l))[:, 0]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   mask_ref, s_ref, lse_i_ref, dkl_ref, dq_ref, g_ref,
                   acc_ref, p_ref, *, group, hkv, block_q, block_k, num_k,
                   topk, scale):
    """dQ of every head of a query block, and the indexer's scores'
    cotangent of the tile: a grid step is one key-value head's ``group``
    query heads on one tile, the key-value head innermost, so the tile's
    probabilities of all the heads pass through VMEM between two writes of
    ``g_ref``: ``g = dkl x (softmax over the selection of the scores -
    their mean)`` on the selected pairs and zero on the others."""
    qi, kj, kv = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(kj == 0, kv == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kv == 0)
    def _tile():
        p_ref[...] = jnp.zeros_like(p_ref)
        # Tiles above the diagonal stay so.
        g_ref[0] = jnp.zeros((block_q, block_k), jnp.float32)

    def tile_body(use_mask):
        k, v = k_ref[0], v_ref[0]
        sel = _selected(mask_ref) if use_mask else None
        total = jnp.zeros((block_q, block_k), jnp.float32)
        for g in range(group):
            s = _dot(q_ref[kv, g], k, _NT) * scale
            if use_mask:
                s = jnp.where(sel, s, NEG_INF)
            p = jnp.exp(s - lse_ref[kv, g, :][:, None])
            total = total + p
            dp = _dot(do_ref[kv, g], v, _NT)
            ds = p * (dp - delta_ref[kv, g, :][:, None])
            acc_ref[kv, g] += _dot(ds, k, _NN)
        p_ref[...] += total

        @pl.when(kv == hkv - 1)
        def _scores_cotangent():
            scores = s_ref[0]
            if use_mask:
                scores = jnp.where(sel, scores, NEG_INF)
            soft = jnp.exp(scores - lse_i_ref[0, 0, :][:, None])
            g_ref[0] = dkl_ref[0, 0, :][:, None] * (
                soft - p_ref[...] * (1.0 / (hkv * group)))

    _by_tile_class(qi, kj, block_q, block_k, topk, tile_body)

    @pl.when(kj == num_k - 1)
    def _finalize():
        dq_ref[kv] = (acc_ref[kv] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    mask_t_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, group,
                    block_q, block_k, num_q, topk, scale):
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile_body(use_mask):
        # Keys by rows, as flash_attention's dK/dV kernel: p and ds come
        # out [bk, bq], the shape dV and dK contract over.
        k, v = k_ref[0], v_ref[0]
        sel = _selected(mask_t_ref) if use_mask else None
        for g in range(group):
            q, do = q_ref[0, g], do_ref[0, g]
            s = _dot(k, q, _NT) * scale                  # [bk, bq]
            if use_mask:
                s = jnp.where(sel, s, NEG_INF)
            p = jnp.exp(s - lse_ref[0, g, :][None, :])
            dv_acc[...] += _dot(p, do, _NN)
            dp = _dot(v, do, _NT)
            ds = p * (dp - delta_ref[0, g, :][None, :])
            dk_acc[...] += _dot(ds, q, _NN)

    _by_tile_class(qi, ki, block_q, block_k, topk, tile_body)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _probs_kernel(q_ref, k_ref, lse_ref, mask_ref, s_ref, kl_ref, p_ref,
                  kl_acc, *, group, hkv, block_q, block_k, num_k, scale):
    """A query block's ``sum p log p - sum p I`` over its selection, ``p``
    the mean over the heads of the attention's probabilities and ``I`` the
    indexer's scores: the rows' KL less the log-sum-exp of ``I``, which is
    not this kernel's to know (``p`` sums to one over the selection).  The
    key-value head is innermost, so a tile's ``p`` is whole on its last
    step and never leaves VMEM."""
    qi, kj, kv = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(kj == 0, kv == 0))
    def _init():
        kl_acc[...] = jnp.zeros_like(kl_acc)

    @pl.when(kv == 0)
    def _tile():
        p_ref[...] = jnp.zeros_like(p_ref)

    @pl.when(_live(qi, kj, block_q, block_k))
    def _compute():
        k = k_ref[0]
        sel = _selected(mask_ref)
        total = jnp.zeros((block_q, block_k), jnp.float32)
        for g in range(group):
            s = _dot(q_ref[0, g], k, _NT) * scale
            total = total + jnp.exp(jnp.where(sel, s, NEG_INF)
                                    - lse_ref[0, g, :][:, None])
        p_ref[...] += total

        @pl.when(kv == hkv - 1)
        def _cross_entropy():
            p = p_ref[...] * (1.0 / (hkv * group))
            held = p > 0.0          # an unselected pair's p is 0
            kl_acc[...] += jnp.sum(jnp.where(
                held, p * (jnp.log(jnp.where(held, p, 1.0)) - s_ref[0]),
                0.0), axis=-1, keepdims=True)

    @pl.when(jnp.logical_and(kj == num_k - 1, kv == hkv - 1))
    def _finalize():
        kl_ref[0, 0, :] = kl_acc[...][:, 0]


def _kv_map(block_q, block_k):
    # Last key block with any causal entry for query block i: the steps
    # above the diagonal repeat its index and Mosaic elides the fetch.
    return lambda bh, i, j: (
        bh, jnp.minimum(j, ((i + 1) * block_q - 1) // block_k), 0)


def _head_innermost_maps(hkv, block_q, block_k):
    """Index maps of a grid (batch, query block, key block, key-value
    head): ``rows`` [B * Hkv, G, T, D], ``stat`` [B * Hkv, G, T] and
    ``keys`` [B * Hkv, T, D] a key-value head a step, ``tile`` [B, T, T].
    The steps above the diagonal repeat the indices of the last one under
    it, head included, so that nothing is fetched for them."""
    def clamped(index):
        def of_step(b_, i, j, h):
            last = ((i + 1) * block_q - 1) // block_k
            head = b_ * hkv + jnp.where(j > last, hkv - 1, h)
            return index(head, b_, i, jnp.minimum(j, last))
        return of_step

    return {"rows": clamped(lambda head, b_, i, j: (head, 0, i, 0)),
            "stat": clamped(lambda head, b_, i, j: (head, 0, i)),
            "keys": clamped(lambda head, b_, i, j: (head, j, 0)),
            "tile": clamped(lambda head, b_, i, j: (b_, i, j))}


def _q_first(block_q, block_k):
    # First query block that sees key block j.
    return lambda j, i: jnp.maximum(i, (j * block_k) // block_q)


@functools.cache
def _fwd_call(bh, hkv, group, t, d, dtype, scale, block_q, block_k, topk,
              interpret, vma):
    num_k = t // block_k
    kernel = functools.partial(_fwd_kernel, group=group, block_q=block_q,
                               block_k=block_k, num_k=num_k, topk=topk,
                               scale=scale)
    kv = _kv_map(block_q, block_k)
    return pl.pallas_call(
        kernel, grid=(bh, t // block_q, num_k),
        in_specs=[
            pl.BlockSpec((1, group, block_q, d),
                         lambda bh_, i, j: (bh_, 0, i, 0)),
            pl.BlockSpec((1, block_k, d), kv),
            pl.BlockSpec((1, block_k, d), kv),
            pl.BlockSpec((1, block_q, block_k),
                         lambda bh_, i, j: (bh_ // hkv, i, kv(bh_, i, j)[1])),
        ],
        out_specs=[
            pl.BlockSpec((1, group, block_q, d),
                         lambda bh_, i, j: (bh_, 0, i, 0)),
            pl.BlockSpec((1, group, block_q), lambda bh_, i, j: (bh_, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, group, t, d), dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, group, t), jnp.float32, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((group, block_q, d), jnp.float32),
                        pltpu.VMEM((group, block_q, 1), jnp.float32),
                        pltpu.VMEM((group, block_q, 1), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret, name=scopes.DSA_FWD)


@functools.cache
def _bwd_dq_call(bh, hkv, group, t, d, dtype, scale, block_q, block_k, topk,
                 interpret, vma):
    # In VMEM at the cell's shapes (32 heads of 128, 512 x 512 tiles): q,
    # dO and dQ of every head of a query block, 4 MiB each and twice (the
    # pipeline's two buffers), dQ's float32 accumulators 8 MiB, the
    # scores' tile and its cotangent's 1 MiB each and twice, the
    # probabilities' sum 1 MiB: 39 MiB and a head's temporaries.
    b, num_k = bh // hkv, t // block_k
    kernel = functools.partial(_bwd_dq_kernel, group=group, hkv=hkv,
                               block_q=block_q, block_k=block_k,
                               num_k=num_k, topk=topk, scale=scale)
    maps = _head_innermost_maps(hkv, block_q, block_k)
    rows = pl.BlockSpec((hkv, group, block_q, d),
                        lambda b_, i, j, h: (b_, 0, i, 0))
    stat = pl.BlockSpec((hkv, group, block_q), lambda b_, i, j, h: (b_, 0, i))
    keys = pl.BlockSpec((1, block_k, d), maps["keys"])
    tile = pl.BlockSpec((1, block_q, block_k), maps["tile"])
    row = pl.BlockSpec((1, 1, block_q), lambda b_, i, j, h: (b_, 0, i))
    return pl.pallas_call(
        kernel, grid=(b, t // block_q, num_k, hkv),
        in_specs=[rows, keys, keys, rows, stat, stat, tile, tile, row, row],
        out_specs=[rows, pl.BlockSpec((1, block_q, block_k),
                                      lambda b_, i, j, h: (b_, i, j))],
        out_shape=[jax.ShapeDtypeStruct((bh, group, t, d), dtype, vma=vma),
                   jax.ShapeDtypeStruct((b, t, t), jnp.float32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((hkv, group, block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, block_k), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        interpret=interpret, name=scopes.DSA_BWD_DQ)


@functools.cache
def _bwd_dkv_call(bh, hkv, group, t, d, dtype, scale, block_q, block_k,
                  topk, interpret, vma):
    num_q = t // block_q
    kernel = functools.partial(_bwd_dkv_kernel, group=group, block_q=block_q,
                               block_k=block_k, num_q=num_q, topk=topk,
                               scale=scale)
    first = _q_first(block_q, block_k)
    rows = pl.BlockSpec((1, group, block_q, d),
                        lambda bh_, j, i: (bh_, 0, first(j, i), 0))
    stat = pl.BlockSpec((1, group, block_q),
                        lambda bh_, j, i: (bh_, 0, first(j, i)))
    keys = pl.BlockSpec((1, block_k, d), lambda bh_, j, i: (bh_, j, 0))
    return pl.pallas_call(
        kernel, grid=(bh, t // block_k, num_q),
        in_specs=[
            rows, keys, keys, rows, stat, stat,
            pl.BlockSpec((1, block_k, block_q),
                         lambda bh_, j, i: (bh_ // hkv, j, first(j, i))),
        ],
        out_specs=[keys, keys],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), dtype, vma=vma),
                   jax.ShapeDtypeStruct((bh, t, d), dtype, vma=vma)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret, name=scopes.DSA_BWD_DKV)


@functools.cache
def _probs_call(b, hkv, group, t, d, scale, block_q, block_k, interpret,
                vma):
    num_k = t // block_k
    kernel = functools.partial(_probs_kernel, group=group, hkv=hkv,
                               block_q=block_q, block_k=block_k,
                               num_k=num_k, scale=scale)
    maps = _head_innermost_maps(hkv, block_q, block_k)
    tile = pl.BlockSpec((1, block_q, block_k), maps["tile"])
    return pl.pallas_call(
        kernel, grid=(b, t // block_q, num_k, hkv),
        in_specs=[
            pl.BlockSpec((1, group, block_q, d), maps["rows"]),
            pl.BlockSpec((1, block_k, d), maps["keys"]),
            pl.BlockSpec((1, group, block_q), maps["stat"]), tile, tile,
        ],
        out_specs=pl.BlockSpec((1, 1, block_q),
                               lambda b_, i, j, h: (b_, 0, i)),
        out_shape=jax.ShapeDtypeStruct((b, 1, t), jnp.float32, vma=vma),
        scratch_shapes=[pltpu.VMEM((block_q, block_k), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        interpret=interpret, name=scopes.DSA_PROBS)


def _fold_q(q, hkv):
    # [B, T, H, D] -> [B * Hkv, G, T, D]
    b, t, h, d = q.shape
    return q.reshape(b, t, hkv, h // hkv, d).transpose(0, 2, 3, 1, 4).reshape(
        b * hkv, h // hkv, t, d)


def _unfold_q(x, b):
    bh, g, t, d = x.shape
    return x.reshape(b, bh // b, g, t, d).transpose(0, 3, 1, 2, 4).reshape(
        b, t, (bh // b) * g, d)


def _fold_kv(k):
    # [B, T, Hkv, D] -> [B * Hkv, T, D]
    b, t, h, d = k.shape
    return k.transpose(0, 2, 1, 3).reshape(b * h, t, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def masked_attention_folded(q, k, v, scores, lse_i, mask, mask_t, topk: int,
                            scale: float, interpret: bool):
    """Softmax attention over the keys ``mask`` [B, T, T] int8 selects and
    the indexer's loss a query, by the Pallas kernels, on operands in the
    kernels' layout: q [B * Hkv, G, T, D] (the ``G`` query heads of a
    key-value head together; the bytes of ``[B * H, T, D]``), k/v [B * Hkv,
    T, D]; ``scores`` [B, T, T] float32 the indexer's, ``lse_i`` [B, T]
    their log-sum-exp over each row's selection (:func:`select`'s second
    result; a constant here); ``mask_t`` the mask's transpose (the dK/dV
    kernel reads it keys by rows); rows under ``topk`` select every
    earlier key.  Returns ``(o [B * Hkv, G, T, D], kl [B, T])``, ``kl[t] =
    KL(mean over the heads of the attention's probabilities || softmax
    over the selection of scores[t, .])``.

    One gradient rule for the pair: ``o``'s cotangent reaches q, k and v
    alone and ``kl``'s the scores alone, as ``dkl x (softmax - mean
    probabilities)`` on the selected pairs and zero on every other (the
    attention's probabilities are a constant of the loss, by this rule and
    not by ``stop_gradient``)."""
    return _masked_fwd(q, k, v, scores, lse_i, mask, mask_t, topk, scale,
                       interpret)[0]


def _masked_fwd(qf, kf, vf, scores, lse_i, mask, mask_t, topk, scale,
                interpret):
    bh, group, t, d = qf.shape
    b = mask.shape[0]
    hkv = bh // b
    block = attention_block(t)
    operands = (qf, kf, vf, mask)
    _record_tiles(scopes.DSA_FWD, bh, t, block, block, topk)
    of, lse = _fwd_call(bh, hkv, group, t, d, qf.dtype, scale, block, block,
                        topk, interpret, _out_vma(*operands))(*operands)
    with jax.named_scope(scopes.DSA_INDEX_LOSS):
        # Nothing the backward pass needs comes out of this call: under
        # jax.checkpoint the recomputed forward holds it as dead code.
        operands = (qf, kf, lse, mask, scores)
        _record_pass(scopes.DSA_PROBS)
        kl = _probs_call(b, hkv, group, t, d, scale, block, block, interpret,
                         _out_vma(*operands))(*operands)[:, 0] + lse_i
    return ((of, kl),
            (qf, kf, vf, of, lse, scores, lse_i, mask, mask_t))


def _masked_bwd(topk, scale, interpret, res, cotangents):
    dof, dkl = cotangents
    qf, kf, vf, of, lse, scores, lse_i, mask, mask_t = res
    bh, group, t, d = qf.shape
    hkv = bh // mask.shape[0]
    block = attention_block(t)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)                             # [BH, G, T]
    config = (bh, hkv, group, t, d, qf.dtype, scale, block, block, topk,
              interpret)
    operands = (qf, kf, vf, dof, lse, delta)
    loss = (mask, scores, lse_i[:, None], dkl.astype(jnp.float32)[:, None])
    _record_tiles(scopes.DSA_BWD_DQ, bh, t, block, block, topk)
    _record_pass(scopes.DSA_BWD_DQ)
    dq, g = _bwd_dq_call(*config, _out_vma(*operands, *loss))(*operands,
                                                              *loss)
    _record_tiles(scopes.DSA_BWD_DKV, bh, t, block, block, topk)
    dk, dv = _bwd_dkv_call(*config, _out_vma(*operands, mask_t))(
        *operands, mask_t)
    nothing = np.zeros(mask.shape, jax.dtypes.float0)
    return dq, dk, dv, g, jnp.zeros_like(lse_i), nothing, nothing


masked_attention_folded.defvjp(_masked_fwd, _masked_bwd)


def masked_attention(q, k, v, scores, lse_i, mask, mask_t, topk: int,
                     scale: float, interpret: bool):
    """:func:`masked_attention_folded` for q [B, T, H, D], k/v [B, T, Hkv,
    D]: the moves to the kernels' layout, the same kernels under the same
    gradient rule, and the move back.  Returns ``(o [B, T, H, D], kl [B,
    T])``."""
    hkv = k.shape[2]
    of, kl = masked_attention_folded(
        _fold_q(q, hkv), _fold_kv(k), _fold_kv(v), scores, lse_i, mask,
        mask_t, topk, scale, interpret)
    return _unfold_q(of, q.shape[0]), kl


# ---------------------------------------------------------------------------
# The layer's route
# ---------------------------------------------------------------------------

def _selection(qi, ki, w, topk, index_scale, kernels, interpret):
    """``(scores, mask, lse)``: :func:`indexer_selection`'s pair and, by
    the kernels, the log-sum-exp of each row's scores over its selection
    (None by the ``jax.numpy`` forms, whose loss finds it for itself)."""
    with jax.named_scope(scopes.DSA_INDEX_SCORES):
        scores = (index_scores(qi, ki, w, index_scale, interpret) if kernels
                  else index_scores_jnp(qi, ki, w, index_scale))
    with jax.named_scope(scopes.DSA_SELECT):
        held = lax.stop_gradient(scores)
        if kernels:
            return (scores,) + select(held, topk, interpret)
        return scores, select_jnp(held, topk), None


def indexer_selection(qi, ki, w, *, topk: int, index_scale: float,
                      kernels: Optional[bool] = None,
                      interpret: Optional[bool] = None):
    """``(scores [B, T, T] float32, mask [B, T, T])``: the indexer's scores
    (differentiable in qi, ki and w) and the keys each query selects by
    them (nonzero: selected; no gradient), by the Pallas kernels or by
    their ``jax.numpy`` forms as :func:`dsa_attention` chooses."""
    if kernels is None:
        kernels = path(qi) == "kernel"
    if interpret is None:
        interpret = _interpret_default(qi)
    return _selection(qi, ki, w, topk, index_scale, kernels, interpret)[:2]


def dsa_attention(q, k, v, qi, ki, w, *, topk: int, index_scale: float,
                  kernels: Optional[bool] = None,
                  interpret: Optional[bool] = None, folded: bool = False):
    """Sparse attention and its indexer's loss.

    q [B, T, H, D], k/v [B, T, Hkv, D]: the attention's operands; qi [B, T,
    HI, DI], ki [B, T, DI], w [B, T, HI]: the indexer's (the caller cuts
    their gradient off the rest of the model).  Returns ``(o [B, T, H, D],
    kl [B, T])``: attention over each query's ``topk`` best-scored earlier
    keys, and each query's ``KL(head-mean probabilities || softmax of the
    indexer's scores)`` over them.  ``o``'s gradient reaches q, k and v
    alone, ``kl``'s qi, ki and w alone.  ``kernels`` (default: on a TPU)
    picks the Pallas kernels over their ``jax.numpy`` forms.  ``folded``:
    q [B * H, T, D] and k/v [B * Hkv, T, D] were born in the kernels'
    layout (:mod:`horovod_tpu.ops.qk_assemble`), and ``o`` leaves in it;
    the kernels' alone."""
    scale = q.shape[-1] ** -0.5
    if kernels is None:
        kernels = path(q) == "kernel"
    if folded and not kernels:
        raise ValueError("folded operands are the kernels': the jax.numpy "
                         "forms take [B, T, H, D]")
    interp = _interpret_default(q) if interpret is None else interpret
    scores, mask, lse_i = _selection(qi, ki, w, topk, index_scale, kernels,
                                     interp)
    if not kernels:
        with jax.named_scope(scopes.DSA_FLASH):
            o, _, p = attention_jnp(q, k, v, mask, scale)
        with jax.named_scope(scopes.DSA_INDEX_LOSS):
            return o, indexer_kl(scores, mask, lax.stop_gradient(p))
    with jax.named_scope(scopes.DSA_SELECT):
        mask_t = jnp.swapaxes(mask, 1, 2)
    with jax.named_scope(scopes.DSA_FLASH):
        if not folded:
            return masked_attention(q, k, v, scores, lse_i, mask, mask_t,
                                    topk, scale, interp)
        o, kl = masked_attention_folded(
            q.reshape((k.shape[0], -1) + q.shape[1:]), k, v, scores, lse_i,
            mask, mask_t, topk, scale, interp)
        return o.reshape(q.shape), kl
