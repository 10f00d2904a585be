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
scratch.  Causal masking skips the compute of key blocks strictly above
the diagonal (``pl.when``).  The backward pass is the standard flash
recomputation: a per key-block kernel for dK/dV streaming query tiles,
and a per query-block kernel for dQ streaming key tiles, using the saved
row max/denominator.

``interpret=True`` (or ``HOROVOD_FLASH_INTERPRET=1``) runs the kernels
in the Pallas interpreter — exact same code path, CPU-executable — which
is how the CI oracle tests run without a TPU.
"""

from __future__ import annotations

import functools
import os

import numpy as np
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


def _mask_scores(s, qi, kj, block_q, block_k, causal, qseg_ref,
                 kseg_ref=None):
    """Apply causal and/or segment (sequence-packing) masks to a score
    block.  Segment ids ride a [B, 1, T] layout like the m/l rows; tokens
    attend only within their own segment.  ``kseg_ref`` defaults to the
    q-side ref (self-attention); ring attention passes the ROTATED
    K-side ids separately."""
    if causal:
        qpos = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = kj * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    if qseg_ref is not None:
        if kseg_ref is None:
            kseg_ref = qseg_ref
        qseg = qseg_ref[0, 0, pl.dslice(qi * block_q, block_q)]
        kseg = kseg_ref[0, 0, pl.dslice(kj * block_k, block_k)]
        s = jnp.where(qseg[:, None] == kseg[None, :], s, NEG_INF)
    return s


def _fwd_kernel(q_ref, k_ref, v_ref, *rest,
                block_q: int, block_k: int, num_k: int, causal: bool,
                scale: float, segments: bool):
    if segments:
        qseg_ref, kseg_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    rows = pl.dslice(qi * block_q, block_q)

    @pl.when(kj == 0)
    def _init():
        m_ref[0, 0, rows] = jnp.full((block_q,), NEG_INF, jnp.float32)
        l_ref[0, 0, rows] = jnp.zeros((block_q,), jnp.float32)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute():
        q = q_ref[0].astype(jnp.float32)                 # [bq, D]
        k_blk = k_ref[0].astype(jnp.float32)             # [bk, D]
        v_blk = v_ref[0].astype(jnp.float32)
        m = m_ref[0, 0, rows]
        l = l_ref[0, 0, rows]
        acc = acc_ref[...]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        s = _mask_scores(s, qi, kj, block_q, block_k, causal, qseg_ref,
                         kseg_ref)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(s - safe_m[:, None])
        p = jnp.where(s == NEG_INF, 0.0, p)
        corr = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - safe_m))
        m_ref[0, 0, rows] = m_new
        l_ref[0, 0, rows] = l * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc * corr[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # Key blocks strictly above the diagonal contribute nothing.
        pl.when(kj * block_k < (qi + 1) * block_q)(compute)
    else:
        compute()

    @pl.when(kj == num_k - 1)
    def _finalize():
        l = l_ref[0, 0, rows]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Backward — standard flash recomputation
#   D_i  = rowsum(dO ⊙ O)
#   P    = exp(QKᵀ·scale − m) / l          (recomputed per block)
#   dV  += Pᵀ dO
#   dP   = dO Vᵀ
#   dS   = P ⊙ (dP − D_i)
#   dQ  += dS K · scale ;  dK += dSᵀ Q · scale
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, m_ref, l_ref,
                   *rest, block_q: int, block_k: int,
                   num_k: int, causal: bool, scale: float,
                   segments: bool):
    if segments:
        qseg_ref, kseg_ref, dq_ref, acc_ref = rest
    else:
        dq_ref, acc_ref = rest
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    rows = pl.dslice(qi * block_q, block_q)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute():
        q = q_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        m = m_ref[0, 0, rows]
        l = l_ref[0, 0, rows]
        safe_m = jnp.where(m == NEG_INF, 0.0, m)
        denom = jnp.where(l == 0.0, 1.0, l)
        di = jnp.sum(do * o, axis=-1)                    # [bq]
        k_blk = k_ref[0].astype(jnp.float32)             # [bk, D]
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _mask_scores(s, qi, kj, block_q, block_k, causal, qseg_ref,
                         kseg_ref)
        p = jnp.where(s == NEG_INF, 0.0,
                      jnp.exp(s - safe_m[:, None])) / denom[:, None]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk]
        ds = p * (dp - di[:, None])
        acc_ref[...] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        pl.when(kj * block_k < (qi + 1) * block_q)(compute)
    else:
        compute()

    @pl.when(kj == num_k - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, m_ref, l_ref,
                    *rest, block_q: int, block_k: int, num_q: int,
                    causal: bool, scale: float, segments: bool):
    if segments:
        (qseg_ref, kseg_ref, dk_ref, dv_ref, dk_acc_ref,
         dv_acc_ref) = rest
    else:
        dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = rest
        qseg_ref = kseg_ref = None
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    rows = pl.dslice(qi * block_q, block_q)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def compute():
        k = k_ref[0].astype(jnp.float32)                 # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        q_blk = q_ref[0].astype(jnp.float32)             # [bq, D]
        o_blk = o_ref[0].astype(jnp.float32)
        do_blk = do_ref[0].astype(jnp.float32)
        m_blk = m_ref[0, 0, rows]
        l_blk = l_ref[0, 0, rows]
        safe_m = jnp.where(m_blk == NEG_INF, 0.0, m_blk)
        denom = jnp.where(l_blk == 0.0, 1.0, l_blk)
        di = jnp.sum(do_blk * o_blk, axis=-1)
        s = jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        s = _mask_scores(s, qi, ki, block_q, block_k, causal, qseg_ref,
                         kseg_ref)
        p = jnp.where(s == NEG_INF, 0.0,
                      jnp.exp(s - safe_m[:, None])) / denom[:, None]
        dv_acc_ref[...] += jax.lax.dot_general(
            p, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, D]
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - di[:, None])
        dk_acc_ref[...] += jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        # Query blocks strictly left of this key block see none of it.
        pl.when((qi + 1) * block_q > ki * block_k)(compute)
    else:
        compute()

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

def _causal_kv_map(block_q, block_k):
    # Last key block with any unmasked entry for query block i.
    return lambda bh_, i, j: (
        bh_, jnp.minimum(j, ((i + 1) * block_q - 1) // block_k), 0)


def _causal_q_map(block_q, block_k):
    # First query block that sees key block j.
    return lambda bh_, j, i: (
        bh_, jnp.maximum(i, (j * block_k) // block_q), 0)


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


def _fwd_parts(qf, kf, vf, qsegf, ksegf, h, causal, scale, block_q,
               block_k, interpret):
    """Folded-layout forward: (of, m, l) with m/l the [bh, 1, T] online
    softmax state — the raw pieces ring attention merges across steps.
    ``qsegf``/``ksegf`` are [B, 1, T] (pass the same array for
    self-attention)."""
    bh, t, d = qf.shape
    num_k = t // block_k
    grid = (bh, t // block_q, num_k)
    kernel = functools.partial(_fwd_kernel, block_q=block_q,
                               block_k=block_k, num_k=num_k, causal=causal,
                               scale=scale, segments=qsegf is not None)
    # Causal: masked steps (above the diagonal) clamp the K/V block index
    # to the last live block — same index as the preceding step, so Mosaic
    # elides the DMA instead of fetching a tile whose work pl.when skips.
    kv_map = (_causal_kv_map(block_q, block_k) if causal
              else (lambda bh_, i, j: (bh_, j, 0)))
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh_, i, j: (bh_, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_k, d), kv_map),
    ]
    operands = [qf, kf, vf]
    if qsegf is not None:
        in_specs += [_seg_spec(t, h), _seg_spec(t, h)]
        operands += [qsegf, ksegf]
    vma = _out_vma(*operands)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, i, j: (bh_, i, 0)),
            # TPU tiling: the last two block dims must be (8k, 128k) or
            # equal the array dims — a [bh, 1, T] layout with full
            # (1, 1, T) blocks satisfies that for any block_q.  The m/l
            # rows double as the online-softmax running state across the
            # key-block grid dimension (the block is revisited, so it
            # stays resident in VMEM).
            pl.BlockSpec((1, 1, t), lambda bh_, i, j: (bh_, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda bh_, i, j: (bh_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), qf.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name=scopes.FLASH_FWD,
    )(*operands)


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


def _bwd_parts(qf, kf, vf, of, dof, m, l, qsegf, ksegf, h, causal, scale,
               block_q, block_k, interpret):
    """Folded-layout backward: (dqf, dkf, dvf) from the GLOBAL (m, l)
    rows.  Ring attention calls this per rotating block with the final
    accumulated m/l — the per-block contributions are then the exact
    global-softmax gradients (p recomputed as exp(s − m)/l)."""
    bh, t, d = qf.shape
    num_k = t // block_k
    num_q = t // block_q
    segments = qsegf is not None
    kernel_dq = functools.partial(_bwd_dq_kernel, block_q=block_q,
                                  block_k=block_k, num_k=num_k,
                                  causal=causal, scale=scale,
                                  segments=segments)
    kv_map = (_causal_kv_map(block_q, block_k) if causal
              else (lambda bh_, i, j: (bh_, j, 0)))
    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh_, i, j: (bh_, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_q, d), lambda bh_, i, j: (bh_, i, 0)),
        pl.BlockSpec((1, block_q, d), lambda bh_, i, j: (bh_, i, 0)),
        pl.BlockSpec((1, 1, t), lambda bh_, i, j: (bh_, 0, 0)),
        pl.BlockSpec((1, 1, t), lambda bh_, i, j: (bh_, 0, 0)),
    ]
    dq_operands = [qf, kf, vf, of, dof, m, l]
    if segments:
        dq_specs += [_seg_spec(t, h), _seg_spec(t, h)]
        dq_operands += [qsegf, ksegf]
    vma = _out_vma(*dq_operands)
    dq = pl.pallas_call(
        kernel_dq,
        grid=(bh, num_q, num_k),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh_, i, j: (bh_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), qf.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name=scopes.FLASH_BWD_DQ,
    )(*dq_operands)

    kernel_dkv = functools.partial(_bwd_dkv_kernel, block_q=block_q,
                                   block_k=block_k, num_q=num_q,
                                   causal=causal, scale=scale,
                                   segments=segments)
    q_map = (_causal_q_map(block_q, block_k) if causal
             else (lambda bh_, j, i: (bh_, i, 0)))
    dkv_specs = [
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, block_k, d), lambda bh_, j, i: (bh_, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh_, j, i: (bh_, j, 0)),
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, 1, t), lambda bh_, j, i: (bh_, 0, 0)),
        pl.BlockSpec((1, 1, t), lambda bh_, j, i: (bh_, 0, 0)),
    ]
    dkv_operands = [qf, kf, vf, of, dof, m, l]
    if segments:
        dkv_specs += [_seg_spec(t, h), _seg_spec(t, h)]
        dkv_operands += [qsegf, ksegf]
    vma = _out_vma(*dkv_operands)
    dk, dv = pl.pallas_call(
        kernel_dkv,
        grid=(bh, num_k, num_q),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh_, j, i: (bh_, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, j, i: (bh_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), qf.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, t, d), qf.dtype, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name=scopes.FLASH_BWD_DKV,
    )(*dkv_operands)
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
def flash_attention(q, k, v, causal: bool = True,
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
    ≤ 1024 dividing ``T`` (≤ 512 when ``D > 128`` — the 1024 sweep only
    covered head dims ≤ 128, and bigger heads roughly double the bwd
    kernel's VMEM pressure).  Swept on a real v5e (docs/kernels.md): 512
    blocks run the fwd+bwd pair 2.7× faster than 128 blocks at T=2048
    and 4.2× at T=8192, and 1024 another 1.13–1.33× over 512 (r4 sweep;
    bigger tiles amortize the grid/DMA overhead and feed the MXU longer
    contractions; 1024×1024 f32 scores ≈ 4 MB of the ~16 MB VMEM, still
    comfortable next to the tile operands).

    ``segment_ids`` ([B, T] int32) enables sequence packing: tokens
    attend only within their own segment (composes with ``causal``) —
    the block-sparse masking XLA's fused attention cannot express, and
    the reason the kernel scaffold exists (docs/kernels.md).
    """
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                        interpret, segment_ids)
    return out


def _auto_block(t: int, head_dim: Optional[int] = None) -> int:
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
    # 1024 preferred over 512 since r4: measured fwd+bwd 1.33x at T=2048
    # (B4 H32 D128), 1.13x at T=4096/8192 (docs/kernels.md table);
    # 1024x1024 f32 scores = 4 MB of VMEM, still comfortable.  The 1024
    # preference was swept at head_dim<=128 only; larger head dims
    # roughly double the dkv kernel's operand + f32 score/p VMEM
    # pressure, so cap the auto choice at 512 there (explicit
    # block_q/block_k still override).
    prefs = (512, 256, 128) if (head_dim or 0) > 128 else (1024, 512,
                                                           256, 128)
    for b in prefs:
        if t % b == 0:
            return b
    raise ValueError(
        f"sequence length {t} must be divisible by 128 for auto block "
        f"sizing (pad the sequence, or pass explicit block_q/block_k)")


def _eff_blocks(t, block_q, block_k, head_dim=None):
    # None = auto (largest power of two <= 1024 dividing T — capped at
    # 512 when head_dim > 128, see _auto_block — measured fastest);
    # explicit blocks are clamped to T so e.g. T=64 works with block
    # 128 (divisibility still enforced after clamping).
    bq = _auto_block(t, head_dim) if block_q is None else min(block_q, t)
    bk = _auto_block(t, head_dim) if block_k is None else min(block_k, t)
    return bq, bk


@jax.named_scope(scopes.ATTN_FLASH)
def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               segment_ids=None):
    d = q.shape[-1]
    scale_ = (d ** -0.5) if scale is None else scale
    interp = _interpret_default(q) if interpret is None else interpret
    bq, bk = _eff_blocks(q.shape[1], block_q, block_k, d)
    return _fwd(q, k, v, segment_ids, causal, scale_, bq, bk, interp)


@jax.named_scope(scopes.ATTN_FLASH)
def _flash_bwd(causal, scale, block_q, block_k, interpret, res, do):
    t, d = res[0].shape[1], res[0].shape[-1]
    scale_ = (d ** -0.5) if scale is None else scale
    interp = _interpret_default(res[0]) if interpret is None else interpret
    bq, bk = _eff_blocks(t, block_q, block_k, d)
    return _bwd(causal, scale_, bq, bk, interp, res, do)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
