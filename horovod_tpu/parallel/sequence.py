"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has NO sequence machinery (SURVEY §5.7: Horovod predates it;
its closest primitive is dim-0 allgather).  A TPU-native framework makes
long-context training first-class: the sequence axis is a mesh axis, K/V
blocks ride ICI with ``lax.ppermute`` (ring attention, Liu et al. 2023) or
heads/sequence are exchanged with ``lax.all_to_all`` (DeepSpeed-Ulysses,
Jacobs et al. 2023).

Both run inside ``shard_map`` with tensors laid out ``[batch, seq_local,
heads, head_dim]``; sequence shards are contiguous chunks in rank order
(shard i owns global positions [i*T, (i+1)*T)).

Ring attention overlaps compute with the ICI transfer of the next K/V
block and keeps memory at O(seq_local^2-per-block) via online (flash-style)
softmax accumulation, so sequence length scales linearly with the number
of chips.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.telemetry import scopes


def _block_attention(q, k, v, m, l, o, *, q_offset, k_offset, causal, scale,
                     q_seg=None, k_seg=None):
    """One q-block x k-block update of the online-softmax state.

    q: [B, Tq, H, D]; k, v: [B, Tk, H, D]
    m, l: [B, H, Tq] running max / denominator; o: [B, Tq, H, D] running
    numerator.  ``q_seg``/``k_seg`` ([B, Tq]/[B, Tk]) mask cross-segment
    pairs for sequence packing.  Returns updated (m, l, o).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # [B, H, Tq, Tk]
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        qpos = q_offset + jnp.arange(tq)[:, None]
        kpos = k_offset + jnp.arange(tk)[None, :]
        s = jnp.where(qpos >= kpos, s, -jnp.inf)
    if q_seg is not None:
        s = jnp.where(q_seg[:, None, :, None] == k_seg[:, None, None, :],
                      s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)                       # [B, H, Tq]
    m_new = jnp.maximum(m, m_blk)
    # Guard fully-masked rows: exp(-inf - -inf) -> nan without the select.
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - safe_m[..., None])                # [B, H, Tq, Tk]
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    corr = jnp.exp(jnp.where(jnp.isneginf(m), m_new, m) - safe_m)
    corr = jnp.where(jnp.isneginf(m), 0.0, corr)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = (o * corr.transpose(0, 2, 1)[..., None] +
             jnp.einsum("bhqk,bkhd->bqhd", p, v))
    return m_new, l_new, o_new


@jax.named_scope(scopes.ATTN_RING)
def ring_attention(q, k, v, axis_name: str = "seq", causal: bool = True,
                   scale: Optional[float] = None, segment_ids=None):
    """Exact attention over a sequence sharded across ``axis_name``.

    q/k/v: [B, T_local, H, D] (this shard's chunk).  K/V blocks rotate
    around the ring via ``ppermute`` while each device accumulates its
    queries' online softmax; after axis_size steps every query has seen
    every key.  Returns [B, T_local, H, D].

    ``segment_ids`` ([B, T_local] int32, THIS shard's slice of the global
    packing layout) restricts attention to same-segment pairs: the K-side
    ids rotate around the ring with their K/V block, and the block mask is
    segment equality — the same composition the flash kernel uses.  The
    online-softmax state already tolerates fully-masked blocks (m stays
    -inf, l stays 0), so segments that live entirely on other shards cost
    only the masked matmul.
    """
    size = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    scale = (d ** -0.5) if scale is None else scale

    m = jnp.full((b, h, t), -jnp.inf, q.dtype)
    l = jnp.zeros((b, h, t), q.dtype)
    o = jnp.zeros_like(q)

    # The scan carry's vma type must be stable: after one step the online
    # state varies over EVERY axis q/k/v vary over (e.g. 'model' too when
    # composed with tensor parallelism), not just the ring axis.  Pcast the
    # initial zeros up to the union of the inputs' vma sets.
    from horovod_tpu.parallel._vma import pin_to, vma_of
    _match_vma = pin_to(vma_of(q) | vma_of(k) | vma_of(v) | {axis_name})

    m, l, o = _match_vma(m), _match_vma(l), _match_vma(o)
    q_offset = idx * t
    perm = [(i, (i + 1) % size) for i in range(size)]

    def step(carry, s):
        if segment_ids is None:
            m, l, o, k_blk, v_blk = carry
            k_seg = None
        else:
            m, l, o, k_blk, v_blk, k_seg = carry
        # Block s arrived from rank (idx - s) mod size.
        k_offset = ((idx - s) % size) * t
        m, l, o = _block_attention(q, k_blk, v_blk, m, l, o,
                                   q_offset=q_offset, k_offset=k_offset,
                                   causal=causal, scale=scale,
                                   q_seg=segment_ids, k_seg=k_seg)
        # Rotate K/V (and their segment ids) to the right neighbor (ICI).
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        if segment_ids is None:
            return (m, l, o, k_blk, v_blk), None
        k_seg = lax.ppermute(k_seg, axis_name, perm)
        return (m, l, o, k_blk, v_blk, k_seg), None

    init = ((m, l, o, k, v) if segment_ids is None
            else (m, l, o, k, v, segment_ids))
    out = lax.scan(step, init, jnp.arange(size))[0]
    m, l, o = out[0], out[1], out[2]
    denom = jnp.where(l == 0.0, 1.0, l).transpose(0, 2, 1)[..., None]
    return o / denom


def _merge_online(m, l, acc, m_b, l_b, o_b):
    """Merge a block's (m_b, l_b, o_b-normalized) into the running
    (m, l, acc-unnormalized) online-softmax state.  All m/l are
    [bh, 1, T] fp32; acc/o_b are [bh, T, D]."""
    m_new = jnp.maximum(m, m_b)
    safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    c1 = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe))
    c2 = jnp.where(jnp.isneginf(m_b), 0.0, jnp.exp(m_b - safe))
    l_new = l * c1 + l_b * c2
    row = lambda x: x[:, 0, :, None]                     # [bh, T, 1]
    acc_new = acc * row(c1) + o_b.astype(jnp.float32) * row(l_b * c2)
    return m_new, l_new, acc_new


def _lax_fwd_parts(qf, kf, vf, qsegf, ksegf, h, causal, scale, bq, bk,
                   interp):
    """Interpret-mode twin of ``flash_attention._fwd_parts``: the same
    (o, m, l) contract in plain lax ops.  Exists because the Pallas HLO
    interpreter traces kernel internals into the vma-checked jaxpr and
    rejects ppermuted operands under ``check_vma=True`` (CPU-only
    limitation; the compiled TPU path runs the kernel).  Doubles as an
    independent oracle of the kernel's formulas."""
    s = jnp.einsum("bqd,bkd->bqk", qf.astype(jnp.float32),
                   kf.astype(jnp.float32)) * scale
    t = qf.shape[1]
    if causal:
        pos = jnp.arange(t)
        s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    if qsegf is not None:
        qs = jnp.repeat(qsegf[:, 0, :], h, axis=0)       # [bh, T]
        ks = jnp.repeat(ksegf[:, 0, :], h, axis=0)
        s = jnp.where(qs[:, :, None] == ks[:, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)                              # [bh, T]
    safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - safe[..., None]))
    l = jnp.sum(p, axis=-1)
    denom = jnp.where(l == 0.0, 1.0, l)
    o = (jnp.einsum("bqk,bkd->bqd", p, vf.astype(jnp.float32)) /
         denom[..., None]).astype(qf.dtype)
    return o, m[:, None, :], l[:, None, :]


def _lax_bwd_parts(qf, kf, vf, of, dof, m, l, qsegf, ksegf, h, causal,
                   scale, bq, bk, interp):
    """Interpret-mode twin of ``flash_attention._bwd_parts`` (same
    global-(m, l) blockwise gradient formulas in plain lax ops)."""
    f32 = jnp.float32
    s = jnp.einsum("bqd,bkd->bqk", qf.astype(f32),
                   kf.astype(f32)) * scale
    t = qf.shape[1]
    if causal:
        pos = jnp.arange(t)
        s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    if qsegf is not None:
        qs = jnp.repeat(qsegf[:, 0, :], h, axis=0)
        ks = jnp.repeat(ksegf[:, 0, :], h, axis=0)
        s = jnp.where(qs[:, :, None] == ks[:, None, :], s, -jnp.inf)
    safe = jnp.where(jnp.isneginf(m[:, 0, :]), 0.0, m[:, 0, :])
    denom = jnp.where(l[:, 0, :] == 0.0, 1.0, l[:, 0, :])
    p = jnp.where(jnp.isneginf(s), 0.0,
                  jnp.exp(s - safe[..., None])) / denom[..., None]
    do32, o32 = dof.astype(f32), of.astype(f32)
    di = jnp.sum(do32 * o32, axis=-1)                    # [bh, T]
    dv = jnp.einsum("bqk,bqd->bkd", p, do32)
    dp = jnp.einsum("bqd,bkd->bqk", do32, vf.astype(f32))
    ds = p * (dp - di[..., None])
    dq = jnp.einsum("bqk,bkd->bqd", ds, kf.astype(f32)) * scale
    dk = jnp.einsum("bqk,bqd->bkd", ds, qf.astype(f32)) * scale
    return dq.astype(qf.dtype), dk.astype(kf.dtype), dv.astype(vf.dtype)


def _exec_on_tpu(x) -> bool:
    """See :func:`horovod_tpu.ops.flash_attention._exec_on_tpu` — the
    mesh-executing-the-computation platform answer (not the host's
    default backend)."""
    from horovod_tpu.ops import flash_attention as fa
    return fa._exec_on_tpu(x)


def _interp_default_for(x) -> bool:
    """Operand-aware kernel interpret default — delegates to
    :func:`horovod_tpu.ops.flash_attention._interpret_default`."""
    from horovod_tpu.ops import flash_attention as fa
    return fa._interpret_default(x)


def _ring_use_kernel(interpret, interp) -> bool:
    """Kernel vs lax-twin selection for the ring parts: compiled (TPU)
    always runs the kernel; an EXPLICIT interpreter request — the
    ``interpret=True`` argument or ``HOROVOD_FLASH_INTERPRET=1`` —
    keeps the kernel in the Pallas interpreter (kernel-debug surface);
    only the implicit non-TPU default takes the lax twin."""
    import os
    return ((interpret is True) or not interp or
            os.environ.get("HOROVOD_FLASH_INTERPRET") == "1")


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_flash_attention(q, k, v, axis_name: str = "seq",
                         causal: bool = True,
                         scale: Optional[float] = None,
                         interpret: Optional[bool] = None,
                         segment_ids=None):
    """Ring attention with the Pallas flash kernel as the per-step block
    math (Liu et al. 2023 structure; kernel from
    ``ops/flash_attention``).

    Identical semantics to :func:`ring_attention` — exact attention over
    a sequence sharded on ``axis_name``, K/V (and K-side segment ids)
    rotating via ``ppermute`` — but each ring step runs the flash
    forward kernel on the (local Q) x (arriving K/V) pair and merges the
    kernel's online-softmax state (m, l) across steps, so scores never
    materialize in HBM and the block math rides the measured-faster
    kernel (docs/kernels.md).  The DIAGONAL step (own block) uses the
    causal kernel with tile elision; off-diagonal steps are
    position-free (fully visible or fully masked by ring geometry), so
    they run the non-causal kernel and masked steps are zeroed at the
    merge — the same wasted-matmul cost profile as the lax route.

    The backward is a hand-scheduled second ring pass: per arriving
    block, the flash dq/dkv kernels run with the FINAL (m, l) rows —
    block contributions under the global softmax are exactly the global
    gradients — dq accumulates locally while dk/dv accumulate on the
    rotating block and arrive home after the full cycle.
    """
    out, _ = _ring_flash_fwd(q, k, v, axis_name, causal, scale,
                             interpret, segment_ids)
    return out


@jax.named_scope(scopes.ATTN_RING_FLASH)
def _ring_flash_fwd(q, k, v, axis_name, causal, scale, interpret,
                    segment_ids):
    from horovod_tpu.ops import flash_attention as fa

    size = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    bq, bk = fa._eff_blocks(q.shape[1], None, None, q.shape[-1],
                            segment_ids is not None)
    b, t, h, d = fa._check_shapes(q, k, v, bq, bk)
    scale_ = (d ** -0.5) if scale is None else scale
    interp = _interp_default_for(q) if interpret is None else interpret

    if segment_ids is not None:
        if segment_ids.shape != (b, t):
            raise ValueError(
                f"segment_ids must be [B, T_local] = {(b, t)} matching "
                f"this shard's q/k/v, got {segment_ids.shape}")
        if not jnp.issubdtype(segment_ids.dtype, jnp.integer):
            raise ValueError(
                f"segment_ids must be integer, got {segment_ids.dtype}")
    qf, kf, vf = fa._fold(q), fa._fold(k), fa._fold(v)
    segf = (segment_ids.reshape(b, 1, t)
            if segment_ids is not None else None)

    from horovod_tpu.parallel._vma import pin_to, vma_of
    _pin = pin_to(vma_of(q) | vma_of(k) | vma_of(v) | {axis_name})

    # Parts selection: the compiled TPU path always runs the kernel; an
    # EXPLICIT interpreter request (interpret=True or
    # HOROVOD_FLASH_INTERPRET=1) keeps the kernel in the Pallas
    # interpreter (the kernel-debug/test surface; needs check_vma=False
    # — the interpreter traces kernel internals into the vma-checked
    # jaxpr and rejects ppermuted operands); the None-default on a
    # non-TPU backend takes the lax twin so user CPU runs work under
    # check_vma=True train steps.
    use_kernel = _ring_use_kernel(interpret, interp)
    fwd_parts = fa._fwd_parts if use_kernel else _lax_fwd_parts

    # Diagonal step: own K/V, standard causal kernel (tile elision on).
    o0, m, l = fwd_parts(qf, kf, vf, segf, segf, h, causal, scale_,
                         bq, bk, interp)
    row = lambda x: x[:, 0, :, None]
    acc = o0.astype(jnp.float32) * row(l)
    m, l, acc = _pin(m), _pin(l), _pin(acc)

    perm = [(i, (i + 1) % size) for i in range(size)]
    k_rot = lax.ppermute(kf, axis_name, perm)
    v_rot = lax.ppermute(vf, axis_name, perm)
    kseg_rot = (lax.ppermute(segf, axis_name, perm)
                if segf is not None else None)

    def step(carry, s):
        if segf is None:
            m, l, acc, k_rot, v_rot = carry
            kseg = None
        else:
            m, l, acc, k_rot, v_rot, kseg = carry
        o_b, m_b, l_b = fwd_parts(qf, k_rot, v_rot, segf, kseg, h,
                                  False, scale_, bq, bk, interp)
        if causal:
            # Block s arrived from rank (idx - s) mod size: fully
            # visible iff it sits strictly left of our chunk (s <= idx).
            vis = (s <= idx)
            m_b = jnp.where(vis, m_b, -jnp.inf)
            l_b = jnp.where(vis, l_b, 0.0)
        m, l, acc = _merge_online(m, l, acc, m_b, l_b, o_b)
        k_rot = lax.ppermute(k_rot, axis_name, perm)
        v_rot = lax.ppermute(v_rot, axis_name, perm)
        if segf is None:
            return (m, l, acc, k_rot, v_rot), None
        kseg = lax.ppermute(kseg, axis_name, perm)
        return (m, l, acc, k_rot, v_rot, kseg), None

    init = ((m, l, acc, k_rot, v_rot) if segf is None
            else (m, l, acc, k_rot, v_rot, kseg_rot))
    out = lax.scan(step, init, jnp.arange(1, size))[0]
    m, l, acc = out[0], out[1], out[2]
    denom = jnp.where(l == 0.0, 1.0, l)
    of = (acc / row(denom)).astype(q.dtype)
    return fa._unfold(of, b, h), (qf, kf, vf, segf, of, m, l, b, h)


@jax.named_scope(scopes.ATTN_RING_FLASH)
def _ring_flash_bwd(axis_name, causal, scale, interpret, res, do):
    from horovod_tpu.ops import flash_attention as fa

    qf, kf, vf, segf, of, m, l, b, h = res
    size = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    bh, t, d = qf.shape
    scale_ = (d ** -0.5) if scale is None else scale
    interp = _interp_default_for(qf) if interpret is None else interpret
    bq, bk = fa._eff_blocks(t, None, None, d, segf is not None)
    dof = fa._fold(do)

    from horovod_tpu.parallel._vma import pin_to, vma_of
    _pin = pin_to(vma_of(qf) | vma_of(kf) | vma_of(vf) | {axis_name})

    use_kernel = _ring_use_kernel(interpret, interp)   # see forward
    bwd_parts = fa._bwd_parts if use_kernel else _lax_bwd_parts

    # Diagonal step with the causal kernels and GLOBAL m/l rows.
    dq0, dk0, dv0 = bwd_parts(qf, kf, vf, of, dof, m, l, segf, segf,
                              h, causal, scale_, bq, bk, interp)
    dq_acc = _pin(dq0.astype(jnp.float32))
    perm = [(i, (i + 1) % size) for i in range(size)]
    k_rot = lax.ppermute(kf, axis_name, perm)
    v_rot = lax.ppermute(vf, axis_name, perm)
    dk_rot = _pin(lax.ppermute(dk0.astype(jnp.float32), axis_name, perm))
    dv_rot = _pin(lax.ppermute(dv0.astype(jnp.float32), axis_name, perm))
    kseg_rot = (lax.ppermute(segf, axis_name, perm)
                if segf is not None else None)

    def step(carry, s):
        if segf is None:
            dq_acc, dk_rot, dv_rot, k_rot, v_rot = carry
            kseg = None
        else:
            dq_acc, dk_rot, dv_rot, k_rot, v_rot, kseg = carry
        dq_b, dk_b, dv_b = bwd_parts(qf, k_rot, v_rot, of, dof, m, l,
                                     segf, kseg, h, False, scale_,
                                     bq, bk, interp)
        if causal:
            vis = (s <= idx)
            z = lambda g: jnp.where(vis, g.astype(jnp.float32), 0.0)
        else:
            z = lambda g: g.astype(jnp.float32)
        dq_acc = dq_acc + z(dq_b)
        dk_rot = dk_rot + z(dk_b)
        dv_rot = dv_rot + z(dv_b)
        k_rot = lax.ppermute(k_rot, axis_name, perm)
        v_rot = lax.ppermute(v_rot, axis_name, perm)
        dk_rot = lax.ppermute(dk_rot, axis_name, perm)
        dv_rot = lax.ppermute(dv_rot, axis_name, perm)
        if segf is None:
            return (dq_acc, dk_rot, dv_rot, k_rot, v_rot), None
        kseg = lax.ppermute(kseg, axis_name, perm)
        return (dq_acc, dk_rot, dv_rot, k_rot, v_rot, kseg), None

    init = ((dq_acc, dk_rot, dv_rot, k_rot, v_rot) if segf is None
            else (dq_acc, dk_rot, dv_rot, k_rot, v_rot, kseg_rot))
    out = lax.scan(step, init, jnp.arange(1, size))[0]
    dq_acc, dk_fin, dv_fin = out[0], out[1], out[2]
    dq = fa._unfold(dq_acc.astype(qf.dtype), b, h)
    dk = fa._unfold(dk_fin.astype(kf.dtype), b, h)
    dv = fa._unfold(dv_fin.astype(vf.dtype), b, h)
    import numpy as np
    dseg = (np.zeros((b, t), jax.dtypes.float0)
            if segf is not None else None)
    return dq, dk, dv, dseg


ring_flash_attention.defvjp(_ring_flash_fwd, _ring_flash_bwd)


@jax.named_scope(scopes.ATTN_ULYSSES)
def ulysses_attention(q, k, v, axis_name: str = "seq", causal: bool = True,
                      scale: Optional[float] = None, segment_ids=None,
                      use_flash: Optional[bool] = None):
    """DeepSpeed-Ulysses: all-to-all from sequence-sharded to head-sharded,
    full local attention, all-to-all back.  Heads must divide axis size.

    q/k/v: [B, T_local, H, D] -> returns [B, T_local, H, D].

    ``segment_ids`` ([B, T_local], this shard's slice) enables sequence
    packing: after the all-to-all each device holds the FULL sequence for
    its head subset, so the ids are all-gathered over the seq axis once
    (tiny: int32 per token) and applied as a dense segment-equality mask.

    ``use_flash``: the post-all-to-all attention is plain single-device
    attention over the FULL T_global, so the Pallas flash kernel applies
    directly — same exact math, O(block) instead of O(T_global²) score
    memory (r4).  ``None`` auto-selects it on a compiled TPU backend
    when T_global divides the kernel blocks; the lax route remains the
    CPU/oracle path (interpret-mode kernels need ``check_vma=False``,
    see :func:`_ring_use_kernel`).
    """
    size = lax.axis_size(axis_name)
    b, t, h, d = q.shape
    if h % size != 0:
        raise ValueError(f"heads ({h}) must be divisible by axis size "
                         f"({size}) for Ulysses attention")

    def scatter_heads(x):
        # [B, T_local, H, D] -> [B, T_global, H_local, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def gather_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qg, kg, vg = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    scale_ = (d ** -0.5) if scale is None else scale
    tg_ = qg.shape[1]
    # The kernel's own interpret default keys on the host's default
    # backend; answer it here from the EXECUTING mesh instead so a host
    # whose default backend disagrees with the mesh can neither select
    # the compiled TPU kernel for a CPU mesh (explicit use_flash=True)
    # nor flip into the interpreter-debug surface mid-gate (auto path).
    # HOROVOD_FLASH_INTERPRET=1 still wins inside _interp_default_for.
    flash_interpret = _interp_default_for(qg)
    if use_flash is None:
        import os
        on_tpu = _exec_on_tpu(qg)
        # Auto mirrors the model-level flash gate: COMPILED kernel only
        # (HOROVOD_FLASH_INTERPRET=1 means the interpreter-debug
        # surface, which needs check_vma=False — explicit use_flash
        # there), 128-divisible T_global, and above the measured
        # flash-vs-lax crossover (HOROVOD_FLASH_AUTO_MIN_T, same knob
        # as attention="auto").
        min_t = int(os.environ.get("HOROVOD_FLASH_AUTO_MIN_T", "1024"))
        use_flash = (on_tpu and
                     os.environ.get("HOROVOD_FLASH_INTERPRET") != "1" and
                     tg_ % 128 == 0 and tg_ >= min_t)
    if use_flash:
        from horovod_tpu.ops.flash_attention import flash_attention
        seg_g = (lax.all_gather(segment_ids, axis_name, axis=1,
                                tiled=True)
                 if segment_ids is not None else None)
        out = flash_attention(qg, kg, vg, causal, scale_,
                              interpret=flash_interpret,
                              segment_ids=seg_g)
        return gather_heads(out)
    s = jnp.einsum("bqhd,bkhd->bhqk", qg, kg) * scale_
    tg = qg.shape[1]
    allowed = None
    if causal:
        allowed = jnp.tril(jnp.ones((tg, tg), bool))[None, None]
    if segment_ids is not None:
        seg_g = lax.all_gather(segment_ids, axis_name, axis=1, tiled=True)
        seg_ok = seg_g[:, None, :, None] == seg_g[:, None, None, :]
        allowed = seg_ok if allowed is None else (allowed & seg_ok)
    if allowed is not None:
        s = jnp.where(allowed, s, -jnp.inf)
    if segment_ids is not None:
        # Pre-softmax guard for fully-masked rows: zeros with zero
        # gradients (see local_attention).
        row_valid = allowed.any(axis=-1, keepdims=True)
        s = jnp.where(row_valid, s, 0.0)
        p = jnp.where(row_valid, jax.nn.softmax(s, axis=-1), 0.0)
    else:
        p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vg)
    return gather_heads(out)


@jax.named_scope(scopes.ATTN_LOCAL)
def local_attention(q, k, v, causal=True,
                    scale: Optional[float] = None, segment_ids=None):
    """Plain single-device attention (the no-SP reference path; also the
    numerical oracle the SP tests compare against).

    ``causal``: a bool, or a mask's description as the flash kernels take
    it (``ops.flash_attention.as_mask``), applied dense.  ``segment_ids``
    ([B, T] int32) enables sequence packing: tokens attend only within
    their own segment (composes with ``causal``).
    """
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    t = q.shape[1]
    allowed = None
    from horovod_tpu.ops import flash_attention as fa
    mask = fa.as_mask(causal)
    if mask is fa.CAUSAL:
        allowed = jnp.tril(jnp.ones((t, t), bool))[None, None]
    elif mask is not fa.FULL:
        pos = jnp.arange(t)
        allowed = mask.visible(pos[:, None], pos[None, :])[None, None]
    if segment_ids is not None:
        seg_ok = (segment_ids[:, None, :, None] ==
                  segment_ids[:, None, None, :])
        allowed = seg_ok if allowed is None else (allowed & seg_ok)
    if allowed is not None:
        s = jnp.where(allowed, s, -jnp.inf)
    if segment_ids is not None:
        # Fully-masked rows (possible only with exotic segment layouts
        # under causal=False) must yield zeros with zero GRADIENTS: guard
        # BEFORE the softmax (softmax of an all -inf row is NaN in both
        # forward and backward; a post-hoc isnan patch fixes only the
        # forward), matching the flash kernel's l==0 denominator handling.
        row_valid = allowed.any(axis=-1, keepdims=True)   # [B,1,T,1]
        s = jnp.where(row_valid, s, 0.0)
        p = jnp.where(row_valid, jax.nn.softmax(s, axis=-1), 0.0)
    else:
        p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
