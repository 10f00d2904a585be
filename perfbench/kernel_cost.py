"""Operations and bytes a kernel's algorithm needs, from its shapes.

These are the numerators of every ``<kernel>_roofline``.  They count what
the mathematics requires, not what an implementation happens to execute:
a kernel that recomputes, or visits masked blocks, gets a lower share.
"""

from __future__ import annotations


def causal_attention_train(batch: int, heads: int, seq: int, head_dim: int,
                           bytes_per_elem: int = 2) -> dict:
    """Flash attention forward + backward under a causal mask, for
    ``batch * heads`` independent [seq, head_dim] problems.

    FLOPs: a score element (q_i, k_j), j <= i, costs 2 matmul terms of
    ``2 * head_dim`` FLOPs forward (QK^T, PV) and 5 backward (recompute
    QK^T, dV, dP, dQ, dK).  There are ``seq * (seq + 1) / 2`` causal
    elements: the count is exact to the element, not to a block size,
    so it does not move when a kernel changes its blocks.  Softmax's
    exponentials and scalings are not counted (the convention of
    ``lm_train_flops``).

    Bytes: forward reads q, k, v and writes o and the two float32 row
    statistics; backward reads q, k, v, o, dO and the statistics and
    writes dQ, dK, dV.  Each tensor moves once: the least any blocking
    can do.
    """
    bh = batch * heads
    causal = seq * (seq + 1) // 2
    flops_fwd = bh * causal * 2 * (2 * head_dim)
    flops_bwd = bh * causal * 5 * (2 * head_dim)
    tensor = bh * seq * head_dim * bytes_per_elem
    stats = 2 * bh * seq * 4
    bytes_fwd = 4 * tensor + stats
    bytes_bwd = 8 * tensor + stats
    return {"flops": float(flops_fwd + flops_bwd),
            "bytes": float(bytes_fwd + bytes_bwd)}


def roofline_seconds(cost: dict, peak_flops: float, peak_bytes: float):
    """``(seconds, bound)``: the least time the chip could take, and which
    of ``"compute"`` or ``"memory"`` sets it."""
    t_flops = cost["flops"] / peak_flops
    t_bytes = cost["bytes"] / peak_bytes
    return ((t_flops, "compute") if t_flops >= t_bytes
            else (t_bytes, "memory"))
