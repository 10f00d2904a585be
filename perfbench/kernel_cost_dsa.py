"""Operations and bytes that learned sparse attention needs, from shapes
alone: the numerators of ``dsa_flash_roofline`` and
``dsa_indexer_roofline``.

As ``kernel_cost.py`` (which this file leaves as it is): what the
mathematics requires, not what an implementation masks, visits or
recomputes.  A query reads ``min(t + 1, topk)`` keys, so a kernel that
computes a whole tile to keep a tenth of it gets a tenth of the share.
``kernel_cost.roofline_seconds`` turns the result into the least time the
chip could take.
"""

from __future__ import annotations


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs one sequence selects: ``sum_t min(t + 1,
    topk)``."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


def sparse_attention_train(batch: int, heads: int, kv_heads: int, seq: int,
                           head_dim: int, topk: int,
                           bytes_per_elem: int = 2) -> dict:
    """Attention over the selected keys, forward and backward, ``heads``
    query heads over ``kv_heads`` key-value heads.

    FLOPs: ``kernel_cost.causal_attention_train``'s terms (2 forward: QK^T,
    PV; 5 backward: QK^T again, dV, dP, dQ, dK; ``2 * head_dim`` each) a
    **selected** pair a query head.  Bytes: q, o, dO and dQ a query head;
    k, v, dK and dV a key-value head, each read or written once forward
    and once backward as there; one float32 row statistic a query head
    each way; and the selection, 4 bytes a selected pair (an index),
    read once forward and once backward."""
    pairs = batch * selected_pairs(seq, topk)
    flops = heads * pairs * 7 * (2 * head_dim)
    q_like = batch * heads * seq * head_dim * bytes_per_elem
    kv_like = batch * kv_heads * seq * head_dim * bytes_per_elem
    stats = batch * heads * seq * 4
    fwd = 2 * q_like + 2 * kv_like + stats + 4 * pairs
    bwd = 4 * q_like + 4 * kv_like + stats + 4 * pairs
    return {"flops": float(flops), "bytes": float(fwd + bwd)}


def indexer_scores_train(batch: int, index_heads: int, index_dim: int,
                         seq: int, topk: int,
                         bytes_per_elem: int = 2) -> dict:
    """The indexer's scores, forward over every causal pair (a score has
    to exist before it can lose) and backward over the selected pairs (the
    loss reads no other).

    FLOPs: ``2 * index_dim`` a pair an indexer head forward (qI . kI; the
    relu, the weight and the sum over heads are not counted, as softmax's
    exponentials are not), three such terms backward (the product again
    for the relu's mask, dqI, dkI).  Bytes: qI, kI and w read and the
    float32 scores written forward; qI, kI, w and the scores' gradient on
    the selected pairs read, and the three gradients written,
    backward."""
    causal = batch * seq * (seq + 1) // 2
    pairs = batch * selected_pairs(seq, topk)
    width = index_heads * index_dim
    flops = 2 * width * causal + 3 * 2 * width * pairs
    operands = batch * seq * (width * bytes_per_elem
                              + index_dim * bytes_per_elem + index_heads * 4)
    return {"flops": float(flops),
            "bytes": float(operands + 4 * causal + 2 * operands + 4 * pairs)}
