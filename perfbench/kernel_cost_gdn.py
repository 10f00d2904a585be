"""Operations and bytes the gated-delta-rule recurrence of a
linear-attention layer needs, from shapes alone: the numerator of
``gdn_scan_roofline``.

As ``kernel_cost.py`` (which this file leaves as it is): what the
mathematics requires, in its **recurrent** form, never what a chunked
implementation adds (the triangular system, the masked ``Q K^T``, the
block states), so a later kernel that changes its block length does not
move the numerator.  ``kernel_cost.roofline_seconds`` turns the result
into the least time the chip could take.
"""

from __future__ import annotations


def gated_delta_rule_train(tokens: int, heads: int, key_dim: int,
                           value_dim: int, layers: int, recompute: bool,
                           bytes_per_elem: int = 2) -> dict:
    """The recurrence ``S <- alpha S; S <- S + beta k (v - S^T k)^T; o =
    S^T q`` over ``tokens`` tokens and ``heads`` heads with a ``[key_dim,
    value_dim]`` state, forward and backward, in ``layers`` layers.

    FLOPs: a token and head cost three products of ``2 * key_dim *
    value_dim`` forward (``S^T k``, the rank-one update, ``S^T q``) and
    twice that backward; the decay and the gates are not counted (the
    convention of the FLOPs functions here).  Never a recomputed forward:
    the model's FLOPs do not count it either.

    Bytes, each tensor once where a pass needs it at the memory's edge
    (the state stays on the chip): forward reads q, k, v and the two
    float32 gates and writes o; backward reads q, k, v, the gates and
    o's gradient and writes the gradients of q, k, v and of the gates;
    with ``recompute`` (``remat="full"``: ``jax.checkpoint`` of the layer)
    the forward's traffic is paid a second time.  At ``key_dim`` 96 and
    ``value_dim`` 192 the bytes set the bound on a v5e.
    """
    rows = tokens * heads
    qkv = (2 * key_dim + value_dim) * bytes_per_elem
    out = value_dim * bytes_per_elem
    gates = 2 * 4
    forward = qkv + gates + out
    backward = 2 * qkv + 2 * gates + out
    moved = (2 if recompute else 1) * forward + backward
    return {"flops": float(layers * rows * 3 * 6 * key_dim * value_dim),
            "bytes": float(layers * rows * moved)}
