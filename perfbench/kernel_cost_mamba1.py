"""Operations and bytes the Mamba-1 selective scan needs, from shapes
alone: the numerator of ``mamba1_scan_roofline``.

As ``kernel_cost_ssm.py`` (which this file leaves as it is): what the
mathematics requires in its recurrent form, never what an implementation
adds (tiles, saved states, the backward's recomputation of a tile), so no
tile length appears here and a later kernel that changes its tile does not
move the numerator.  ``kernel_cost.roofline_seconds`` turns the result
into the least time the chip could take.

**What the share cannot say.**  The scan is elementwise: its work is done
by the vector unit (a multiply or an add a cycle and lane, one ``exp`` a
state element), for which ``peaks.json`` has no row.  Against the matrix
unit's peak the FLOPs below are a fraction of a millisecond, so the bytes
set the bound, and the share reads near 10% for a kernel whose vector
unit is full.  It is a roofline share by the guide's definition (FLOPs
over the chip's peak, bytes over the memory's), not a measure of how well
the kernel uses the unit it runs on.
"""

from __future__ import annotations


def selective_scan_train(tokens: int, channels: int, state: int, layers: int,
                         recompute: bool, bytes_per_elem: int = 2) -> dict:
    """The recurrence ``h <- exp(delta A) h + delta x B^T; y = h C`` over
    ``tokens`` tokens and ``channels`` channels with a state of ``state``
    a channel, ``B`` and ``C`` shared by all channels, forward and
    backward, in ``layers`` layers.

    FLOPs, by ``kernel_cost_ssm``'s convention: a token and channel cost
    two products of ``2 * state`` forward (the rank-one update, ``h C``)
    and twice that backward; the decay (an ``exp`` and a multiply a state
    element), ``delta x`` and the ``D x`` skip are not counted.  Never a
    recomputed forward: the model's FLOPs do not count it either.

    Bytes, each tensor once where a pass needs it at the memory's edge
    (the state stays on the chip): forward reads x (the model dtype),
    delta (float32), B and C (float32, ``state`` each) and writes y
    (float32); backward reads x, delta, B, C and y's gradient and writes
    the gradients of x, delta, B and C; A, D and their gradients are
    ``channels x state`` a layer and not counted; with ``recompute``
    (``remat="full"``: ``jax.checkpoint`` of the part) the forward's
    traffic is paid a second time.
    """
    x, wide = channels * bytes_per_elem, channels * 4
    bc = 2 * state * 4
    forward = x + wide + bc + wide
    backward = (x + wide + bc + wide) + (x + wide + bc)
    moved = (2 if recompute else 1) * forward + backward
    return {"flops": float(layers * tokens * channels * 3 * 4 * state),
            "bytes": float(layers * tokens * moved)}
