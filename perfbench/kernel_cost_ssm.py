"""Operations and bytes the Mamba-2 state-space recurrence needs, from
shapes alone: the numerator of ``ssm_scan_roofline``.

As ``kernel_cost.py`` (which this file leaves as it is): what the
mathematics requires, in its **recurrent** form, never what a chunked
implementation adds (the masked ``C B^T`` products, the chunk states), so
no chunk length appears here and a later kernel that changes its chunk
does not move the numerator.  ``kernel_cost.roofline_seconds`` turns the
result into the least time the chip could take.
"""

from __future__ import annotations


def state_space_train(tokens: int, heads: int, head_dim: int, state: int,
                      groups: int, layers: int, recompute: bool,
                      bytes_per_elem: int = 2) -> dict:
    """The recurrence ``S <- a S + delta x B^T; y = S C`` over ``tokens``
    tokens and ``heads`` heads with a ``[head_dim, state]`` state, ``B``
    and ``C`` shared by the heads of each of ``groups`` groups, forward
    and backward, in ``layers`` layers.

    FLOPs: a token and head cost two products of ``2 * head_dim * state``
    forward (the rank-one update, ``S C``) and twice that backward; the
    decay, ``delta x`` and the ``D x`` skip are not counted (the
    convention of the FLOPs functions here).  Never a recomputed forward:
    the model's FLOPs do not count it either.

    Bytes, each tensor once where a pass needs it at the memory's edge
    (the state stays on the chip): forward reads x, B, C and the two
    float32 scalars a head (``delta``, ``a``) and writes y; backward
    reads x, B, C, the scalars and y's gradient and writes the gradients
    of x, B, C and of the scalars; with ``recompute`` (``remat="full"``:
    ``jax.checkpoint`` of the layer) the forward's traffic is paid a
    second time.  At ``head_dim`` 64 and ``state`` 128 the bytes set the
    bound on a v5e.
    """
    x = heads * head_dim * bytes_per_elem
    bc = 2 * groups * state * bytes_per_elem
    scalars = 2 * heads * 4
    forward = x + bc + scalars + x
    backward = 2 * (x + bc) + 2 * scalars + x
    moved = (2 if recompute else 1) * forward + backward
    return {"flops": float(layers * tokens * heads * 3 * 4 * head_dim
                           * state),
            "bytes": float(layers * tokens * moved)}
