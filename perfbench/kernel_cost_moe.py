"""Operations and bytes the expert matmuls of a mixture-of-experts layer
need, from shapes alone: the numerator of ``moe_experts_roofline``.

As ``kernel_cost.py`` (which this file leaves as it is): what the
mathematics requires, not what an implementation pads, masks, visits
twice or recomputes.  ``kernel_cost.roofline_seconds`` turns the result
into the least time the chip could take.
"""

from __future__ import annotations


def expert_matmuls_train(assignments: int, d_model: int, d_expert: int,
                         n_experts: int, layers: int,
                         bytes_per_elem: int = 2) -> dict:
    """The three matmuls of every SwiGLU expert (gate, up: ``d_model ->
    d_expert``; down: ``d_expert -> d_model``), forward and backward, for
    ``assignments`` (token, expert) rows per layer (tokens x experts per
    token: nothing is dropped) over ``layers`` layers.

    FLOPs: a row costs ``2 * d_model * d_expert`` per matmul forward and
    twice that backward (the gradient of the rows and of the weights):
    ``3 matmuls x 3 products x 2 * rows * d_model * d_expert`` a layer,
    whatever the split of the rows over the experts.  ``silu`` and the
    elementwise product are not counted (the convention of the FLOPs
    functions here).

    Bytes, each tensor once where a product needs it at the memory's
    edge: every expert's three matrices read forward and again for the
    gradient of the rows (the gradient of the weights does not read
    them), their gradients written once; the gathered rows read forward
    and again for the gradient of gate and up; gate and up written
    forward and read backward; the output written, its gradient read,
    the rows' gradient written.  The activation between up and down, and
    its gradient, can stay on the chip and are not counted.  With 1024
    rows an expert the FLOPs set the bound on a v5e.
    """
    matmul = 2.0 * assignments * d_model * d_expert
    flops = 3 * 3 * matmul
    weights = 3 * n_experts * d_model * d_expert
    moved = (3 * weights
             + assignments * (5 * d_model + 4 * d_expert))
    return {"flops": float(layers * flops),
            "bytes": float(layers * moved * bytes_per_elem)}
