"""Operations and bytes the Pallas kernels of a ``cca_moe_lm`` step need,
from shapes alone: the numerators of ``cca_flash_roofline`` and
``zaya_experts_roofline``.

As ``kernel_cost.py`` (which this file leaves as it is): what the
mathematics requires, not what an implementation pads, masks, repeats for
a group of query heads or recomputes.
"""

from __future__ import annotations

from perfbench import kernel_cost_moe


def grouped_causal_attention_train(batch: int, heads: int, kv_heads: int,
                                   seq: int, head_dim: int, layers: int,
                                   bytes_per_elem: int = 2) -> dict:
    """Flash attention forward + backward under a causal mask with
    ``heads`` query heads on ``kv_heads`` key-value heads, ``layers``
    times.  FLOPs as ``kernel_cost.causal_attention_train``: 2 matmul
    terms of ``2 * head_dim`` forward and 5 backward for each of the ``seq
    (seq + 1) / 2`` causal pairs a query head.  Bytes, each tensor once:
    q, o, dO, dQ a query head and k, v, dK, dV a **key-value** head (a
    kernel handed K and V repeated a query head reads more than it needs,
    which lowers its share), the forward reading q, k, v and writing o and
    two float32 statistics a row, the backward reading q, k, v, o, dO and
    the statistics and writing dQ, dK, dV."""
    causal = seq * (seq + 1) // 2
    flops = batch * heads * causal * 7 * (2 * head_dim)
    q_tensor = batch * heads * seq * head_dim * bytes_per_elem
    kv_tensor = batch * kv_heads * seq * head_dim * bytes_per_elem
    stats = 2 * batch * heads * seq * 4
    moved = (2 * q_tensor + 2 * kv_tensor + stats          # forward
             + 4 * q_tensor + 4 * kv_tensor + stats)       # backward
    return {"flops": float(layers * flops), "bytes": float(layers * moved)}


def one_of_seventeen_experts_train(tokens: int, held: int, choices: int,
                                   d_model: int, d_expert: int,
                                   layers: int) -> dict:
    """The three matmuls of the SwiGLU expert a token chooses, forward and
    backward, for the rows a uniform router sends to the ``held`` experts
    of this chip out of ``choices`` (the experts and the skip): ``tokens *
    held / choices`` rows a layer, ``kernel_cost_moe.expert_matmuls_train``
    of them.  The skip multiplies no matrix."""
    return kernel_cost_moe.expert_matmuls_train(
        tokens * held // choices, d_model, d_expert, held, layers)
