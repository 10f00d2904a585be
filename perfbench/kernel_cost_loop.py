"""Operations and bytes the flash kernels need in a stack run several
times on the same weights: the numerator of ``loop_flash_roofline``.

They count what the model requires, not what an implementation executes:
every pass is needed (the passes differ in their inputs, not in their
weights), a recomputed forward kernel is not.
"""

from __future__ import annotations

from perfbench import kernel_cost


def needed_pairs(seq: int, layers: int, loops: int) -> int:
    """(query, key) pairs one head of one sequence needs in a step: the
    ``seq (seq + 1) / 2`` causal pairs, once a layer and pass."""
    return seq * (seq + 1) // 2 * layers * loops


def looped_causal_attention_train(batch: int, heads: int, seq: int,
                                  head_dim: int, layers: int, loops: int,
                                  bytes_per_elem: int = 2) -> dict:
    """Flash attention forward + backward under a causal mask, ``layers x
    loops`` times for ``batch * heads`` independent [seq, head_dim]
    problems: ``kernel_cost.causal_attention_train``'s FLOPs (2 matmul
    terms of ``2 * head_dim`` forward and 5 backward a needed pair) and
    bytes (every tile of q, k, v, o, dO and the statistics fetched once a
    kernel, dQ, dK, dV and o written once: the least any blocking can
    do), once a layer and pass."""
    once = kernel_cost.causal_attention_train(batch, heads, seq, head_dim,
                                              bytes_per_elem)
    return {name: value * layers * loops for name, value in once.items()}
