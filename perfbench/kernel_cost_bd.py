"""Operations and bytes the flash kernels need under the block-diffusion
mask, from its shapes: the numerator of ``bd_flash_roofline``.

They count what the objective requires, not what an implementation
executes: a kernel that computes a whole tile to keep a 4 x 4 block of it,
or the clean copy of a query's own block, gets that part of the share.
"""

from __future__ import annotations


def needed_pairs(length: int, block: int) -> int:
    """(query, key) pairs one head needs for a clean sequence of ``length``
    tokens in blocks of ``block`` beside its noised copy, ``K = length /
    block`` blocks: clean x clean, block-causal, ``block ** 2 * K (K + 1)
    / 2``; noised x clean, the finished blocks only, ``block ** 2 * K (K -
    1) / 2``; noised x noised, a block with itself, ``block ** 2 * K``;
    clean x noised, none.  ``length ** 2 + length * block``, a quarter of
    the ``(2 * length) ** 2`` a maskless call over both halves computes."""
    if length % block:
        raise ValueError(f"blocks of {block} do not tile {length}")
    k = length // block
    clean = block * block * k * (k + 1) // 2
    finished = block * block * k * (k - 1) // 2
    own = block * block * k
    return clean + finished + own


def block_diffusion_attention_train(batch: int, heads: int, length: int,
                                    block: int, head_dim: int,
                                    bytes_per_elem: int = 2) -> dict:
    """Flash attention forward + backward under the block-diffusion mask,
    for ``batch * heads`` independent [2 * length, head_dim] problems.

    FLOPs, ``kernel_cost.causal_attention_train``'s convention: a needed
    score element costs 2 matmul terms of ``2 * head_dim`` FLOPs forward
    (QK^T, PV) and 5 backward (recompute QK^T, dV, dP, dQ, dK); the count
    of elements is :func:`needed_pairs`, exact to the element.

    Bytes: forward reads q, k, v and writes o and the two float32 row
    statistics over the ``2 * length`` rows; backward reads q, k, v, o, dO
    and the statistics and writes dQ, dK, dV.  Each tensor moves once, the
    least any blocking can do, one key-value head a query head as the
    kernels are handed them (the group's repeat is the glue's)."""
    bh, rows = batch * heads, 2 * length
    pairs = needed_pairs(length, block)
    tensor = bh * rows * head_dim * bytes_per_elem
    stats = 2 * bh * rows * 4
    return {"flops": float(bh * pairs * (2 + 5) * (2 * head_dim)),
            "bytes": float(4 * tensor + stats + 8 * tensor + stats)}
