"""Milliseconds per step in the attention halves of the sparse-attention
layers: everything under ``attn/qkv`` (the norm, q, k and v, the per-head
norm and rotation, the indexer's projections), ``attn/flash_attention``
(the indexer's scores, the selection, the attention kernels under the mask,
the indexer's loss) and ``attn/out``, every phase, on one device."""

from perfbench import dsa_reduce


def read(ctx):
    return dsa_reduce.scope_ms(ctx, dsa_reduce.ATTENTION_SCOPES)
