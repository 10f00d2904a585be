"""Milliseconds per step around the route of the sparse-attention layers
that is not the indexer's: under ``attn/qkv`` the norm, q, k and v, the
per-head norm and the rotation, and under ``attn/out`` the out projection
of ``heads x head width`` and the residual add; every phase, on one
device."""

from perfbench import dsa_reduce


def read(ctx):
    around = dsa_reduce.scope_ms(ctx, dsa_reduce.PROJECTION_SCOPES)
    if around is None:
        return None
    return around - dsa_reduce.part_ms(ctx, (dsa_reduce.INDEX_PROJ,))
