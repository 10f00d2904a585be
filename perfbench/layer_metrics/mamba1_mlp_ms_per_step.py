"""Milliseconds per step under ``mlp`` in a model whose layers are
Mamba-1 mixers and one attention layer, each followed by the dense SwiGLU
MLP: norm, the three matmuls and the residual add of every layer's MLP,
every phase, on one device."""

from perfbench import mamba1_reduce, scope_reduce


def read(ctx):
    if mamba1_reduce.for_ctx(ctx) is None:
        return None
    return scope_reduce.scope_ms(ctx, ("mlp",))
