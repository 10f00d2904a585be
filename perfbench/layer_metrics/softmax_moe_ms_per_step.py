"""Milliseconds per step under ``mlp`` in a sparse-attention model: the
softmax-routed expert layers whole (norm, router over every expert, this
chip's share of the rows, the held experts, the weighted sum, the residual
add), every phase, on one device."""

from perfbench import dsa_reduce


def read(ctx):
    return dsa_reduce.scope_ms(ctx, ("mlp",))
