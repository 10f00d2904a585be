"""Milliseconds per step under ``mlp`` in a block-diffusion step: the
softmax-routed expert layers whole (norm, router over every expert, this
chip's share of the rows of both halves, the held experts, the weighted
sum, the residual add), every phase, on one device."""

from perfbench import bd_reduce


def read(ctx):
    return bd_reduce.scope_ms(ctx, ("mlp",))
