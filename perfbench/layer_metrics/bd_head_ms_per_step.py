"""Milliseconds per step under ``head`` (the final norm and the untied
head's matmul over the noised half alone) and ``loss`` (the weighted
masked-token sum), every phase, on one device."""

from perfbench import bd_reduce


def read(ctx):
    return bd_reduce.scope_ms(ctx, ("head", "loss"))
