"""Milliseconds per step in the routed half of the sigmoid-routed expert
layers: the router over every expert, the choice of this chip's rows,
sort and row moves, the grouped matmuls and SwiGLU over the held experts,
the weighted sum back to the tokens, every phase, on one device."""

from perfbench import mla_reduce


def read(ctx):
    return mla_reduce.part_ms(ctx, mla_reduce.ROUTED_PARTS)
