"""Milliseconds per step in the routed parts of the softmax-routed expert
layers of a sparse-attention model: the router over every expert, the
choice of this chip's rows, sort and row moves, the grouped matmuls and
SwiGLU over the held experts, the weighted sum back to the tokens, every
phase, on one device."""

from perfbench import dsa_reduce


def read(ctx):
    return dsa_reduce.part_ms(ctx, dsa_reduce.ROUTED_PARTS)
