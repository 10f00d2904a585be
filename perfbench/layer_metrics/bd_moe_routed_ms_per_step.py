"""Milliseconds per step in the routed parts of the expert layers of a
block-diffusion step: the router over every expert, the choice of this
chip's rows, sort and row moves, the grouped matmuls and SwiGLU over the
held experts, the weighted sum back to the positions, every phase, on one
device."""

from perfbench import bd_reduce


def read(ctx):
    return bd_reduce.part_ms(ctx, bd_reduce.ROUTED_PARTS)
