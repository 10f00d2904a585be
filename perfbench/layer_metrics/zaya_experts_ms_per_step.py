"""Milliseconds per step in the expert layers outside their router and
their merge: the second norm, the sort and the row moves, the grouped
matmuls and SwiGLU over the held experts, the weighting, the skip's
term, every phase, on one device."""

from perfbench import cca_reduce


def read(ctx):
    return cca_reduce.part_ms(ctx, ("experts",))
