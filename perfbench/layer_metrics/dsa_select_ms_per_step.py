"""Milliseconds per step in the selection (``dsa_select``): each query's
``topk``-th largest score by bisection, the mask and its transpose, every
phase (the forward pass and its recomputation), on one device."""

from perfbench import dsa_reduce


def read(ctx):
    return dsa_reduce.part_ms(ctx, (dsa_reduce.SELECT,))
