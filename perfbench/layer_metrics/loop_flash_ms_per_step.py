"""Milliseconds per step in the three flash kernels of a looped stack
(forward, its recomputation, dQ, dK+dV), summed over the layers and the
passes, on one device: the denominator of ``loop_flash_roofline``."""

from perfbench import loop_reduce


def read(ctx):
    return loop_reduce.part_ms(ctx, ("flash",)) or None
