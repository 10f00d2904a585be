"""Milliseconds per step in the three flash kernels of the compressed
convolutional attention layers (forward, its recomputation, dQ, dK+dV), on
one device: the denominator of ``cca_flash_roofline``."""

from perfbench import cca_reduce


def read(ctx):
    return cca_reduce.part_ms(ctx, ("flash",)) or None
