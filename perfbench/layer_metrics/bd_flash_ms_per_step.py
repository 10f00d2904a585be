"""Milliseconds per step in the three flash kernels under the
block-diffusion mask (forward, its recomputation, dQ, dK+dV), summed over
the layers, on one device: the denominator of ``bd_flash_roofline``."""

from perfbench import bd_reduce


def read(ctx):
    return bd_reduce.flash_kernels_ms(ctx)
