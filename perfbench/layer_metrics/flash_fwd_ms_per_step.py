"""Milliseconds per step in the flash-attention forward kernel: the
custom calls named ``flash_fwd``, summed over the layers, on one device."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "flash_fwd")
