"""Milliseconds per step in the flash-attention dK/dV kernel: the
custom calls named ``flash_bwd_dkv``, summed over the layers, on one device."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "flash_bwd_dkv")
