"""Milliseconds per step under ``attn/flash_attention`` less its three
kernels in a block-diffusion step: the repeat of each key-value head for
the query heads that read it, the sum of dK and dV over them, and the
relayouts between ``[B, T, H, D]`` and the kernels' ``[B*H, T, D]``, every
phase, on one device."""

from perfbench import bd_reduce


def read(ctx):
    total = bd_reduce.scope_ms(ctx, bd_reduce.FLASH_SCOPE)
    kernels = bd_reduce.flash_kernels_ms(ctx)
    if total is None or kernels is None:
        return None
    return total - kernels
