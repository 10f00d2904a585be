"""Milliseconds per step under the scope ``attn/flash_attention`` less its
three kernels: the relayouts between ``[B, T, H, D]`` and the kernels'
``[B*H, T, D]`` that stay ops of their own."""

from perfbench import scope_reduce


def read(ctx):
    total = scope_reduce.scope_ms(ctx, ("attn/flash_attention",))
    if total is None:
        return None
    kernels = [scope_reduce.kernel_ms(ctx, k)
               for k in scope_reduce.KERNEL_NAMES]
    return total - sum(k for k in kernels if k is not None)
