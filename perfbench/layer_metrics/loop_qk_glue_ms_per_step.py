"""Milliseconds per step under ``attn/qkv`` and ``attn/flash_attention``
outside matmuls and kernels in a looped stack: rotary, the head split and
the relayouts between ``[B, T, H, D]`` and the kernels' ``[B*H, T, D]``,
every phase, on one device."""

from perfbench import loop_reduce


def read(ctx):
    return loop_reduce.part_ms(ctx, ("qk_glue",))
