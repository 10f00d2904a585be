"""Milliseconds per step in the attention halves of a block-diffusion
step: everything under ``attn/qkv`` (the norm, q, k and v over both halves,
the per-head norm and rotation), ``attn/flash_attention`` (the kernels
under the block-diffusion mask and the glue around them) and ``attn/out``,
every phase, on one device."""

from perfbench import bd_reduce


def read(ctx):
    return bd_reduce.scope_ms(ctx, bd_reduce.ATTENTION_SCOPES)
