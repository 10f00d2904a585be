"""Milliseconds per step under ``attn/flash_attention`` in a model whose
attention is latent: the three flash kernels at the head's own width
(forward, its recomputation, dQ, dK+dV) and the relayouts around them,
every phase, on one device."""

from perfbench import mla_reduce


def read(ctx):
    return mla_reduce.scope_ms(ctx, mla_reduce.FLASH_SCOPE)
