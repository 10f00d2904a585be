"""Milliseconds per step under ``attn/flash_attention`` in a model whose
attention layer is multi-query (ONE key-value head): the flash kernels
(forward, its recomputation, dQ, dK+dV) and the glue around them, which
holds the repeat of the one key-value head for every query head and the
sum of dK and dV over them, every phase, on one device."""

from perfbench import mamba1_reduce, scope_reduce


def read(ctx):
    if mamba1_reduce.for_ctx(ctx) is None:
        return None
    return scope_reduce.scope_ms(ctx, ("attn/flash_attention",))
