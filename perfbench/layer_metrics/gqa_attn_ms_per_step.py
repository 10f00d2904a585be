"""Milliseconds per step under ``attn/flash_attention`` in a model whose
attention layers are grouped-query: the flash kernels (forward, its
recomputation, dQ, dK+dV) and the glue around them, which holds the
repeat of each key-value head for its query heads and the sum of dK and
dV over them, in the main stack and the prediction module, every phase."""

from perfbench import scope_reduce, ssm_reduce


def read(ctx):
    if ssm_reduce.for_ctx(ctx) is None:
        return None
    return scope_reduce.scope_ms(ctx, ("attn/flash_attention",))
