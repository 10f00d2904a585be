"""Milliseconds per step under the scope ``mlp`` of a model whose MLP is a
mixture of experts: router, dispatch, grouped matmuls, combine, the
layer's norm and residual add, every phase, kernels included."""

from perfbench import moe_reduce, scope_reduce


def read(ctx):
    if moe_reduce.for_ctx(ctx) is None:
        return None
    return scope_reduce.scope_ms(ctx, ("mlp",))
