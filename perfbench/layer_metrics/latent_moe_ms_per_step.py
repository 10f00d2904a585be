"""Milliseconds per step under ``mlp`` in a model whose feed-forward
parts are all latent mixtures of experts: norm, router, the latent
projections, dispatch, the grouped matmuls and ``relu^2``, combine, the
shared expert and the residual add, the prediction module's included,
every phase, on one device."""

from perfbench import scope_reduce, ssm_reduce


def read(ctx):
    if ssm_reduce.for_ctx(ctx) is None:
        return None
    return scope_reduce.scope_ms(ctx, ("mlp",))
