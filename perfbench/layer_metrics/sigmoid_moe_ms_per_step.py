"""Milliseconds per step in the expert layers' feed-forward halves of a
model with a leading dense layer: everything under ``mlp`` but the dense
MLP: norm, the sigmoid router, dispatch, the grouped matmuls and SwiGLU,
combine, the shared expert and the residual add, the prediction module's
included, every phase, on one device."""

from perfbench import mla_reduce


def read(ctx):
    dense = mla_reduce.part_ms(ctx, (mla_reduce.DENSE,))
    if dense is None:
        return None
    return mla_reduce.scope_ms(ctx, ("mlp",)) - dense
