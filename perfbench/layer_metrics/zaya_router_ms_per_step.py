"""Milliseconds per step under ``mlp/moe_router`` of the layers with the
MLP router: the down projection and the carried state, the norm and the
MLP, softmax, the choice, the share's bookkeeping, every phase, on one
device."""

from perfbench import cca_reduce


def read(ctx):
    return cca_reduce.part_ms(ctx, ("router",))
