"""Milliseconds per step in the attention halves of a model whose
attention is latent (MLA): the norm, both latents' down- and
up-projections and their norms, the rotary part, the relayouts, the three
flash kernels, the out projection and the residual add, in the main stack
and the prediction module, every phase, on one device."""

from perfbench import mla_reduce


def read(ctx):
    return mla_reduce.scope_ms(ctx, mla_reduce.ATTENTION_SCOPES)
