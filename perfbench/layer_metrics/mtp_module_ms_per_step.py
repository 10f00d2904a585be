"""Milliseconds per step in the multi-token-prediction module of a model
with latent attention: the two norms and the combining matrix, its one
latent-attention + expert layer, its final norm, the head a second time
and its loss, every phase, on one device."""

from perfbench import mla_reduce


def read(ctx):
    return mla_reduce.part_ms(ctx, (mla_reduce.MTP,))
