"""Milliseconds per step in the dense half of the latent mixture of
experts: the shared expert and the projections into and out of the
experts' latent width, every phase, on one device."""

from perfbench import ssm_reduce


def read(ctx):
    return ssm_reduce.part_ms(ctx, ssm_reduce.SHARED_PARTS)
