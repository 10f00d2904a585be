"""Milliseconds per step in the routed half of the latent mixture of
experts: the router over every expert, the choice of this chip's rows,
sort and gathers, the grouped matmuls and ``relu^2`` over the held
experts, the gather back and the weighted sum, every phase, on one
device."""

from perfbench import ssm_reduce


def read(ctx):
    return ssm_reduce.part_ms(ctx, ssm_reduce.ROUTED_PARTS)
