"""Milliseconds per step in the Mamba-2 mixers: norm, in-projection, the
step and the decay, the causal convolution, the state-space recurrence,
the gated group norm and the out projection, every phase (forward,
backward, recomputation), on one device."""

from perfbench import ssm_reduce


def read(ctx):
    return ssm_reduce.part_ms(ctx, ssm_reduce.SSM_PARTS)
