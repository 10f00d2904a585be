"""Milliseconds per step in the multi-token-prediction module: the two
norms and the combining matrix, its layers, its final norm, the head a
second time and its loss, every phase, on one device."""

from perfbench import ssm_reduce


def read(ctx):
    return ssm_reduce.part_ms(ctx, (ssm_reduce.MTP,))
