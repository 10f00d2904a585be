"""Milliseconds per step under the compressed-convolutional-attention
part (its five projections and first norm, both convolutions, the mean,
the value shift, the L2 norm and half rotation, the layouts, the three
flash kernels; the scaled merge is ``zaya_merge_ms_per_step``'s), every
phase, on one device."""

from perfbench import cca_reduce


def read(ctx):
    return cca_reduce.part_ms(ctx, cca_reduce.CCA)
