"""Milliseconds per step under ``cca_mix`` and ``cca_norm_rope`` (both
causal convolutions, the mean, the value shift, the L2 norm, the
temperature, the half rotation) and in the head layouts around the flash
kernels (K and V repeated for the group), every phase, on one device."""

from perfbench import cca_reduce


def read(ctx):
    return cca_reduce.part_ms(ctx, ("mix",))
