"""Milliseconds per step in the Mamba-1 mixers: norm and in-projection,
the causal convolution, x_proj with the three inner norms, dt_proj and
``softplus``, the selective scan, the gate and the out projection, every
phase (forward, backward, recomputation), on one device."""

from perfbench import mamba1_reduce


def read(ctx):
    return mamba1_reduce.part_ms(ctx, mamba1_reduce.PARTS)
