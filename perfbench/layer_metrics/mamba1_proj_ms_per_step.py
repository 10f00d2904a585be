"""Milliseconds per step in the Mamba-1 mixers' projections with their
norms: the layer's norm and in-projection (``mamba_proj``), x_proj, the
dt/B/C norms, dt_proj and ``softplus`` (``mamba_dt_bc``), the out
projection and the residual add (``mamba_out``), every phase, on one
device."""

from perfbench import mamba1_reduce


def read(ctx):
    return mamba1_reduce.part_ms(ctx, mamba1_reduce.PROJ_PARTS)
