"""Milliseconds per step under ``attn/mamba_scan``: the selective scan
alone (the two Pallas kernels, and the relayout of their operands where
XLA did not fuse it into a neighbour), every phase, on one device.  Read
by scope, not by instruction name."""

from perfbench import mamba1_reduce


def read(ctx):
    return mamba1_reduce.part_ms(ctx, (mamba1_reduce.SCAN,))
