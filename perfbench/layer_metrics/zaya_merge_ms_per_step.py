"""Milliseconds per step under ``res_scale``, the scaled residual merge
of both sub-layers of every layer, every phase, on one device: should read
near a hundredth of the step, and guards that."""

from perfbench import cca_reduce


def read(ctx):
    return cca_reduce.part_ms(ctx, ("merge",))
