"""Milliseconds per step under the loop's scope and under no model scope:
the stacked saves, the carry's copies and the adds that sum a shared leaf's
gradient over the passes, every phase, on one device."""

from perfbench import loop_reduce


def read(ctx):
    return loop_reduce.part_ms(ctx, ("carry",))
