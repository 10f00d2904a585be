"""Collective operations executed per step on one device."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.collective_per_step(ctx, "collective_calls")
