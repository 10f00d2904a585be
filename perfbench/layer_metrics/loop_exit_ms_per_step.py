"""Milliseconds per step in ``exit_gate`` and ``exit_mix`` alone: the
gate's pre-activation, the exit distribution, the weighted sum and the
entropy.  Should read near 0, and guards that."""

from perfbench import loop_reduce


def read(ctx):
    return loop_reduce.part_ms(ctx, ("exit",))
