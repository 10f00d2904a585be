"""Milliseconds per step in the ``post_norm`` components (the second norm
of every sandwich-normed branch) and in ``loop_norm`` (the final norm after
every pass, carried into the next): what the sandwich and the carried norm
cost, every phase, on one device."""

from perfbench import loop_reduce


def read(ctx):
    return loop_reduce.part_ms(ctx, ("norm",))
