"""Milliseconds per step under ``head`` and ``loss`` in a looped stack:
the readouts after every pass, their cross-entropies, the exit gate and
the mixture, every phase, on one device."""

from perfbench import loop_reduce


def read(ctx):
    return loop_reduce.part_ms(ctx, loop_reduce.HEAD)
