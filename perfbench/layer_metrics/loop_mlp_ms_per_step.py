"""Milliseconds per step under ``mlp`` in a looped stack (the first norm,
the three matmuls, ``silu *`` and the residual add; the sandwich's second
norm is ``loop_norm_ms_per_step``'s), all passes, every phase, on one
device."""

from perfbench import loop_reduce


def read(ctx):
    return loop_reduce.part_ms(ctx, ("mlp",))
