"""Milliseconds per step under ``attn/*`` in a looped stack (the
projections, rotary and layouts, the flash kernels, the first norm and
the residual add; the sandwich's second norm is ``loop_norm_ms_per_step``'s),
all passes, every phase, on one device."""

from perfbench import loop_reduce


def read(ctx):
    return loop_reduce.part_ms(ctx, loop_reduce.ATTN)
