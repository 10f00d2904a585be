"""Milliseconds per step under ``diffusion_assemble``: laying the noised
copy beside the clean sequence, the repeated positions and the loss's
weights, every phase, on one device.  It should read about 0 (a few
integer ops over 2 L ids) and guards that."""

from perfbench import bd_reduce


def read(ctx):
    return bd_reduce.part_ms(ctx, (bd_reduce.ASSEMBLE,))
