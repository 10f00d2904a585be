"""GiB occupied at the peak instant of the temporaries by buffers of phase
``fwd``: what the forward saved for the backward, logits included
(``perfbench/memory_reduce.py``; None where the buffer assignment holds no
live ranges)."""

from perfbench import memory_reduce


def read(ctx):
    return memory_reduce.metric(ctx, "hbm_peak_fwd_gib")
