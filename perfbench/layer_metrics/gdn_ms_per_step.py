"""Milliseconds per step in the linear-attention layers' mixers: norm,
projections, gates, the causal convolution, the gated-delta-rule
recurrence, the gated per-head norm and the out projection, every phase
(forward, backward, recomputation), on one device.  Their MLPs are not
part of it."""

from perfbench import gdn_reduce


def read(ctx):
    return gdn_reduce.part_ms(ctx)
