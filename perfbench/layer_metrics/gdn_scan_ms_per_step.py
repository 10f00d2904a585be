"""Milliseconds per step under ``attn/gdn_scan``: the gated-delta-rule
recurrence alone (the triangular systems, the scan over blocks, the
outputs), every phase, what ``jax.checkpoint`` recomputes included."""

from perfbench import gdn_reduce


def read(ctx):
    return gdn_reduce.part_ms(ctx, ("gdn_scan",))
