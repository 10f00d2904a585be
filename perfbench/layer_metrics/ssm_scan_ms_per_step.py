"""Milliseconds per step under ``attn/ssm_scan``: the Mamba-2 recurrence
alone (the masked ``C B^T`` products of a chunk, the chunk states, the
scan that carries the state, the carried states' part of the output),
every phase, on one device.  Read by scope, not by instruction name."""

from perfbench import ssm_reduce


def read(ctx):
    return ssm_reduce.part_ms(ctx, ("ssm_scan",))
