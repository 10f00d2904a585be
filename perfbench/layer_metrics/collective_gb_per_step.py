"""Gigabytes (1e9) of collective results per step on one device, from the
result shapes and dtypes of the collective events."""

from perfbench import scope_reduce


def read(ctx):
    total = scope_reduce.collective_per_step(ctx, "collective_bytes")
    return None if total is None else total / 1e9
