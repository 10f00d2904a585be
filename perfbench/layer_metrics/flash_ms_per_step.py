"""Milliseconds per step in the flash-attention kernels (forward, dQ,
dK/dV), summed over the layers, on one device."""

from perfbench.layer_metrics import kernel_seconds


def read(ctx):
    seconds = kernel_seconds(ctx, "flash")
    return None if seconds is None else seconds * 1e3 / ctx["trace_steps"]
