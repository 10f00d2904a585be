"""Milliseconds per step of backward device time on one device: ops under
``transpose(``, the backward kernels included, and the weight-gradient
fusions with the update fused into them (a fusion is booked by the
matmul inside it, ``perfbench/scope_reduce.py``)."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "bwd")
